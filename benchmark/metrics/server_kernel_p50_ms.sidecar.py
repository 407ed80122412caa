"""One host stage of the sidecar server's ``check`` op: the
``service.kernel`` span, the check program dispatched until its verdict
tensors are ready; the median of the program's global sketch
``service.stage_s{op=check,stage=kernel}``, in ms.  Read after the
window: the warm-up's requests, which carry the compile or the cache
load, are in the sketch, which the median leaves aside."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    want = (("op", "check"), ("stage", "kernel"))
    for name, labels, sk in REGISTRY.items():
        if name == "service.stage_s" and labels == want and sk.count:
            return sk.quantile(0.5) * 1e3
    return None
