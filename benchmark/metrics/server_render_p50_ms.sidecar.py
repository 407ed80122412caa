"""One host stage of the sidecar server's ``check`` op: the
``service.render`` span, the verdict tensors read back and rendered as
the reply's maps; the median of the program's global sketch
``service.stage_s{op=check,stage=render}``, in ms.  Read after the
window: the warm-up's requests are in the sketch, which the median
leaves aside."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    want = (("op", "check"), ("stage", "render"))
    for name, labels, sk in REGISTRY.items():
        if name == "service.stage_s" and labels == want and sk.count:
            return sk.quantile(0.5) * 1e3
    return None
