"""One host stage of the sidecar server's ``check`` op: the
``service.frame`` spans, the request decoded once its first bytes are
in, the reply encoded and sent; the median of the program's global
sketch ``service.stage_s{op=check,stage=frame}``, in ms.  Read after the
window: the warm-up's requests are in the sketch, which the median
leaves aside."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    want = (("op", "check"), ("stage", "frame"))
    for name, labels, sk in REGISTRY.items():
        if name == "service.stage_s" and labels == want and sk.count:
            return sk.quantile(0.5) * 1e3
    return None
