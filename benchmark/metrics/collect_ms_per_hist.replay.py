"""Verdict read-back per history: the pipeline's ``pipeline.collect``
span (waiting for each chunk's verdict and copying it to the host), from
the program's global ``pipeline.stage_busy_s{stage=collect}`` over its
``pipeline.histories``, in ms per history.  Read after the window: the
mean covers the warm-up pass too.  A program that does not account the
span reads nothing."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    n = REGISTRY.value("pipeline.histories")
    s = REGISTRY.value("pipeline.stage_busy_s", stage="collect")
    if not n or not s:
        return None
    passes = ctx.counts.get("passes", 0) + 1
    return s * 1e3 / n, f"mean over {passes} passes, the warm-up's included"
