"""Verdict assembly per history: the pipeline's ``pipeline.assemble``
span (the per-history conversion of each chunk's verdict tensors after
the executor returns, or the fold of the reduced verdicts on a mesh),
from the program's global ``pipeline.stage_busy_s{stage=assemble}`` over
its ``pipeline.histories``, in ms per history.  Read after the window:
the mean covers the warm-up pass too."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    n = REGISTRY.value("pipeline.histories")
    if not n:
        return None
    s = REGISTRY.value("pipeline.stage_busy_s", stage="assemble")
    passes = ctx.counts.get("passes", 0) + 1
    return s * 1e3 / n, f"mean over {passes} passes, the warm-up's included"
