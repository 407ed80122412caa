"""Time the pipeline's place/check thread waited on host ingest per
history: the ``pipeline.wait`` span around its take from the producer's
queue, from the program's global ``pipeline.wait_s{on=produce}`` over its
``pipeline.histories``, in ms per history.  Read after the window: the
mean covers the warm-up pass too."""


def read(ctx):
    from jepsen_tpu.obs.metrics import REGISTRY

    n = REGISTRY.value("pipeline.histories")
    if not n:
        return None
    s = REGISTRY.value("pipeline.wait_s", on="produce")
    passes = ctx.counts.get("passes", 0) + 1
    return s * 1e3 / n, f"mean over {passes} passes, the warm-up's included"
