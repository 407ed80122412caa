"""Staging per history: the pipeline's place stage (``device_put`` or
``shard_packed`` of each packed chunk), from
``PipelineStats.place_busy_s`` summed over the window's passes, in ms per
history checked."""


def read(ctx):
    c = ctx.counts
    if not c.get("histories"):
        return None
    return c["place_s"] * 1e3 / c["histories"]
