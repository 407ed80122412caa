"""Host ingest per history: the pipeline's produce stage (parse and pack
of the history files, ``history/fastpack.py`` and the native packer,
``history/encode.pack_row_matrices``), from ``PipelineStats.produce_busy_s``
summed over the window's passes, in ms per history checked."""


def read(ctx):
    c = ctx.counts
    if not c.get("histories"):
        return None
    return c["produce_s"] * 1e3 / c["histories"]
