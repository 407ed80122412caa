"""The sidecar client's packing (``history/encode.pack_histories`` of one
finished history), timed by the harness around the call, mean ms per
request of the window."""


def read(ctx):
    c = ctx.counts
    if not c.get("requests"):
        return None
    return c["pack_s"] * 1e3 / c["requests"]
