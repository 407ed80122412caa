"""The sidecar server's own wall time per ``check`` op, from its
``service.check_latency_s{op=check}`` sketch: the mean over the window's
requests, in ms."""


def read(ctx):
    c = ctx.counts
    if not c.get("server_requests"):
        return None
    return c["server_check_s"] * 1e3 / c["server_requests"]
