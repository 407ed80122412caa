"""The device's idle share of the traced window: 1 - (union of the
intervals in which an XLA op ran on the device) / window, averaged over
the devices, from the profiler trace.  Nothing without a device plane."""


def read(ctx):
    t = ctx.trace
    if not t.devices or not t.window_s:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
