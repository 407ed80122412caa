"""Collective ops' share of the devices' busy time in the traced window
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all:
the psum / pmin of the reduced verdict), summed over the devices."""


def read(ctx):
    t = ctx.trace
    busy = sum(t.busy_s)
    if not t.devices or not busy:
        return None
    return 100.0 * t.collective_s / busy
