"""Programs lowered inside the measured window (each a compile or a
compile-cache load the warm-up missed), from JAX's monitoring events.
It should read 0."""


def read(ctx):
    return ctx.window_compiles
