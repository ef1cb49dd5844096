"""XLA program cache: programs compiled (or loaded from the persistent
cache) inside the measured window, from JAX's monitoring events.  The
warm-up should leave none."""


def read(ctx):
    return len(ctx.out.win.compiles)
