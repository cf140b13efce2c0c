"""Backend: programs compiled (or loaded from a persistent cache) inside
the window, from JAX's own compile events."""


def read(ctx):
    return ctx.compiles
