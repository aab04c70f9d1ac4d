"""Programs compiled inside the window: JAX's backend-compile events less
those the persistent cache served (JAX's own monitoring events)."""


def read(ctx):
    return ctx.compiles
