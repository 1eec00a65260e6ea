"""Device time per step in the ``optimizer`` scope: the finite check and the
optimiser update, averaged over the cell's devices."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "optimizer")
