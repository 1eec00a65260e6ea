"""Device time per step in the ``attention`` scope (each attention block
through its output projection) in the forward, the backward and the
recompute together, averaged over the cell's devices."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attention")
