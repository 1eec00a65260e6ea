"""Device time per step in the ``forward`` scope (the model's forward pass,
its loss included, the backward and the recompute left out), averaged over
the cell's devices."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "forward")
