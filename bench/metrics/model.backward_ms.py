"""Device time per step under ``transpose(jvp(forward))``: the backward pass,
the remat recompute included, averaged over the cell's devices."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "backward")
