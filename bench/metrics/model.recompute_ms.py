"""Device time per step under ``rematted_computation`` inside the backward:
the forward recomputed by per-layer remat, averaged over the cell's
devices."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "recompute")
