"""Device time per step in operations that are neither collectives nor the
combine kernel (the model's forward and backward, the optimiser and the
averaging's packing), averaged over the cell's devices."""


def read(ctx):
    tr = ctx["trace"]
    return 1e3 * tr["compute_s"] / tr["steps"]
