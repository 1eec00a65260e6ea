"""Device idle time per step inside the host span ``trainer.read_metrics``
(the step's blocking reads of its metrics), averaged over the cell's
devices."""

from bench import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "read")
