"""Device idle time per step inside the host spans ``trainer.put_batch`` and
``trainer.dispatch`` (the batch put on the chips and the step's dispatch),
averaged over the cell's devices."""

from bench import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "launch")
