"""Device time per step of the ops of the averager's ``average`` and
``sync`` scopes (its collectives, and the packing and combining around
them), averaged over the cell's devices.  A TPU core runs the ops of its
"XLA Ops" line one after another, so this is time in which the step's
other ops wait: an asynchronous exchange (start and done ops) would count
its start and its done op, the wait in the done op included, and not the
transfer that runs behind other ops."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "average")
