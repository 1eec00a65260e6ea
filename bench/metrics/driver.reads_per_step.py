"""Blocking device-to-host reads per step of ``Trainer.step_once``, from the
program's counter ``Trainer.host_reads`` as each ``trainer.read_metrics`` span
of the traced window stamps it: its rise from the first such span to the
last, over the steps between them."""

from bench import scopes


def read(ctx):
    reads = scopes.readings(ctx)["host_reads"]
    if len(reads) < 2:
        return None
    return (reads[-1] - reads[0]) / (len(reads) - 1)
