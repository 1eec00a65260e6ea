"""Blocking device-to-host reads per step of ``Trainer.step_once``, from the
program's counter ``Trainer.host_reads`` as each ``trainer.read_metrics`` span
of the traced window stamps it: its rise from the first such span to the
last, over the steps between them."""

from bench import scopes


def read(ctx):
    return scopes.stat_rise_per_step(ctx, "trainer.read_metrics",
                                     "host_reads")
