"""Set-up: process start to the first timed step, compile included, the
time spent reading the program's state for ``correct`` left out."""


def read(ctx):
    return ctx["setup_s"]
