"""Loss-bearing target tokens of every step completed in the window, over
the window's wall time, over the chips."""


def read(ctx):
    return ctx["tokens_per_step"] * ctx["steps"] / ctx["window_s"] \
        / ctx["chips"]
