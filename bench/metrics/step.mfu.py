"""Model FLOP utilisation of the train step over the traced window: the
model FLOPs of the traced steps (``yardstick.train_flops_per_step``, no
recompute) over the window's length, the chips and the chip's peak."""

from bench import yardstick


def read(ctx):
    tr = ctx["trace"]
    peak = yardstick.peaks(ctx["device_kind"]).flops
    return 100.0 * ctx["flops_per_step"] * tr["steps"] / (
        tr["window_s"] * ctx["chips"] * peak)
