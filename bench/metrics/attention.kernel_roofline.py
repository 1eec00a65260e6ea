"""Share of the device time per step of the ops under the
``attention_kernel`` scope (the splash kernels, and the scaling, layout and
row-sum ops around them) that the attention's roofline takes: for each
forward pass the traced steps ran (a call of the forward kernel: the
forward, and the recompute) and each backward pass (a call of the dk/dv
kernel, whose dq kernel is the same pass's), the larger of the pass's least
FLOPs over the chip's peak and of its least bytes over the HBM bandwidth
(``yardstick.attention_pass``).  Sizes from the cell's configuration (a
decoder's) and traffic, per chip."""

from bench import scopes, yardstick

SCOPE = "attention_kernel"
# the splash kernel whose calls count each pass, as the trace names it; only
# the calls under SCOPE count, the ops whose time the share divides by
PASSES = {"fwd": r"splash_mqa_fwd", "bwd": r"splash_mqa_dkv"}


def read(ctx):
    spec, mix = ctx["spec"], ctx["traffic"]
    peaks = yardstick.peaks(ctx["device_kind"])
    per_step = 0.0
    for kind, pattern in PASSES.items():
        work = yardstick.attention_pass(
            kind, mix["batch_per_worker"], mix["seq_len"],
            spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"])
        per_step += scopes.calls_per_step(ctx, pattern, SCOPE) * \
            yardstick.roofline_s(*work, peaks)
    return scopes.roofline_share(ctx, SCOPE, per_step)
