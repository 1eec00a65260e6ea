"""The one traffic generator: seeded first-order-teacher token streams.

The semantics are those of the program's ``SyntheticTask`` (a learnable LM
task: token t+1 is ``perm[token t]`` with probability ``order_mix``, else
uniform), computed for every row of every batch at once instead of one
batch at a time.  A traffic mix is a JSON file of parameters beside this
module; every seed gives the same shapes, only the tokens differ.

Parameters of a mix:
    kind             "teacher_lm"
    workers          data-parallel workers, one per chip
    batch_per_worker rows per worker per step
    seq_len          target tokens per row (every position carries loss)
    source_len       source tokens per row (encoder input), 0 for none
    order_mix        probability that a token follows the teacher
    distinct_batches batches made in set-up; the window cycles through them
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("kind") != "teacher_lm":
        raise ValueError(f"traffic {name!r}: unknown kind {mix.get('kind')!r}")
    return mix


def teacher_rows(rng, perm, n_rows: int, seq_len: int, order_mix: float,
                 vocab: int) -> np.ndarray:
    """(n_rows, seq_len + 1) int32 token rows of the first-order teacher."""
    toks = np.empty((n_rows, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, n_rows)
    noise = rng.random((n_rows, seq_len)) > order_mix
    rand = rng.integers(0, vocab, (n_rows, seq_len), dtype=np.int32)
    for t in range(seq_len):
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], perm[toks[:, t]])
    return toks


def batches(mix: dict, vocab: int, seed: int) -> list:
    """``distinct_batches`` global batches (dicts of int32 numpy arrays).

    Rows of one batch are laid out worker-major: worker r holds rows
    ``[r * batch_per_worker, (r + 1) * batch_per_worker)``.  No two rows of
    any batch are drawn alike.
    """
    rng = np.random.default_rng([seed, 0x7EAC])
    perm = rng.permutation(vocab).astype(np.int32)
    rows = mix["batch_per_worker"] * mix["workers"]
    n = mix["distinct_batches"]
    toks = teacher_rows(rng, perm, n * rows, mix["seq_len"],
                        mix["order_mix"], vocab)
    out = []
    for i in range(n):
        blk = toks[i * rows:(i + 1) * rows]
        out.append({"tokens": blk[:, :-1], "labels": blk[:, 1:]})
    if mix["source_len"]:
        src = rng.integers(0, vocab, (n, rows, mix["source_len"]),
                           dtype=np.int32)
        for i, b in enumerate(out):
            b["src"] = src[i]
    return out


def tokens_per_step(mix: dict) -> int:
    """Loss-bearing target tokens of one global step."""
    return mix["batch_per_worker"] * mix["workers"] * mix["seq_len"]
