"""The comparison that decides ``correct``.

Set-up drives the program's own train-step object through its first
``CHECKED_STEPS`` steps; the harness reads, from the program's state:

* the loss of each of those steps (``Trainer.step_once``'s return);
* per parameter leaf and replica, the norm of the first gradient as the
  optimiser got it: SGD's momentum after one step from zero is that
  gradient;
* per leaf and replica, the norm of the parameters' change over the
  checked steps.

After the window the plain reference (``reference/<family>.py``) follows
the same steps from the same weights and batches: float32 at the highest
precision, parameters stored in the configuration's dtype between steps,
SGD momentum, and WAGMA's group averaging and tau-sync by the paper's
schedule.  Three numbers are compared, each against its limit:

* ``loss_gap``: the largest relative gap between a step's losses;
* ``grad_gap``: over leaves and replicas, the largest gap between the
  program's and the reference's gradient norm, over the larger of that
  leaf's reference norm and the median leaf's;
* ``change_gap``: the same for the parameters' change, leaving out leaves
  whose reference gradient is under ``NOUGHT`` of the median leaf's (they
  move by round-off alone).
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as C

CHECKED_STEPS = 3
NOUGHT = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")
HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


# -- readings ---------------------------------------------------------------

@jax.jit
def leaf_norms(tree):
    """Per leaf, the float32 norm of each replica row: {path: (P,)}."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {C.path_name(p): jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)).reshape(x.shape[0], -1), axis=1))
        for p, x in flat}


@jax.jit
def change_norms(new, old):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def host(norms: dict) -> dict:
    return {k: np.asarray(jax.device_get(v), np.float64)
            for k, v in norms.items()}


# -- the paper's schedule ----------------------------------------------------

def _log2(x: int) -> int:
    if x < 1 or x & (x - 1):
        raise ValueError(f"{x} is not a power of two")
    return x.bit_length() - 1


def group_size(P: int, configured) -> int:
    """The paper's S = sqrt(P), a power of two, at least 2 from P = 4."""
    if configured:
        return int(configured)
    return 1 << max(1, _log2(P) // 2) if P >= 4 else P


def exchange_bits(P: int, S: int, t: int) -> tuple:
    """XOR bits of the butterfly stages of iteration t (Algorithm 1):
    stage r exchanges over bit (t log2 S + r) mod log2 P."""
    lp, ls = _log2(P), _log2(S)
    if ls == 0:
        return ()
    return tuple((t * ls + r) % lp for r in range(ls))


def average(replicas: list, t: int, train: dict, dtype):
    """WAGMA's averaging after the local update of iteration t."""
    P = len(replicas)
    f32 = [jax.tree.map(lambda a: a.astype(jnp.float32), p) for p in replicas]
    if (t + 1) % train["tau"] == 0:
        mean = jax.tree.map(lambda *xs: sum(xs) / P, *f32)
        out = [mean] * P
    else:
        S = group_size(P, train.get("group_size"))
        acc = f32
        for bit in exchange_bits(P, S, t):
            acc = [jax.tree.map(jnp.add, acc[r], acc[r ^ (1 << bit)])
                   for r in range(P)]
        out = [jax.tree.map(lambda a: a * (1.0 / S), a) for a in acc]
    return [jax.tree.map(lambda a: a.astype(dtype), p) for p in out]


# -- the reference run -------------------------------------------------------

def reference_readings(family, spec: dict, train: dict, params0, batches,
                       chips: int, t0: int, mode: str = "reference",
                       fault: str | None = None) -> dict:
    """Follow the checked steps in the reference (or its control/fault).

    ``params0``: the benchmark's initial weights (one replica, storage
    dtype); ``batches``: the checked steps' global batches (numpy, rows
    chip-major).  ``mode`` picks the matmul precision; ``fault`` plants
    "half_batch" (each replica's mean over the first half of its rows) for
    calibrating the limits.
    """
    mm = C.matmul(mode)
    dtype = jax.tree.leaves(params0)[0].dtype
    lr, mu = train["learning_rate"], train["momentum"]
    step = jax.jit(functools.partial(family.loss_and_grad, spec, mm))

    @jax.jit
    def update(p, g, m):
        m = jax.tree.map(lambda m, g: mu * m + g, m, g)
        p = jax.tree.map(lambda p, m: (p.astype(jnp.float32) - lr * m
                                       ).astype(p.dtype), p, m)
        return p, m

    reps = [params0] * chips
    moms = [jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params0)
            for _ in range(chips)]
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        rows = batch["tokens"].shape[0] // chips
        step_losses, grads = [], []
        for r in range(chips):
            take = rows // 2 if fault == "half_batch" else rows
            b = {k: jnp.asarray(v[r * rows:r * rows + take])
                 for k, v in batch.items()}
            f32 = jax.tree.map(lambda a: a.astype(jnp.float32), reps[r])
            loss, g = step(f32, b)
            step_losses.append(float(loss))
            grads.append(g)
            reps[r], moms[r] = update(reps[r], g, moms[r])
        if i == 0:
            grad_norms = _stack_norms(grads)
        del grads
        reps = average(reps, t0 + i, train, dtype)
        losses.append(float(np.mean(step_losses)))
    init = jax.tree.map(lambda a: a[None], params0)
    change = host(change_norms(_stack(reps), init))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _stack_norms(trees):
    return host(leaf_norms(_stack(trees)))


# -- the numbers -------------------------------------------------------------

def _worst(prog: dict, ref: dict, keep=None) -> float:
    ref_all = np.concatenate([ref[k] for k in sorted(ref)])
    median = float(np.median(ref_all))
    worst = 0.0
    for k in sorted(ref):
        if keep is not None and not keep[k].any():
            continue
        p, r = np.asarray(prog[k]), np.asarray(ref[k])
        gap = np.abs(p - r) / np.maximum(r, median)
        if keep is not None:
            gap = gap[keep[k]]
        worst = max(worst, float(gap.max()))
    return worst


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers from program and reference readings."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference parameter trees differ: "
                         f"{sorted(set(prog['grad_norms']) ^ set(ref['grad_norms']))}")
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_all = np.concatenate(list(ref["grad_norms"].values()))
    floor = NOUGHT * float(np.median(g_all))
    keep = {k: v >= floor for k, v in ref["grad_norms"].items()}
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _worst(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": _worst(prog["change_norms"], ref["change_norms"],
                             keep),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
