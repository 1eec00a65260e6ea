"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves to interpret mode off-TPU (so the same call
sites work in CPU tests) and to the native Mosaic kernel on a TPU backend.
It is resolved *before* the jitted call, so the choice is part of the jit
cache key: a trace made under one resolution is never reused under another.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.group_average import group_average_combine as _combine
from repro.kernels.group_average import (group_average_combine_multi
                                         as _combine_multi)
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.splash_attention import splash_attention as _splash


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(interpret) -> bool:
    return _default_interpret() if interpret is None else bool(interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _splash_jit(q, k, v, *, causal, block_q, block_k, interpret):
    return _splash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                   interpret=interpret)


def splash_attention(q, k, v, *, causal=True, block_q=512, block_k=1024,
                     interpret=None):
    return _splash_jit(q, k, v, causal=causal, block_q=block_q,
                       block_k=block_k, interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("inv_s", "interpret"))
def _combine_jit(w, recv, inv_s, *, interpret):
    return _combine(w, recv, inv_s, interpret=interpret)


def group_average_combine(w, recv, inv_s, *, interpret=None):
    return _combine_jit(w, recv, float(inv_s), interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("inv_s", "interpret"))
def _combine_multi_jit(ws, rs, inv_s, *, interpret):
    return _combine_multi(list(ws), list(rs), inv_s, interpret=interpret)


def group_average_combine_multi(ws, rs, inv_s, *, interpret=None):
    """One launch for a batch of independent bucket combines (overlap path)."""
    return _combine_multi_jit(list(ws), list(rs), float(inv_s),
                              interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rglru_jit(a, x, h0, *, interpret):
    return _rglru(a, x, h0, interpret=interpret)


def rglru_scan(a, x, h0=None, *, interpret=None):
    return _rglru_jit(a, x, h0, interpret=_resolve(interpret))
