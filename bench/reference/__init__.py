"""Plain float32 references of the benchmark's model families.

Each family module defines ``init(spec, key)``, the benchmark's own
weights in the program's parameter layout, and ``loss(spec, params,
batch, mm)``, the mean next-token cross-entropy in straightforward
``jax.numpy``.  ``mm`` is the matrix product the reference computes with:
float32 at the highest precision for the reference, a lower precision for
the control (``common.matmul``).
"""
