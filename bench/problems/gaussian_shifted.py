"""Small dense systems as MAGMA's batched-solver tests make them, with a
plain float64 reference.

A = G + 2 sqrt(n) I with G standard normal (the shift keeps every system
well conditioned, so an f32 answer is accurate to a few ulps of its scale),
b standard normal.  The reference is `numpy.linalg.solve` in float64; an
answer is judged by max|x - x_ref| / max|x_ref|.
"""

from __future__ import annotations

import numpy as np


def make_pool(seed: int, sizes, dtype: str = "float32"):
    """One (A, b) pair of host arrays per size, from the seed."""
    rng = np.random.default_rng([int(seed), 0])
    out = []
    for n in sizes:
        n = int(n)
        A = rng.standard_normal((n, n)).astype(dtype)
        A[np.diag_indices(n)] += np.asarray(2.0 * np.sqrt(n), dtype)
        out.append((A, rng.standard_normal(n).astype(dtype)))
    return out


def reference(A, b) -> np.ndarray:
    return np.linalg.solve(np.asarray(A, np.float64), np.asarray(b, np.float64))


def rel_error(x, x_ref) -> float:
    x = np.asarray(x, np.float64)
    if x.shape != x_ref.shape:
        return float("inf")
    return float(np.abs(x - x_ref).max() / np.abs(x_ref).max())
