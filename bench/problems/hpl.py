"""HPL's problem and its acceptance test, with nothing taken from the solver.

netlib HPL 2.3: A and b have entries uniform on [-0.5, 0.5); a solution is
judged by the scaled residual

    ||A x - b||_inf / (eps (||A||_inf ||x||_inf + ||b||_inf) N)

computed here on the host in float64, with eps the machine epsilon of the
dtype the configuration states.
"""

from __future__ import annotations

import numpy as np


def make_pool(seed: int, n: int, count: int, dtype: str):
    """`count` systems (A [n, n], b [n]) made on the device from
    the seed, in one jitted call, in the dtype they are solved in."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    key = jax.random.wrap_key_data(jnp.asarray(words))

    @jax.jit
    def make(key):
        out = []
        for k in jax.random.split(key, count):
            ka, kb = jax.random.split(k)
            A = jax.random.uniform(ka, (n, n), dtype) - 0.5
            b = jax.random.uniform(kb, (n,), dtype) - 0.5
            out.append((A, b))
        return out

    return jax.block_until_ready(make(key))


def scaled_residuals(A, xs, bs, dtype: str) -> list[float]:
    """HPL's scaled residual of each (x, b) against one host matrix A."""
    n = A.shape[0]
    eps = float(np.finfo(np.dtype(dtype)).eps)
    X = np.stack([np.asarray(x, np.float64).reshape(n, -1) for x in xs])  # [k, n, r]
    B = np.stack([np.asarray(b, np.float64).reshape(n, -1) for b in bs])
    k = len(xs)
    r_inf = np.zeros(k)
    a_inf = 0.0
    for i in range(0, n, 1024):
        rows = np.asarray(A[i:i + 1024], np.float64)
        a_inf = max(a_inf, float(np.abs(rows).sum(axis=1).max()))
        R = np.einsum("ij,kjr->kir", rows, X) - B[:, i:i + 1024]
        r_inf = np.maximum(r_inf, np.abs(R).reshape(k, -1).max(axis=1))
    out = []
    for j in range(k):
        x_inf = float(np.abs(X[j]).max())
        b_inf = float(np.abs(B[j]).max())
        out.append(float(r_inf[j] / (eps * (a_inf * x_inf + b_inf) * n)))
    return out
