"""Cholesky panel kernel — the SPD analogue of the masked panel LUP.

One program factorizes a [v, v] SPD diagonal block held entirely in VMEM:
v rounds of (sqrt the pivot -> scale the column -> symmetric rank-1 trailing
update), right-looking.  No pivoting and no row masking: SPD guarantees a
positive pivot at every step (paper follow-up arXiv:2108.09337 builds its
near-I/O-optimal Cholesky from exactly this local primitive plus the LU
TRSM/Schur kernels).  v <= 256 keeps the block far inside VMEM.

Mosaic cannot index a value with the loop counter, so column k and the
pivot are read with iota-masked sums (exact: one nonzero term), and the
column is turned into a row for the rank-1 update by the same masked sum
over the diagonal.

`chol_panel_batched` factorizes B independent SPD blocks from one launch
via a batch grid dimension — the many-small-systems path (per-user GP /
Kalman updates) where a single small block leaves the MXU idle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, l_ref):
    """The v sqrt/scale/rank-1 rounds on one [v, v] SPD block, fp32."""
    v = a_ref.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (v, v), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (v, v), 1)
    sub = rows[:, :1]

    def body(k, A):
        colk = jnp.sum(jnp.where(cols == k, A, 0.0), axis=1, keepdims=True)
        d = jnp.sqrt(jnp.sum(jnp.where(sub == k, colk, 0.0), axis=0,
                             keepdims=True))
        l = jnp.where(sub > k, colk / d, 0.0)  # [v, 1]
        A = jnp.where(cols == k, l + jnp.where(sub == k, d, 0.0), A)
        lrow = jnp.sum(jnp.where(rows == cols, l, 0.0), axis=0, keepdims=True)
        return A - l * lrow  # l is zero at rows/cols <= k

    A = jax.lax.fori_loop(0, v, body, a_ref[...].astype(jnp.float32))
    l_ref[...] = jnp.where(rows >= cols, A, 0.0).astype(l_ref.dtype)


def chol_panel(A, *, interpret: bool = False):
    """Lower Cholesky factor of an SPD block A [v, v]:  A = L @ L^T.

    Returns L [v, v] with an explicitly zeroed upper triangle.
    """
    v = A.shape[0]
    spec = pl.BlockSpec((v, v), lambda: (0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((v, v), A.dtype),
        interpret=interpret,
    )(A)


def chol_panel_batched(A, *, interpret: bool = False):
    """Lower Cholesky factors of B independent SPD blocks A [B, v, v].

    One (b,) grid program per block.  Returns L [B, v, v].
    """
    B, v, _ = A.shape
    spec = pl.BlockSpec((None, v, v), lambda b: (b, 0, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, v, v), A.dtype),
        interpret=interpret,
    )(A)
