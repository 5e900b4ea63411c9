"""Schur-complement update kernel:  C = A - L @ U  (COnfLUX step 11).

The rank-v trailing update dominates LU FLOPs.  MXU-aligned tiling: (bm, bn)
output tiles with a (bm, bk)x(bk, bn) accumulation loop over the contraction
as the fastest grid dimension; the fp32 accumulator lives in VMEM scratch
across the k-steps (HBM -> VMEM -> MXU staging is explicit in the
BlockSpecs).  fp32 operands multiply at full fp32 dot precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision


def _kernel(a_ref, l_ref, u_ref, o_ref, acc_ref, *, k_axis: int):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = a_ref[...].astype(jnp.float32)

    acc_ref[...] -= jnp.dot(
        l_ref[...], u_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(l_ref.dtype),
    )

    @pl.when(k == pl.num_programs(k_axis) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _vmem(*block):
    return pl.BlockSpec(*block, memory_space=pltpu.VMEM)


def schur_update(A, L, U, *, bm: int = 128, bn: int = 128, bk: int = 128,
                 interpret: bool = False):
    """A [M,N] - L [M,K] @ U [K,N], tiled for the 128x128 MXU."""
    M, N = A.shape
    K = L.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, k_axis=2),
        grid=grid,
        in_specs=[
            _vmem((bm, bn), lambda i, j, k: (i, j)),
            _vmem((bm, bk), lambda i, j, k: (i, k)),
            _vmem((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=_vmem((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), A.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(A, L, U)


def schur_update_batched(A, L, U, *, bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: bool = False):
    """B independent rank-K updates from one launch:  A_b - L_b @ U_b.

    A [B,M,N], L [B,M,K], U [B,K,N]; grid (b, i, j, k) — one program per
    output tile per system, k (the contraction) fastest so the fp32 VMEM
    accumulator carries across the k-steps exactly as in the single-system
    kernel.
    """
    B, M, N = A.shape
    K = L.shape[2]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (B, M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, k_axis=3),
        grid=grid,
        in_specs=[
            _vmem((None, bm, bn), lambda b, i, j, k: (b, i, j)),
            _vmem((None, bm, bk), lambda b, i, j, k: (b, i, k)),
            _vmem((None, bk, bn), lambda b, i, j, k: (b, k, j)),
        ],
        out_specs=_vmem((None, bm, bn), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), A.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(A, L, U)
