"""Fused TRSM -> Schur megakernel:  U01 = L00^-1 R01,  A -= L10 @ U01.

COnfLUX steps 5+6 executed back-to-back keep U01 resident: the unfused path
materializes U01 to HBM after the triangular solve and immediately re-reads
it as the GEMM operand of the rank-v trailing update — one full [v, C]
round-trip per step that the schedule never requires.  Here one pallas_call
covers both: the grid walks column tiles in the outer dimension and row
tiles in the inner one, the forward substitution for a column tile runs
exactly once (first row step) into a VMEM scratch accumulator, and every
row step consumes that resident tile straight on the MXU.  Pallas's
pipelined BlockSpec staging double-buffers the A/L10 tiles in VMEM around
the compute, so the only HBM traffic is the tiles the update itself owns.

The substitution body is `trsm.forward_solve` (column independence makes
the fused result bit-compatible with the two-call composition), and the
update is the same fp32-accumulated `A - L @ U` as `schur_update.py` with
the whole v-contraction in one block, at full fp32 dot precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision
from repro.kernels.trsm import forward_solve


def _kernel(a_ref, l00_ref, r01_ref, l10_ref, o_ref, u_ref, u_acc, *,
            row_axis: int, unit: bool):
    # Row tiles are the fastest grid axis; (system,) column tile are slower.
    @pl.when(pl.program_id(row_axis) == 0)
    def _solve():
        # Forward substitution L00 @ U = R01 for this column tile, once per
        # column tile; U stays resident in VMEM for every row step below.
        X = forward_solve(l00_ref[...].astype(jnp.float32),
                          r01_ref[...].astype(jnp.float32), unit=unit)
        u_acc[...] = X
        u_ref[...] = X.astype(u_ref.dtype)

    o_ref[...] = (
        a_ref[...].astype(jnp.float32)
        - jnp.dot(l10_ref[...].astype(jnp.float32), u_acc[...],
                  preferred_element_type=jnp.float32,
                  precision=dot_precision(jnp.float32))
    ).astype(o_ref.dtype)


def _vmem(*block):
    return pl.BlockSpec(*block, memory_space=pltpu.VMEM)


def fused_trsm_schur(A, L00, R01, L10, *, bm: int = 128, bc: int = 128,
                     unit: bool = True, interpret: bool = False):
    """(A - L10 @ L00^-1 R01, L00^-1 R01) in one grid.

    A [M, C], L00 [v, v] (unit-)lower, R01 [v, C], L10 [M, v].
    Returns (A_new [M, C], U01 [v, C]).
    """
    M, C = A.shape
    v = L00.shape[0]
    bm, bc = min(bm, M), min(bc, C)
    assert M % bm == 0 and C % bc == 0
    grid = (C // bc, M // bm)  # column tiles outer, row tiles inner
    return pl.pallas_call(
        functools.partial(_kernel, row_axis=1, unit=unit),
        grid=grid,
        in_specs=[
            _vmem((bm, bc), lambda j, i: (i, j)),
            _vmem((v, v), lambda j, i: (0, 0)),
            _vmem((v, bc), lambda j, i: (0, j)),
            _vmem((bm, v), lambda j, i: (i, 0)),
        ],
        out_specs=[
            _vmem((bm, bc), lambda j, i: (i, j)),
            _vmem((v, bc), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, C), A.dtype),
            jax.ShapeDtypeStruct((v, C), R01.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((v, bc), jnp.float32)],
        interpret=interpret,
    )(A, L00, R01, L10)


def fused_trsm_schur_batched(A, L00, R01, L10, *, bm: int = 128, bc: int = 128,
                             unit: bool = True, interpret: bool = False):
    """B independent fused TRSM -> Schur steps from one launch.

    A [B, M, C], L00 [B, v, v] (unit-)lower, R01 [B, v, C], L10 [B, M, v].
    Grid (b, column tile, row tile) — each system's column tile solves its
    own U01 tile once (first row step) into VMEM scratch, then every row
    step of that system consumes the resident tile.
    Returns (A_new [B, M, C], U01 [B, v, C]).
    """
    B, M, C = A.shape
    v = L00.shape[1]
    bm, bc = min(bm, M), min(bc, C)
    assert M % bm == 0 and C % bc == 0
    grid = (B, C // bc, M // bm)  # row tiles fastest, per (system, column tile)
    return pl.pallas_call(
        functools.partial(_kernel, row_axis=2, unit=unit),
        grid=grid,
        in_specs=[
            _vmem((None, bm, bc), lambda b, j, i: (b, i, j)),
            _vmem((None, v, v), lambda b, j, i: (b, 0, 0)),
            _vmem((None, v, bc), lambda b, j, i: (b, 0, j)),
            _vmem((None, bm, v), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            _vmem((None, bm, bc), lambda b, j, i: (b, i, j)),
            _vmem((None, v, bc), lambda b, j, i: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, C), A.dtype),
            jax.ShapeDtypeStruct((B, v, C), R01.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((v, bc), jnp.float32)],
        interpret=interpret,
    )(A, L00, R01, L10)
