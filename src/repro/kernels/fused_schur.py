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
the compute.

The single-system kernel visits only the trailing column window: column
tiles from j0 = min(c_start // bc, C // bc - 1) on, a grid whose column
extent C // bc - j0 is read from a prefetched scalar.  A and R01 are
aliased to A_new and U01, so the tiles left of j0 are never read or
written and come back as passed; a caller whose R01 is zero left of
c_start (the sequential LU's pre-masked pivot rows) gets exactly the
values a full grid would give, since there U01 = 0 and A - L10 @ 0 = A.
A call reads and writes the window's tiles of A once, plus one L10 tile
per grid step.  The tile sizes come from the shape
(`repro.kernels.ops.schur_tiles`: the largest that fit the scoped VMEM
double-buffered), because a grid step carries a fixed cost that small
tiles do not amortize: at 128 x 128 an f32 step moves ~144 KB, which
HBM delivers in less time than the step's fixed cost.

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


def _window_kernel(j0_ref, *refs, unit: bool):
    del j0_ref  # read by the index maps only
    _kernel(*refs, row_axis=1, unit=unit)


def fused_trsm_schur(A, L00, R01, L10, c_start=0, *, bm: int, bc: int,
                     unit: bool = True, interpret: bool = False):
    """(A - L10 @ L00^-1 R01, L00^-1 R01) over the column tiles from
    min(c_start // bc, C // bc - 1) on, in one grid.

    A [M, C], L00 [v, v] (unit-)lower, R01 [v, C], L10 [M, v]; c_start an
    int32 scalar (traced or not).  Columns of the tiles left of the first
    visited one come back as passed: A's in A_new, R01's in U01.
    Returns (A_new [M, C], U01 [v, C]).
    """
    M, C = A.shape
    v = L00.shape[0]
    assert M % bm == 0 and C % bc == 0
    j0 = jnp.minimum(jnp.asarray(c_start, jnp.int32) // bc, C // bc - 1)

    def spec(block, index):
        return _vmem(block, lambda j, i, j0_ref: index(j + j0_ref[0], i))

    # column tiles outer (from j0 on), row tiles inner
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C // bc - j0, M // bm),
        in_specs=[
            spec((bm, bc), lambda j, i: (i, j)),
            spec((v, v), lambda j, i: (0, 0)),
            spec((v, bc), lambda j, i: (0, j)),
            spec((bm, v), lambda j, i: (i, 0)),
        ],
        out_specs=[
            spec((bm, bc), lambda j, i: (i, j)),
            spec((v, bc), lambda j, i: (0, j)),
        ],
        scratch_shapes=[pltpu.VMEM((v, bc), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_window_kernel, unit=unit),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, C), A.dtype),
            jax.ShapeDtypeStruct((v, C), R01.dtype),
        ],
        # operand 0 is j0: A -> A_new and R01 -> U01 keep the unvisited tiles
        input_output_aliases={1: 0, 3: 1},
        interpret=interpret,
    )(j0[None], A, L00, R01, L10)


def schur_tile_share(N: int, v: int, bc: int) -> float:
    """Share of the full grid's tiles that `fused_trsm_schur` computes over
    one sequential LU of an N x N matrix in panels of width v, whose step t
    passes c_start = (t + 1) v with column tiles of width bc."""
    nt, steps = N // bc, N // v
    visited = sum(nt - min((t + 1) * v // bc, nt - 1) for t in range(steps))
    return visited / (steps * nt)


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
