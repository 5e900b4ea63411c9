"""Public jit'd wrappers for the Pallas kernels.

On a TPU the kernels compile with Mosaic; on any other platform they run
in interpret mode, which checks the kernel bodies' results but says
nothing about their speed.  `interpret=None` picks by platform.

Block sizes are auto-fit before jit: each requested tile (`bm`/`bn`/`bk`/
`br`/`bc`) is shrunk to the largest divisor of its array dimension that does
not exceed it and that Mosaic can tile — a multiple of 128 on the lane
(last) axis of a block, of 8 (16 for 2-byte dtypes) on the sublane axis —
or else the whole dimension, which Mosaic always accepts.  So a direct
`ops.schur_update` / `ops.trsm_*` call on a matrix smaller (or merely not a
multiple) of the 128/256 defaults works instead of tripping the kernels'
exact-cover assertions.  The single-system fused TRSM -> Schur kernel has
no fixed default: its tiles come from the shape (`schur_tiles`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import chol_panel as _cp
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_schur as _fs
from repro.kernels import lu_panel as _lp
from repro.kernels import mamba_scan as _ms
from repro.kernels import schur_update as _su
from repro.kernels import trsm as _tr


def _interp(flag):
    if flag is not None:
        return flag
    return jax.default_backend() != "tpu"


LANE = 128


def _sublane(x) -> int:
    return 8 * (4 // x.dtype.itemsize)


def _tiles(dim: int, align: int) -> list[int]:
    """The tiles a grid may cut `dim` into: the multiples of `align` (Mosaic's
    tiling) that divide it (exact cover), or the whole dim when none does."""
    return [d for d in range(align, dim + 1, align) if dim % d == 0] or [dim]


def _fit(block: int, dim: int, align: int) -> int:
    """Largest of `_tiles(dim, align)` not above `block`; the whole dim
    when none is."""
    return max((d for d in _tiles(dim, align) if d <= block), default=dim)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _schur_update(A, L, U, bm, bn, bk, interpret):
    return _su.schur_update(A, L, U, bm=bm, bn=bn, bk=bk, interpret=interpret)


def schur_update(A, L, U, bm=128, bn=128, bk=128, interpret=None):
    M, N = A.shape
    K = L.shape[1]
    return _schur_update(A, L, U, _fit(bm, M, _sublane(A)), _fit(bn, N, LANE),
                         _fit(bk, K, LANE), _interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lu_panel(panel, weights, interpret=None):
    return _lp.lu_panel(panel, weights, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_panel(A, interpret=None):
    return _cp.chol_panel(A, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def _trsm_right_upper(B, U, br, interpret):
    return _tr.trsm_right_upper(B, U, br=br, interpret=interpret)


def trsm_right_upper(B, U, br=256, interpret=None):
    return _trsm_right_upper(B, U, _fit(br, B.shape[0], _sublane(B)),
                             _interp(interpret))


@functools.partial(jax.jit, static_argnames=("bc", "unit", "interpret"))
def _trsm_left_lower(L, B, bc, unit, interpret):
    return _tr.trsm_left_lower(L, B, bc=bc, unit=unit, interpret=interpret)


def trsm_left_lower(L, B, bc=256, unit=True, interpret=None):
    return _trsm_left_lower(L, B, _fit(bc, B.shape[1], LANE), unit,
                            _interp(interpret))


# Scoped VMEM a Mosaic kernel may use by default on a v5e TensorCore.
SCOPED_VMEM = 16 * 2**20


def _pad(n: int, align: int) -> int:
    return -(-n // align) * align


def schur_vmem_bytes(bm: int, bc: int, v: int, dtype) -> int:
    """VMEM of the single-system fused TRSM -> Schur kernel at tiles
    (bm, bc): its blocks double-buffered (A in and out, L10, R01, U01,
    L00, padded to Mosaic's (sublane, 128) tiling), the f32 U scratch and
    the f32 product of one row step.  A column tile narrower than 128
    lanes (a whole narrow window) takes 128 in VMEM."""
    s = jnp.dtype(dtype).itemsize
    vs, vl, cl = _pad(v, 8 * (4 // s)), _pad(v, LANE), _pad(bc, LANE)
    blocks = (2 * bm * cl + bm * vl + 2 * vs * cl + vs * vl) * s
    return 2 * blocks + _pad(v, 8) * cl * 4 + bm * cl * 4


def schur_tiles(M: int, C: int, v: int, dtype) -> tuple[int, int]:
    """(bm, bc) of the single-system fused TRSM -> Schur grid for A [M, C]:
    of `_tiles` of each dimension, the largest whose VMEM fits SCOPED_VMEM
    (the fewer bytes among equal areas), else the smallest.  A grid step
    carries a fixed cost, so the tiles grow until VMEM stops them; a
    matrix that fits whole is one tile."""
    sub = 8 * (4 // jnp.dtype(dtype).itemsize)
    pairs = [(bm, bc) for bm in _tiles(M, sub) for bc in _tiles(C, LANE)]
    fit = [p for p in pairs if schur_vmem_bytes(*p, v, dtype) <= SCOPED_VMEM]
    if not fit:
        return min(pairs, key=lambda p: p[0] * p[1])
    return max(fit, key=lambda p: (p[0] * p[1], -schur_vmem_bytes(*p, v, dtype)))


@functools.partial(jax.jit, static_argnames=("bm", "bc", "unit", "interpret"))
def _fused_trsm_schur(A, L00, R01, L10, c_start, bm, bc, unit, interpret):
    return _fs.fused_trsm_schur(A, L00, R01, L10, c_start, bm=bm, bc=bc,
                                unit=unit, interpret=interpret)


def fused_trsm_schur(A, L00, R01, L10, bm=None, bc=None, unit=True,
                     interpret=None, c_start=0):
    """U01 = L00^-1 R01 and A - L10 @ U01 in one VMEM-resident grid, over
    the column tiles from the one holding `c_start` on (a traced int32
    scalar; R01 must be zero left of it).

    Tiles default to `schur_tiles` of the shape; a given `bm`/`bc` is fit
    like every other kernel's.  Returns (A_new, U01) — see
    `repro.kernels.fused_schur`.
    """
    M, C = A.shape
    tbm, tbc = schur_tiles(M, C, L00.shape[0], A.dtype)
    return _fused_trsm_schur(A, L00, R01, L10, jnp.asarray(c_start, jnp.int32),
                             _fit(bm or tbm, M, _sublane(A)),
                             _fit(bc or tbc, C, LANE), unit, _interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lu_panel_batched(panel, weights, interpret=None):
    return _lp.lu_panel_batched(panel, weights, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_panel_batched(A, interpret=None):
    return _cp.chol_panel_batched(A, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def _trsm_right_upper_batched(B, U, br, interpret):
    return _tr.trsm_right_upper_batched(B, U, br=br, interpret=interpret)


def trsm_right_upper_batched(B, U, br=256, interpret=None):
    return _trsm_right_upper_batched(B, U, _fit(br, B.shape[1], _sublane(B)),
                                     _interp(interpret))


@functools.partial(jax.jit, static_argnames=("bc", "unit", "interpret"))
def _trsm_left_lower_batched(L, B, bc, unit, interpret):
    return _tr.trsm_left_lower_batched(L, B, bc=bc, unit=unit,
                                       interpret=interpret)


def trsm_left_lower_batched(L, B, bc=256, unit=True, interpret=None):
    return _trsm_left_lower_batched(L, B, _fit(bc, B.shape[2], LANE), unit,
                                    _interp(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _schur_update_batched(A, L, U, bm, bn, bk, interpret):
    return _su.schur_update_batched(A, L, U, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)


def schur_update_batched(A, L, U, bm=128, bn=128, bk=128, interpret=None):
    _, M, N = A.shape
    K = L.shape[2]
    return _schur_update_batched(A, L, U, _fit(bm, M, _sublane(A)),
                                 _fit(bn, N, LANE), _fit(bk, K, LANE),
                                 _interp(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bc", "unit", "interpret"))
def _fused_trsm_schur_batched(A, L00, R01, L10, bm, bc, unit, interpret):
    return _fs.fused_trsm_schur_batched(A, L00, R01, L10, bm=bm, bc=bc,
                                        unit=unit, interpret=interpret)


def fused_trsm_schur_batched(A, L00, R01, L10, bm=128, bc=128, unit=True,
                             interpret=None):
    """Per-system U01 = L00^-1 R01 and A - L10 @ U01 from one launch.

    Returns (A_new, U01) with leading batch axes — see `repro.kernels.fused_schur`.
    """
    _, M, C = A.shape
    return _fused_trsm_schur_batched(A, L00, R01, L10, _fit(bm, M, _sublane(A)),
                                     _fit(bc, C, LANE), unit, _interp(interpret))


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bkv", "interpret")
)
def flash_attention(q, k, v, causal=True, window=None, softcap=None,
                    bq=128, bkv=128, interpret=None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        bq=bq, bkv=bkv, interpret=_interp(interpret),
    )


@functools.partial(jax.jit, static_argnames=("bd", "cs", "interpret"))
def mamba_scan(a, b, C, bd=512, cs=64, interpret=None):
    return _ms.mamba_scan(a, b, C, bd=bd, cs=cs, interpret=_interp(interpret))
