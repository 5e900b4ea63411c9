"""KernelBackend — pluggable local-compute primitives for the factorizations.

The COnfLUX schedule (and its 2D/sequential siblings) spends essentially all
FLOPs in three local primitives (paper Algorithm 1): the masked panel LUP of
the tournament (step 2), the triangular solves producing L10/U01 (steps 4/5),
and the rank-v Schur update (step 6).  A `KernelBackend` packages one
implementation of those primitives; the strategies call the backend instead
of inlining jnp math, so swapping "ref" (pure jnp, any dtype) for "pallas"
(the MXU-tiled kernels — interpret mode on CPU, Mosaic on TPU), or adding a
future fused backend, touches no schedule code.  The follow-up paper
(arXiv:2108.09337) builds Cholesky/QR from the same local kernels, and the
Cholesky family (`repro.core.cholesky`) is the first such consumer: it adds
only the SPD `panel_chol` primitive and reuses the TRSMs and Schur update.

Selection flows from `SolverConfig.backend` through plan resolution
(`repro.api.plan.resolve`), which validates the name and auto-falls back
`pallas -> ref` (with a warning) when the plan violates the kernels' tiling
constraints — see `pallas_constraint_violation` for the exact rules.
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lu.sequential import masked_lup


@runtime_checkable
class KernelBackend(Protocol):
    """The paper's local compute primitives, one jax-traceable method each.

    Every method is called from inside traced code (a `fori_loop` step body
    under `shard_map`/`jit`), so implementations must be pure functions of
    their array arguments.
    """

    name: str

    def panel_lup(self, panel: jax.Array, weights: jax.Array, v: int):
        """Masked LUP of an [R, v] panel; rows with weight 0 are untouched.

        Returns (F [R, v] packed factors, order [v] int32 pivot rows,
        ok [v] bool validity)."""
        ...

    def panel_chol(self, A: jax.Array) -> jax.Array:
        """Lower Cholesky factor of an SPD diagonal block A [v, v] = L L^T.

        The SPD analogue of `panel_lup`: no pivoting, no masking (positive
        pivots are guaranteed).  Returns L with a zeroed upper triangle."""
        ...

    def trsm_right_upper(self, B: jax.Array, U: jax.Array) -> jax.Array:
        """X U = B  ->  X = B U^-1.  B [R, v], U [v, v] upper (L10, step 4)."""
        ...

    def trsm_left_lower(self, L: jax.Array, B: jax.Array, *, unit: bool = True) -> jax.Array:
        """L X = B  ->  X = L^-1 B.  L [v, v] (unit-)lower, B [v, C] (U01, step 5)."""
        ...

    def schur_update(self, A: jax.Array, L: jax.Array, U: jax.Array) -> jax.Array:
        """A - L @ U.  A [M, N], L [M, K], U [K, N] (rank-v update, step 6)."""
        ...

    def fused_trsm_schur(self, A: jax.Array, L00: jax.Array, R01: jax.Array,
                         L10: jax.Array, *, unit: bool = True, c_start=0):
        """Steps 5+6 fused: U01 = L00^-1 R01, then A - L10 @ U01.

        Returns (A_new, U01).  The fused form keeps U01 resident between the
        triangular solve and the trailing update (no HBM round-trip); the
        forward substitution is columnwise independent, so the result is
        bit-compatible with the trsm_left_lower -> schur_update composition.
        `c_start` (an int32 scalar, traced or not) is the first column that
        can change: R01 is zero left of it, so a backend may leave those
        columns of A, and of U01 (zero), as they are.
        """
        ...

    # -- batched variants: one leading batch axis, B independent systems ----
    #
    # The many-small-systems path (`plan((B, N))`) runs every primitive on a
    # stack of systems at once.  "ref" implements these as `jax.vmap` of its
    # single-system methods — guaranteeing bit-identity with a vmapped
    # single-system plan — while "pallas" launches the batch-grid kernels
    # (one (b, tile...) program per tile, a single launch for all B systems).

    def panel_lup_batched(self, panel: jax.Array, weights: jax.Array, v: int):
        """Masked LUP of B panels [B, R, v]; returns (F [B, R, v],
        order [B, v] int32, ok [B, v] bool)."""
        ...

    def panel_chol_batched(self, A: jax.Array) -> jax.Array:
        """Lower Cholesky factors of B SPD blocks A [B, v, v]."""
        ...

    def trsm_right_upper_batched(self, B: jax.Array, U: jax.Array) -> jax.Array:
        """Per-system X_b U_b = B_b.  B [Bb, R, v], U [Bb, v, v] upper."""
        ...

    def trsm_left_lower_batched(self, L: jax.Array, B: jax.Array, *,
                                unit: bool = True) -> jax.Array:
        """Per-system L_b X_b = B_b.  L [Bb, v, v] (unit-)lower, B [Bb, v, C]."""
        ...

    def schur_update_batched(self, A: jax.Array, L: jax.Array,
                             U: jax.Array) -> jax.Array:
        """Per-system A_b - L_b @ U_b.  A [B, M, N], L [B, M, K], U [B, K, N]."""
        ...

    def fused_trsm_schur_batched(self, A: jax.Array, L00: jax.Array,
                                 R01: jax.Array, L10: jax.Array, *,
                                 unit: bool = True):
        """Per-system fused steps 5+6; returns (A_new [B, M, C], U01 [B, v, C])."""
        ...


_BACKENDS: dict[str, KernelBackend] = {}


def register_backend(name: str, backend: KernelBackend, *, overwrite: bool = False) -> None:
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered (pass overwrite=True)")
    _BACKENDS[name] = backend


def get_backend(name: str) -> KernelBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def pallas_constraint_violation(dtype, v: int | None) -> str | None:
    """Why the resolved plan cannot run on the Pallas kernels (None = it can).

    `dtype` is the *compute* dtype the kernels would run in — callers pass
    `SolverConfig.effective_compute_dtype`, so `dtype='float64'` with
    `compute_dtype='float32'` keeps the pallas kernels.  The rules mirror the
    hardware the kernels are tiled for: the MXU/VPU have no float64 path (the
    kernels accumulate in fp32), and the VPU's minimum tile is (8, 128) for
    4-byte and (16, 128) for 2-byte dtypes, so unaligned panel widths would
    force ragged sublane masking the kernels do not implement.
    """
    dt = np.dtype(dtype)
    if dt.itemsize > 4:
        return (
            f"dtype {dt.name} exceeds the fp32 accumulation the "
            f"MXU-tiled kernels provide"
        )
    sublane = 8 * (4 // dt.itemsize)
    if v is not None and (v < sublane or v % sublane):
        return (
            f"panel width v={v} is not a multiple of the {sublane}-sublane "
            f"VPU tile for {dt.name}"
        )
    return None


def _wants_f32_accum(*arrays) -> bool:
    """True when the inputs are sub-4-byte floats (bf16/f16): the ref
    primitives then compute in fp32 and round the result back, matching the
    fp32 accumulation scratch the Pallas kernels use on those dtypes."""
    return jnp.dtype(arrays[0].dtype).itemsize < 4


class RefBackend:
    """Pure-jnp primitives — the numerics the strategies inlined before the
    dispatch layer existed, bit-for-bit on >= 4-byte dtypes: native-dtype
    solves and matmuls.  Sub-4-byte inputs (bf16/f16, the mixed-precision
    compute dtypes) are upcast to fp32 per primitive and rounded back on the
    way out — the same fp32-accumulation contract the Pallas kernels honor,
    so ref and pallas pick identical pivots on low-precision panels."""

    name = "ref"

    def panel_lup(self, panel, weights, v):
        if _wants_f32_accum(panel):
            F, order, ok = masked_lup(
                panel.astype(jnp.float32), weights.astype(jnp.float32), v
            )
            return F.astype(panel.dtype), order, ok
        return masked_lup(panel, weights, v)

    def panel_chol(self, A):
        # jnp.linalg.cholesky has no bf16/f16 lowering, so the upcast is
        # load-bearing here, not just an accumulation-precision choice.
        if _wants_f32_accum(A):
            return jnp.linalg.cholesky(A.astype(jnp.float32)).astype(A.dtype)
        return jnp.linalg.cholesky(A)

    def trsm_right_upper(self, B, U):
        if _wants_f32_accum(B):
            return jax.scipy.linalg.solve_triangular(
                U.astype(jnp.float32).T, B.astype(jnp.float32).T, lower=True
            ).T.astype(B.dtype)
        return jax.scipy.linalg.solve_triangular(U.T, B.T, lower=True).T

    def trsm_left_lower(self, L, B, *, unit=True):
        if _wants_f32_accum(B):
            return jax.scipy.linalg.solve_triangular(
                L.astype(jnp.float32), B.astype(jnp.float32), lower=True,
                unit_diagonal=unit,
            ).astype(B.dtype)
        return jax.scipy.linalg.solve_triangular(L, B, lower=True, unit_diagonal=unit)

    def schur_update(self, A, L, U):
        if _wants_f32_accum(A):
            out = A.astype(jnp.float32) - jnp.matmul(
                L, U, preferred_element_type=jnp.float32
            )
            return out.astype(A.dtype)
        return A - L @ U

    def fused_trsm_schur(self, A, L00, R01, L10, *, unit=True, c_start=0):
        del c_start  # the whole matrix: columns left of it come out unchanged
        U01 = self.trsm_left_lower(L00, R01, unit=unit)
        if _wants_f32_accum(A):
            out = A.astype(jnp.float32) - jnp.matmul(
                L10, U01, preferred_element_type=jnp.float32
            )
            return out.astype(A.dtype), U01
        return A - L10 @ U01, U01

    # Batched = vmap of the single-system methods, so a `plan((B, N))` on the
    # ref backend is bit-identical to `jax.vmap` over single-system plans.

    def panel_lup_batched(self, panel, weights, v):
        return jax.vmap(lambda p, w: self.panel_lup(p, w, v))(panel, weights)

    def panel_chol_batched(self, A):
        return jax.vmap(self.panel_chol)(A)

    def trsm_right_upper_batched(self, B, U):
        return jax.vmap(self.trsm_right_upper)(B, U)

    def trsm_left_lower_batched(self, L, B, *, unit=True):
        return jax.vmap(lambda l, b: self.trsm_left_lower(l, b, unit=unit))(L, B)

    def schur_update_batched(self, A, L, U):
        return jax.vmap(self.schur_update)(A, L, U)

    def fused_trsm_schur_batched(self, A, L00, R01, L10, *, unit=True):
        return jax.vmap(
            lambda a, l00, r01, l10: self.fused_trsm_schur(a, l00, r01, l10, unit=unit)
        )(A, L00, R01, L10)


class PallasBackend:
    """The MXU-tiled Pallas kernels (`repro.kernels.ops`); the ops wrappers
    auto-fit block sizes to the local shapes (largest divisor of each
    dimension not exceeding the 128x128 MXU tile, 256 for the long TRSM
    dimension; the single-system fused TRSM -> Schur grid takes the
    largest tiles its VMEM holds, `ops.schur_tiles`)."""

    name = "pallas"

    def panel_lup(self, panel, weights, v):
        from repro.kernels import ops

        F, order, ok = ops.lu_panel(panel, weights.astype(panel.dtype))
        return F, order, ok != 0

    def panel_chol(self, A):
        from repro.kernels import ops

        return ops.chol_panel(A)

    def trsm_right_upper(self, B, U):
        from repro.kernels import ops

        return ops.trsm_right_upper(B, U)

    def trsm_left_lower(self, L, B, *, unit=True):
        from repro.kernels import ops

        return ops.trsm_left_lower(L, B, unit=unit)

    def schur_update(self, A, L, U):
        from repro.kernels import ops

        return ops.schur_update(A, L, U)

    def fused_trsm_schur(self, A, L00, R01, L10, *, unit=True, c_start=0):
        from repro.kernels import ops

        return ops.fused_trsm_schur(A, L00, R01, L10, unit=unit, c_start=c_start)

    # Batched = the batch-grid kernels: one launch covers all B systems.

    def panel_lup_batched(self, panel, weights, v):
        from repro.kernels import ops

        F, order, ok = ops.lu_panel_batched(panel, weights.astype(panel.dtype))
        return F, order, ok != 0

    def panel_chol_batched(self, A):
        from repro.kernels import ops

        return ops.chol_panel_batched(A)

    def trsm_right_upper_batched(self, B, U):
        from repro.kernels import ops

        return ops.trsm_right_upper_batched(B, U)

    def trsm_left_lower_batched(self, L, B, *, unit=True):
        from repro.kernels import ops

        return ops.trsm_left_lower_batched(L, B, unit=unit)

    def schur_update_batched(self, A, L, U):
        from repro.kernels import ops

        return ops.schur_update_batched(A, L, U)

    def fused_trsm_schur_batched(self, A, L00, R01, L10, *, unit=True):
        from repro.kernels import ops

        return ops.fused_trsm_schur_batched(A, L00, R01, L10, unit=unit)


register_backend("ref", RefBackend())
register_backend("pallas", PallasBackend())
