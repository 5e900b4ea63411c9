"""Sequential LU with row masking — the jnp oracle for all distributed variants.

The paper's COnfLUX never swaps rows (§7.3): pivot rows are *masked* and the
pivot order is tracked as an index vector.  The packed factor matrix F keeps
every row in its original position; row r that was chosen as the k-th pivot
holds U[k, k:] in its trailing columns and L multipliers in columns < k.
`unpack_factors` reorders into the classic PA = LU triple.

`masked_lup` is the "ref" KernelBackend's panel primitive (see
`repro.kernels.backend`); `lu_masked_sequential` routes its panel LUP /
TRSM / Schur compute through whichever backend the plan selected.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


@functools.partial(jax.jit, static_argnames=("v",))
def masked_lup(panel: jax.Array, weights: jax.Array, v: int):
    """Masked LU with partial pivoting of a panel (R x v), selecting v pivot rows.

    panel:   [R, v] values (rows in original positions).
    weights: [R] candidate weights — 1.0 for selectable/active rows, 0.0 for
             rows that must keep their values (already pivoted, padding, or
             remote rows).  Rows with weight 0 receive no updates.

    Returns (F, order, piv_ok):
      F:     [R, v] packed factors in original row positions.
      order: [v] int32 — local row index chosen as pivot for each column.
      piv_ok:[v] bool — False when no admissible pivot remained (all-zero col).
    """
    R = panel.shape[0]

    def body(k, carry):
        F, w, order, ok = carry
        col = jnp.abs(F[:, k]) * w
        p = jnp.argmax(col)
        ok = ok.at[k].set(col[p] > 0.0)
        order = order.at[k].set(p.astype(jnp.int32))
        w = w.at[p].set(0.0)
        pivval = F[p, k]
        safe = jnp.where(jnp.abs(pivval) > 0.0, pivval, 1.0)
        active = w > 0.0
        mult = jnp.where(active, F[:, k] / safe, F[:, k])
        F = F.at[:, k].set(mult)
        colmask = (jnp.arange(v) > k).astype(F.dtype)
        upd = jnp.outer(jnp.where(active, mult, 0.0), F[p, :] * colmask)
        return (F - upd, w, order, ok)

    init = (panel, weights.astype(panel.dtype), jnp.zeros(v, jnp.int32), jnp.zeros(v, bool))
    F, _, order, ok = jax.lax.fori_loop(0, v, body, init)
    return F, order, ok


@functools.partial(jax.jit, static_argnames=("v", "backend"))
def lu_masked_sequential(A: jax.Array, v: int = 32, backend: str = "ref"):
    """Full masked LU of A [N, N] in panels of width v — the single-device
    oracle, with the local compute (panel LUP, TRSM, Schur update) routed
    through the named `KernelBackend` ("ref" = pure jnp, "pallas" = the
    MXU-tiled kernels).

    Returns (F, rows): packed factors in original row positions and the pivot
    order `rows` (global row index of the k-th pivot).  Equivalent to partial
    pivoting — at each panel the locally-best rows are chosen, like a
    single-processor tournament.
    """
    from repro.kernels.backend import get_backend

    bk = get_backend(backend)
    N = A.shape[0]
    assert N % v == 0, "N must be a multiple of the panel width v"
    nsteps = N // v

    def step(t, carry):
        F, active, rows = carry
        c0 = t * v
        with obs.scope(obs.LU_PANEL):
            panel = jax.lax.dynamic_slice(F, (0, c0), (N, v))
            Fp, order, _ = bk.panel_lup(panel, active, v)
            F = jax.lax.dynamic_update_slice(F, Fp, (0, c0))
            rows = jax.lax.dynamic_update_slice(rows, order.astype(jnp.int32), (c0,))
        with obs.scope(obs.LU_PIVOT_GATHER):
            piv_onehot = jax.nn.one_hot(order, N, dtype=F.dtype)  # [v, N]
            active = active * (1.0 - piv_onehot.sum(0))
            colmask = (jnp.arange(N) >= c0 + v).astype(F.dtype)  # [N]
            U00_packed = piv_onehot @ Fp  # [v, v] packed LU of the pivot block
            L00 = jnp.tril(U00_packed, -1) + jnp.eye(v, dtype=F.dtype)
            R01 = (piv_onehot @ F) * colmask[None, :]  # pivot rows, trailing cols
        with obs.scope(obs.LU_UPDATE):
            # Trailing update: A11 -= L10 @ U01.
            L10 = Fp * active[:, None]  # multipliers of still-active rows
            # Steps 5+6 fused: U01 = L00^-1 R01 and the trailing update in one
            # backend call (R01 is pre-masked, so U01 comes out masked columnwise
            # and the columns left of c0 + v need not be visited).
            F, U01 = bk.fused_trsm_schur(F, L00, R01, L10 * active[:, None],
                                         unit=True, c_start=c0 + v)
        with obs.scope(obs.LU_PIVOT_WRITEBACK):
            # Write U01 into the pivot rows' trailing columns.
            F = F * (1.0 - piv_onehot.sum(0)[:, None] * colmask[None, :]) + piv_onehot.T @ (
                U01 * colmask[None, :]
            )
        return (F, active, rows)

    init = (A, jnp.ones(N, A.dtype), jnp.zeros(N, jnp.int32))
    F, _, rows = jax.lax.fori_loop(0, nsteps, step, init)
    return F, rows


@functools.partial(jax.jit, static_argnames=("v", "backend"))
def lu_masked_sequential_batched(A: jax.Array, v: int = 32, backend: str = "ref"):
    """Masked LU of B independent systems A [B, N, N] in one traced program.

    The step body is the literal batched translation of
    `lu_masked_sequential` — every matmul gains a leading batch dimension and
    the local compute goes through the backend's `*_batched` primitives ("ref"
    = `jax.vmap` of the single-system primitives, so this function is
    bit-identical to `jax.vmap(lu_masked_sequential)`; "pallas" = the
    batch-grid kernels, one launch per step for all B systems).

    Returns (F [B, N, N], rows [B, N]).
    """
    from repro.kernels.backend import get_backend

    bk = get_backend(backend)
    B, N = A.shape[0], A.shape[1]
    assert N % v == 0, "N must be a multiple of the panel width v"
    nsteps = N // v

    def step(t, carry):
        F, active, rows = carry
        c0 = t * v
        with obs.scope(obs.LU_PANEL):
            panel = jax.lax.dynamic_slice(F, (0, 0, c0), (B, N, v))
            Fp, order, _ = bk.panel_lup_batched(panel, active, v)
            F = jax.lax.dynamic_update_slice(F, Fp, (0, 0, c0))
            rows = jax.lax.dynamic_update_slice(rows, order.astype(jnp.int32), (0, c0))
        with obs.scope(obs.LU_PIVOT_GATHER):
            piv_onehot = jax.nn.one_hot(order, N, dtype=F.dtype)  # [B, v, N]
            active = active * (1.0 - piv_onehot.sum(1))
            colmask = (jnp.arange(N) >= c0 + v).astype(F.dtype)  # [N]
            U00_packed = piv_onehot @ Fp  # [B, v, v]
            L00 = jnp.tril(U00_packed, -1) + jnp.eye(v, dtype=F.dtype)
            R01 = (piv_onehot @ F) * colmask[None, None, :]
        with obs.scope(obs.LU_UPDATE):
            L10 = Fp * active[:, :, None]
            F, U01 = bk.fused_trsm_schur_batched(
                F, L00, R01, L10 * active[:, :, None], unit=True
            )
        with obs.scope(obs.LU_PIVOT_WRITEBACK):
            F = F * (
                1.0 - piv_onehot.sum(1)[:, :, None] * colmask[None, None, :]
            ) + jnp.swapaxes(piv_onehot, 1, 2) @ (U01 * colmask[None, None, :])
        return (F, active, rows)

    init = (A, jnp.ones((B, N), A.dtype), jnp.zeros((B, N), jnp.int32))
    F, _, rows = jax.lax.fori_loop(0, nsteps, step, init)
    return F, rows


def unpack_factors(F: jax.Array, rows: jax.Array):
    """Packed masked factors -> (P, L, U) with P @ A = L @ U (P = row selection)."""
    n = F.shape[0]
    Fp = F[rows, :]
    L = jnp.tril(Fp, -1) + jnp.eye(n, dtype=F.dtype)
    U = jnp.triu(Fp)
    P = jax.nn.one_hot(rows, n, dtype=F.dtype)
    return P, L, U


def permutation_sign(perm) -> float:
    """Sign of the permutation `perm` (e.g. the pivot order `rows`), +1 or -1.

    sign = (-1)^(n - #cycles).  The cycle count is found without a Python
    loop over n: pointer-doubling label propagation reaches the minimum of
    every cycle in ceil(log2 n) vectorized rounds, and a cycle is counted
    where that minimum labels itself.
    """
    p = np.asarray(perm, dtype=np.int64)
    n = p.size
    if n == 0:
        return 1.0
    labels = np.arange(n)
    jump = p.copy()
    for _ in range(max(int(n - 1).bit_length(), 1)):
        labels = np.minimum(labels, labels[jump])
        jump = jump[jump]
    ncycles = int(np.count_nonzero(labels == np.arange(n)))
    return -1.0 if (n - ncycles) % 2 else 1.0


def reconstruct(F: jax.Array, rows: jax.Array):
    """Rebuild A (in original row order) from packed masked factors."""
    P, L, U = unpack_factors(F, rows)
    return P.T @ (L @ U)
