"""COnfLUX — near-communication-optimal 2.5D LU factorization (paper §7).

Layout.  P = Px*Py*c processors form a (px, py, pz) mesh.  A is distributed
v x v tile-block-cyclically over (px, py): global tile (bi, bj) lives on
(bi % Px, bj % Py) at local tile (bi // Px, bj // Py).  The pz axis holds the
2.5D replication layers: layer 0 stores the base matrix, and each layer
accumulates the Schur updates of the steps t with t % c == layer.  The true
current value of any entry is therefore the *sum over pz* of the local
partials — materialized lazily (the paper's "Reduce next block column").

Schedule per step t (Algorithm 1):
  1. reduce the panel block-column over pz                       (psum 'pz')
  2. tournament pivoting along px: local masked LUP -> butterfly (ppermute 'px')
  3. broadcast factored A00 + pivot ids to all py                (psum 'py')
  4. L10 := A10 U00^-1 on the owner column; broadcast along py   (psum 'py')
  5. gather pivot rows over (px, pz); U01 := L00^-1 R01          (psum 'px','pz')
  6. Schur update A11 -= L10 @ U01 on layer t % c                (local GEMM)
  7. write L10 / A00 / U01 into the output factors               (local)

Row masking: no row is ever moved; `active` weights mask pivoted rows and
the pivot order is tracked as an index vector (paper §7.3).

SPMD note (CPU backend).  A real deployment executes the step-1/4/5
collectives only on the processors the schedule involves (conditional on
py == t % Py or pz == t % c).  XLA:CPU's in-process communicator requires
every device to join every collective (conditional collectives deadlock its
rendezvous), so this port executes them unconditionally with masked
payloads — numerically identical, but the *executed* volume exceeds the
schedule's.  Communication volume is therefore accounted by
`lu_comm_volume`, which instruments the exact schedule (payload x group per
collective call site) the way the paper instruments MPI with Score-P.  The
TPU program is the same one: it too runs the unconditional, masked
collectives.  Running only the collectives the schedule involves is
ROADMAP 1.6.

Device scopes (`repro.obs`).  Each step of the windowed body runs in a
`jax.named_scope`: the steps that do the sequential schedule's jobs take its
names (`lu.panel`, `lu.pivot_gather`, `lu.update`, `lu.pivot_writeback`),
and the three it lacks have their own (`lu.panel_reduce`, `lu.tournament`,
`lu.bcast`).  The flat body, a test oracle, is scoped only where it shares
`pivot_panel`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.lu.cost_models import conflux_model
from repro.core.lu.grid import GridConfig
from repro.core.windows import window_bucket_index, window_buckets
from repro.kernels.backend import get_backend

# ---------------------------------------------------------------------------
# Block-cyclic layout helpers (shared with tests and the 2D baseline).
# ---------------------------------------------------------------------------

def block_cyclic_scatter(A: np.ndarray, Px: int, Py: int, v: int) -> np.ndarray:
    """A [N, N] -> blocks [Px, Py, R, C] with v x v tile-cyclic ownership.

    Global tile (bi, bj) = (li*Px + px, lj*Py + py), so splitting each tile
    axis into (local, owner) and hoisting the owner axes is the whole layout:
    one reshape/transpose instead of the O((N/v)^2) Python double loop.
    """
    N = A.shape[0]
    nbi = N // v
    T = A.reshape(nbi // Px, Px, v, nbi // Py, Py, v)  # [li, px, r, lj, py, c]
    return np.ascontiguousarray(
        T.transpose(1, 4, 0, 2, 3, 5).reshape(Px, Py, (nbi // Px) * v, (nbi // Py) * v)
    )


def block_cyclic_gather(blocks: np.ndarray, N: int, v: int) -> np.ndarray:
    """Inverse of block_cyclic_scatter."""
    Px, Py = blocks.shape[:2]
    nbi = N // v
    T = blocks.reshape(Px, Py, nbi // Px, v, nbi // Py, v)  # [px, py, li, r, lj, c]
    return np.ascontiguousarray(T.transpose(2, 0, 3, 4, 1, 5).reshape(N, N))


def _block_cyclic_scatter_loop(A: np.ndarray, Px: int, Py: int, v: int) -> np.ndarray:
    """Loop-form scatter kept as the oracle for the vectorized layout."""
    N = A.shape[0]
    nbi = N // v
    R, C = (nbi // Px) * v, (nbi // Py) * v
    out = np.zeros((Px, Py, R, C), A.dtype)
    for bi in range(nbi):
        for bj in range(nbi):
            li, lj = bi // Px, bj // Py
            out[bi % Px, bj % Py, li * v:(li + 1) * v, lj * v:(lj + 1) * v] = \
                A[bi * v:(bi + 1) * v, bj * v:(bj + 1) * v]
    return out


def _block_cyclic_gather_loop(blocks: np.ndarray, N: int, v: int) -> np.ndarray:
    """Loop-form gather kept as the oracle for the vectorized layout."""
    Px, Py = blocks.shape[:2]
    A = np.zeros((N, N), blocks.dtype)
    nbi = N // v
    for bi in range(nbi):
        for bj in range(nbi):
            li, lj = bi // Px, bj // Py
            A[bi * v:(bi + 1) * v, bj * v:(bj + 1) * v] = blocks[
                bi % Px, bj % Py, li * v:(li + 1) * v, lj * v:(lj + 1) * v
            ]
    return A


# ---------------------------------------------------------------------------
# The distributed factorization (shard_map body).
# ---------------------------------------------------------------------------

def _local_lu(cfg: GridConfig, pivot: str, backend: str, Aloc, *,
              hotloop: str = "windowed"):
    """Local program for device (px, py, pz).  Aloc: [1, 1, R, C] local block.

    pivot: "tournament" (COnfLUX, butterfly merge along px) or "partial"
    (ScaLAPACK-style column-by-column global argmax — the 2D baseline).
    backend: registered KernelBackend name ("ref" / "pallas") supplying the
    local compute primitives (panel LUP, TRSMs, Schur update).
    hotloop: "windowed" (shrinking trailing-column windows, indexed pivot-row
    gathers, fused TRSM->Schur — the default) or "flat" (the historical
    full-block step body, kept as the bit-parity oracle for the windowed
    path and for A/B wall-time rows in the benchmarks)."""
    bk = get_backend(backend)
    Px, Py, c, v, N = cfg.Px, cfg.Py, cfg.c, cfg.v, cfg.N
    px = jax.lax.axis_index("px")
    py = jax.lax.axis_index("py")
    pz = jax.lax.axis_index("pz")
    Aloc = Aloc[0, 0]
    R, C = Aloc.shape
    dtype = Aloc.dtype
    nsteps = N // v
    rounds = max(int(math.log2(Px)), 0)

    # Global ids of my local rows / cols (tile-cyclic).
    lrow = jnp.arange(R)
    lcol = jnp.arange(C)
    row_gid = ((lrow // v * Px + px) * v + lrow % v).astype(jnp.int32)
    col_gid = ((lcol // v * Py + py) * v + lcol % v).astype(jnp.int32)

    # Layer pz==0 holds the base matrix; other layers accumulate partials only.
    Aloc = jnp.where(pz == 0, Aloc, jnp.zeros_like(Aloc))
    Floc = jnp.zeros_like(Aloc)

    def tournament(panel_vals, weights):
        """Local masked LUP -> butterfly merge along px.  Returns packed A00
        factors [v, v] (in elimination order) and winners' global ids [v]."""
        with obs.scope(obs.LU_PANEL):
            _, order, ok = bk.panel_lup(panel_vals, weights, v)
            cand_vals = panel_vals[order, :]  # original values of local winners
            valid = ok & (weights[order] > 0)
            cand_gids = jnp.where(valid, row_gid[order], -1)
        with obs.scope(obs.LU_TOURNAMENT):
            for r in range(rounds):
                perm = [(i, i ^ (1 << r)) for i in range(Px)]
                other_vals = jax.lax.ppermute(cand_vals, "px", perm)
                other_gids = jax.lax.ppermute(cand_gids, "px", perm)
                vals2 = jnp.concatenate([cand_vals, other_vals], axis=0)  # [2v, v]
                gids2 = jnp.concatenate([cand_gids, other_gids], axis=0)
                w2 = (gids2 >= 0).astype(dtype)
                _, order2, ok2 = bk.panel_lup(vals2, w2, v)
                cand_vals = vals2[order2, :]
                cand_gids = jnp.where(ok2, gids2[order2], -1)
            A00p, order_f, ok_f = bk.panel_lup(cand_vals, (cand_gids >= 0).astype(dtype), v)
            return A00p[order_f, :], jnp.where(ok_f, cand_gids[order_f], -1)

    def partial_pivot(panel_vals, weights):
        """ScaLAPACK-style panel factorization: per column, a global argmax
        over px picks the pivot; the pivot row is broadcast and eliminated.
        Same (A00, gids) interface as `tournament` (A00 in elimination order,
        already consistent on every px)."""

        def col_round(k, carry):
            F, w, A00, gids = carry
            col = jnp.abs(F[:, k]) * w
            lmax = jnp.max(col)
            larg = jnp.argmax(col)
            gmax = jax.lax.pmax(lmax, "px")
            cand = jnp.where((lmax == gmax) & (lmax > 0), row_gid[larg], -1)
            g = jax.lax.pmax(cand, "px")  # deterministic tie-break: larger gid
            mine = (row_gid == g).astype(dtype)  # [R] one-hot (zero if remote)
            prow = jax.lax.psum(mine @ F, "px")  # [v] packed pivot row
            pv = prow[k]
            safe = jnp.where(jnp.abs(pv) > 0, pv, 1.0)
            w = w * (1.0 - mine)
            active = w > 0
            mult = jnp.where(active, F[:, k] / safe, F[:, k])
            F = F.at[:, k].set(mult)
            colmask = (jnp.arange(v) > k).astype(dtype)
            F = F - jnp.outer(jnp.where(active, mult, 0.0), prow * colmask)
            return (F, w, A00.at[k].set(prow), gids.at[k].set(g))

        init = (panel_vals, weights, jnp.zeros((v, v), dtype), jnp.full((v,), -1, jnp.int32))
        with obs.scope(obs.LU_PANEL):
            _, _, A00, gids = jax.lax.fori_loop(0, v, col_round, init)
        return A00, gids

    def pivot_panel(t, panel, active):
        """Steps 2+3: pivot along px, broadcast A00 + ids from the owner
        column — shared verbatim by the flat and windowed step bodies (the
        windowed path must keep the pivot order bit-identical)."""
        is_owner_col = py == (t % Py)
        ow = is_owner_col.astype(dtype)
        if pivot == "tournament":
            A00, piv_gids = tournament(panel, active)
        else:
            A00, piv_gids = partial_pivot(panel, active)
        with obs.scope(obs.LU_BCAST):
            A00 = jax.lax.psum(A00 * ow, "py")
            piv_gids = jax.lax.psum(jnp.where(is_owner_col, piv_gids, 0), "py")
        return A00, piv_gids, ow

    def step_flat(t, carry):
        Aloc, Floc, active, rows = carry
        lc0 = (t // Py) * v  # local tile-column index of the panel (owner py)

        # -- 1. Reduce the panel block-column over pz. ------------------------
        my_panel = jax.lax.dynamic_slice(Aloc, (0, lc0), (R, v))
        panel = jax.lax.psum(my_panel, "pz")  # base + all pending partials

        # -- 2+3. Pivoting along px; broadcast A00 + ids to all py. ----------
        A00, piv_gids, ow = pivot_panel(t, panel, active)

        L00 = jnp.tril(A00, -1) + jnp.eye(v, dtype=dtype)
        U00 = jnp.triu(A00)
        S = (row_gid[:, None] == piv_gids[None, :]).astype(dtype)  # [R, v]
        is_new_piv = S.sum(1)
        new_active = active * (1.0 - is_new_piv)

        # -- 4. L10 on the owner column, broadcast along py. ------------------
        L10_own = bk.trsm_right_upper(panel * new_active[:, None], U00)
        L10 = jax.lax.psum(L10_own * ow, "py")  # [R, v]

        # -- 5. Pivot rows gathered over (px, pz); local TRSM -> U01. ---------
        R01 = jax.lax.psum(S.T @ Aloc, ("px", "pz"))  # [v, C] current values
        trailing = (col_gid >= (t + 1) * v).astype(dtype)  # [C]
        U01 = bk.trsm_left_lower(L00, R01, unit=True)
        U01 = U01 * trailing[None, :]

        # -- 6. Schur update on layer t % c (2.5D update partitioning), -------
        #    blocked to MXU-aligned tiles by the backend.
        on_layer = (pz == (t % c)).astype(dtype)
        Aloc = bk.schur_update(Aloc, L10 * (on_layer * new_active)[:, None], U01)

        # -- 7. Write factors (identical on every pz layer). ------------------
        # Panel column block: still-active rows get multipliers, new pivot
        # rows their packed A00 rows; rows pivoted in EARLIER steps keep the
        # U01 values written back then.
        prev = jax.lax.dynamic_slice(Floc, (0, lc0), (R, v))
        was_piv = (1.0 - active)[:, None]
        Fpanel = L10 * new_active[:, None] + S @ A00 + prev * was_piv
        panel_cols = (col_gid >= t * v) & (col_gid < (t + 1) * v)  # [C]
        Floc = jnp.where(
            panel_cols[None, :],
            jax.lax.dynamic_update_slice(Floc, Fpanel, (0, lc0)),
            Floc,
        )
        Floc = Floc + S @ U01  # new pivot rows' trailing columns

        rows = jax.lax.dynamic_update_slice(rows, piv_gids, (t * v,))
        return (Aloc, Floc, new_active, rows)

    # -- Windowed stepping (paper Lemma 10): at step t only columns with ------
    # gid >= t*v are read or written, and those are a *suffix* of the local
    # columns (tile-cyclic ownership is monotone in the local tile index), so
    # each bucketed body works on the static window Aloc[:, C - wc:].  Rows
    # cannot be windowed under pivoting — active rows stay scattered over the
    # whole local block (§7.3 row masking) — so the row dimension stays R.
    # Pivot-row movement is indexed (take / scatter-add on local row ids)
    # instead of the dense one-hot matmuls S.T@Aloc / S@A00 / S@U01, which
    # drops the O(v*R*C)-per-step gather cost the schedule never required.
    def pivot_local_rows(piv_gids):
        """Local row index + ownership mask of each pivot gid on this px."""
        tile = piv_gids // v
        lr = jnp.clip((tile // Px) * v + piv_gids % v, 0, R - 1)
        own = (tile % Px == px) & (piv_gids >= 0)
        return lr, own.astype(dtype)

    def make_windowed_step(rem_cap: int):
        WC = min(-(-rem_cap // Py), C // v)  # worst-case trailing tiles per py
        wc = WC * v
        c_start = C - wc

        def body(args):
            t, Aloc, Floc, active, rows = args
            with obs.scope(obs.LU_UPDATE):
                Awin = Aloc[:, c_start:]
            cg = col_gid[c_start:]
            lc0 = (t // Py) * v
            lc0w = jnp.clip(lc0 - c_start, 0, wc - v)  # owner never clips

            # -- 1. Reduce the panel block-column over pz (window slice). -----
            with obs.scope(obs.LU_PANEL_REDUCE):
                my_panel = jax.lax.dynamic_slice(Awin, (0, lc0w), (R, v))
                panel = jax.lax.psum(my_panel, "pz")

            # -- 2+3. Pivoting + broadcast (identical to the flat body). ------
            A00, piv_gids, ow = pivot_panel(t, panel, active)

            with obs.scope(obs.LU_PIVOT_GATHER):
                L00 = jnp.tril(A00, -1) + jnp.eye(v, dtype=dtype)
                U00 = jnp.triu(A00)
                lr, own = pivot_local_rows(piv_gids)
                is_new_piv = jnp.zeros((R,), dtype).at[lr].add(own)
                new_active = active * (1.0 - is_new_piv)

            # -- 4. L10 on the owner column, broadcast along py. --------------
            with obs.scope(obs.LU_PANEL):
                L10_own = bk.trsm_right_upper(panel * new_active[:, None], U00)
            with obs.scope(obs.LU_BCAST):
                L10 = jax.lax.psum(L10_own * ow, "py")  # [R, v]

            # -- 5. Pivot rows gathered by index over (px, pz). ---------------
            with obs.scope(obs.LU_PIVOT_GATHER):
                R01 = jax.lax.psum(
                    jnp.take(Awin, lr, axis=0) * own[:, None], ("px", "pz")
                )  # [v, wc] current values
                trailing = (cg >= (t + 1) * v).astype(dtype)
                R01 = R01 * trailing[None, :]  # columnwise: same U01 as masking after

            # -- 6. Fused TRSM -> Schur on layer t % c: U01 never leaves the --
            #    kernel between the solve and the trailing update.
            with obs.scope(obs.LU_UPDATE):
                on_layer = (pz == (t % c)).astype(dtype)
                Awin, U01 = bk.fused_trsm_schur(
                    Awin, L00, R01, L10 * (on_layer * new_active)[:, None], unit=True
                )

            # -- 7. Factor write-back: one v-wide panel slab + an indexed -----
            #    row scatter for the pivot rows' trailing columns.  The
            #    scatter covers whole local rows, U01 padded with zeros on
            #    the left: XLA:TPU cannot emit a scatter into a column slice
            #    that does not start at 0, and expands one into a loop over
            #    the v rows that copies the whole local Floc every row.  The
            #    padding is exact: a pivot row's columns left of the window
            #    gain +0, rows with own = 0 add zero rows.
            with obs.scope(obs.LU_PANEL):
                lc0c = jnp.clip(lc0, 0, C - v)
                prev = jax.lax.dynamic_slice(Floc, (0, lc0c), (R, v))
                was_piv = (1.0 - active)[:, None]
                SA00 = jnp.zeros((R, v), dtype).at[lr].add(A00 * own[:, None])
                Fpanel = L10 * new_active[:, None] + SA00 + prev * was_piv
                cgs = jax.lax.dynamic_slice(col_gid, (lc0c,), (v,))
                is_panel = (cgs >= t * v) & (cgs < (t + 1) * v)  # all-false off-owner
                Floc = jax.lax.dynamic_update_slice(
                    Floc, jnp.where(is_panel[None, :], Fpanel, prev), (0, lc0c)
                )
            with obs.scope(obs.LU_PIVOT_WRITEBACK):
                Floc = Floc.at[lr].add(
                    jnp.pad(U01 * own[:, None], ((0, 0), (c_start, 0)))
                )

            with obs.scope(obs.LU_UPDATE):
                Aloc = jax.lax.dynamic_update_slice(Aloc, Awin, (0, c_start))
            with obs.scope(obs.LU_PANEL):
                rows = jax.lax.dynamic_update_slice(rows, piv_gids, (t * v,))
            return (Aloc, Floc, new_active, rows)

        return body

    if hotloop == "windowed":
        bodies = [make_windowed_step(cap) for cap in window_buckets(nsteps)]

        def step(t, carry):
            return jax.lax.switch(
                window_bucket_index(t, nsteps), bodies, (t, *carry)
            )
    else:
        step = step_flat

    active0 = jnp.ones(R, dtype)
    rows0 = jnp.zeros(N, jnp.int32)
    _, Floc, _, rows = jax.lax.fori_loop(0, nsteps, step, (Aloc, Floc, active0, rows0))
    return Floc[None, None], rows


def make_lu_mesh(cfg: GridConfig, devices=None) -> jax.sharding.Mesh:
    devices = devices if devices is not None else jax.devices()
    need = cfg.Px * cfg.Py * cfg.c
    if len(devices) < need:
        raise ValueError(f"grid {cfg} needs {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(cfg.Px, cfg.Py, cfg.c)
    return jax.sharding.Mesh(arr, ("px", "py", "pz"))


# ---------------------------------------------------------------------------
# Instrumented communication volume of the schedule (elements, per processor).
# ---------------------------------------------------------------------------

def lu_comm_volume(N: int, grid: GridConfig, pivot: str = "tournament") -> dict:
    """Exact per-collective accounting of the COnfLUX schedule.

    For each collective call site we count the elements each *participating*
    processor transfers (ring all-reduce of payload S over g members:
    2*S*(g-1)/g per member; butterfly round: payload per member; masked
    broadcast: payload to each receiver), per step, summed over the schedule
    and averaged over all P — the paper's "communication volume per node".
    """
    Px, Py, c, v = grid.Px, grid.Py, grid.c, grid.v
    Ptot = Px * Py * c
    rounds = max(int(math.log2(Px)), 0)
    vol = dict.fromkeys(
        ("panel_reduce", "pivot_tournament", "a00_bcast", "l10_bcast", "u01_gather"), 0.0
    )
    for t in range(N // v):
        rem = max(N - (t + 1) * v, 0)  # trailing size
        rloc = (N - t * v) / Px  # panel rows per owner-column proc
        cloc = rem / Py  # trailing cols per proc
        # 1. panel reduce over pz: owner column only (Px procs x c layers).
        vol["panel_reduce"] += Px * c * (2 * rloc * v * (c - 1) / c)
        # 2. tournament butterfly on the owner column (values + ids per round).
        if pivot == "tournament":
            vol["pivot_tournament"] += Px * c * rounds * (v * v + v)
        else:  # partial pivoting: per column, argmax reduce + pivot-row psum
            vol["pivot_tournament"] += Px * c * v * (v + 2) * 2.0 * (Px - 1) / max(Px, 1)
        # 3. A00 + pivot ids broadcast to every proc.
        vol["a00_bcast"] += Ptot * (v * v + v)
        # 4. L10 broadcast along py — but only to layer t % c (the Schur
        #    owner), so Px * Py procs receive their rows' multipliers.
        vol["l10_bcast"] += Px * Py * rloc * v
        # 5. pivot-row gather + U01 to the Schur layer: v x cloc per proc.
        vol["u01_gather"] += Px * Py * v * cloc
    out = {k: val / Ptot for k, val in vol.items()}
    out["total"] = sum(out.values())
    out["model_lemma10"] = conflux_model(N, Ptot, M=max(N * N * c / Ptot, 4.0), v=v)
    return out
