"""Built-in strategies: sequential, conflux, baseline2d, auto (LU) and
sequential_chol, cholesky25d (SPD Cholesky on the same kernel backends).

Each strategy is a plan builder ``(N, config, mesh=None) -> FactorizationPlan``
plus an attached ``resolve(N, config) -> SolverConfig`` hook that pins the
open choices (grid, panel width, pivot) so the plan cache key is concrete.
Heavy modules (the shard_map program) are imported inside the builders so
`repro.api` stays import-light and cycle-free.
"""

from __future__ import annotations

import jax
import numpy as np

from repro import obs
from repro.api.config import SolverConfig, resolve_dtype
from repro.api.plan import FactorizationPlan
from repro.api.registry import register_strategy
from repro.core.lu.grid import optimize_grid, validate_layout
from repro.core.precision import full_precision


def _devices(out: jax.Array) -> tuple[str, ...]:
    """The devices holding the program's output shards, in shard order."""
    return tuple(str(s.device) for s in out.addressable_shards)


def _factor(fn, A, **args):
    """Upload A, then run the factor program `fn` to ready outputs; `args`
    go on its `repro.execute.factor` span."""
    A = obs.to_device(A)
    with obs.span(obs.FACTOR, **args):
        return jax.block_until_ready(fn(A))


def _compute_aval(shape: tuple[int, ...], config: SolverConfig):
    """Abstract input for static lowering: the traced programs see the
    matrix (or its block-cyclic shards) already cast to the compute dtype."""
    return jax.ShapeDtypeStruct(shape, resolve_dtype(config.effective_compute_dtype))

# ---------------------------------------------------------------------------
# sequential — single-device masked LU (the jnp oracle).
# ---------------------------------------------------------------------------


def default_panel_width(N: int, start: int = 32) -> int:
    """Largest v <= min(start, N) dividing N (the legacy shrink rule)."""
    v = min(start, N)
    while N % v:
        v -= 1
    return v


def _resolve_sequential(N: int, config: SolverConfig) -> SolverConfig:
    if config.pivot == "none":
        raise ValueError(
            "pivot='none' is Cholesky-only (SPD needs no pivoting); LU "
            "strategies need 'tournament' or 'partial'"
        )
    v = config.v
    if v is None:
        v = default_panel_width(N)
    elif not 1 <= v <= N or N % v:
        raise ValueError(
            f"sequential strategy needs a panel width dividing N: v={v}, N={N}"
        )
    return config.with_(v=v, grid=None)


@register_strategy("sequential")
def build_sequential(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    from repro.core.lu.sequential import (
        lu_masked_sequential,
        lu_masked_sequential_batched,
    )

    v = config.v
    backend = config.backend
    batched = config.B is not None
    p = FactorizationPlan(N, config)

    def _traced(A):
        p._note_trace()
        if batched:
            return lu_masked_sequential_batched(A, v=v, backend=backend)
        return lu_masked_sequential(A, v=v, backend=backend)

    fn = jax.jit(full_precision(_traced))
    shape = (N, N) if config.B is None else (config.B, N, N)
    aval = _compute_aval(shape, config)
    # The single-system Pallas update visits only the trailing column
    # window; the batched kernel and the ref backend update every column.
    share = 1.0
    if backend == "pallas" and not batched:
        from repro.kernels.fused_schur import schur_tile_share
        from repro.kernels.ops import schur_tiles

        share = schur_tile_share(N, v, schur_tiles(N, N, v, aval.dtype)[1])

    def run(A):
        F, rows = _factor(fn, A, schur_tile_share=share)
        return obs.to_host(F), obs.to_host(rows).astype(np.int64), _devices(F)

    p._run = run
    p._fn = fn
    p._in_avals = (aval,)
    return p


build_sequential.resolve = _resolve_sequential


# ---------------------------------------------------------------------------
# conflux — the 2.5D near-communication-optimal schedule (paper §7).
# ---------------------------------------------------------------------------


def _reject_batched(strategy: str, config: SolverConfig) -> None:
    if config.B is not None:
        raise ValueError(
            f"strategy {strategy!r} shards one large matrix and does not "
            f"support batched plans (B={config.B}); use 'sequential' / "
            f"'sequential_chol' (or 'auto') for the many-small-systems path"
        )


def _resolve_conflux(N: int, config: SolverConfig) -> SolverConfig:
    _reject_batched("conflux", config)
    if config.pivot == "none":
        raise ValueError(
            "pivot='none' is Cholesky-only (SPD needs no pivoting); LU "
            "strategies need 'tournament' or 'partial'"
        )
    if config.grid is not None:
        return config
    P_target = config.P_target or len(jax.devices())
    try:
        grid = optimize_grid(N, P_target, config.M, v=config.v)
    except ValueError as e:
        raise ValueError(
            f"{e}; or pin the grid yourself: SolverConfig(grid=GridConfig(Px, Py, "
            f"c, v, N={N}))"
        ) from e
    return config.with_(grid=grid)


def _blocks_shape(N: int, grid) -> tuple[int, int, int, int]:
    """Shape of the block-cyclic scatter output fed to the shard_map plans."""
    nbi = N // grid.v
    return (grid.Px, grid.Py, (nbi // grid.Px) * grid.v, (nbi // grid.Py) * grid.v)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# How every block-cyclic plan lays its [Px, Py, R, C] blocks on the mesh:
# one block per (px, py), the same block on every pz layer.
_BLOCKS = ("px", "py", None, None)


def _factor_blocks(fn, A, grid, mesh):
    """Lay A out block-cyclically on the host, upload the blocks to their
    devices, and run the factor program `fn` to ready outputs."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.lu.conflux import block_cyclic_scatter

    with obs.span(obs.LAYOUT, bytes=A.nbytes):
        blocks = block_cyclic_scatter(A, grid.Px, grid.Py, grid.v)
    return _factor(fn, obs.to_devices(blocks, NamedSharding(mesh, P(*_BLOCKS))))


def _gather_blocks(Fblocks, grid):
    """Download the factor blocks and undo the block-cyclic layout."""
    from repro.core.lu.conflux import block_cyclic_gather

    host = obs.to_host(Fblocks)
    with obs.span(obs.LAYOUT, bytes=host.nbytes):
        return block_cyclic_gather(host, grid.N, grid.v)


def _build_shardmap_plan(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    """Shared builder for every block-cyclic shard_map strategy."""
    from jax.sharding import PartitionSpec as P

    from repro.core.lu.conflux import _local_lu, lu_comm_volume, make_lu_mesh

    grid = config.grid
    if grid is None:
        raise ValueError(f"strategy {config.strategy!r} needs a resolved grid")
    validate_layout(N, grid, pivot=config.pivot)
    mesh = mesh or make_lu_mesh(grid)
    p = FactorizationPlan(
        N, config, grid=grid, mesh=mesh,
        comm=lu_comm_volume(N, grid, pivot=config.pivot),
    )

    def _traced(blocks):
        p._note_trace()
        return _local_lu(grid, config.pivot, config.backend, blocks,
                         hotloop=config.hotloop)

    fn = jax.jit(
        _shard_map(
            full_precision(_traced),
            mesh=mesh,
            in_specs=P(*_BLOCKS),
            out_specs=(P(*_BLOCKS), P()),
        )
    )

    def run(A):
        Fblocks, rows = _factor_blocks(fn, A, grid, mesh)
        F = _gather_blocks(Fblocks, grid)
        return F, obs.to_host(rows).astype(np.int64), _devices(Fblocks)

    p._run = run
    p._fn = fn
    p._in_avals = (_compute_aval(_blocks_shape(N, grid), config),)
    return p


@register_strategy("conflux")
def build_conflux(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    return _build_shardmap_plan(N, config, mesh=mesh)


build_conflux.resolve = _resolve_conflux


# ---------------------------------------------------------------------------
# baseline2d — ScaLAPACK/LibSci-style 2D grid with partial pivoting (§8).
# ---------------------------------------------------------------------------


def _resolve_baseline2d(N: int, config: SolverConfig) -> SolverConfig:
    from repro.core.lu.baseline2d import scalapack2d_grid

    _reject_batched("baseline2d", config)
    changes: dict = {}
    if config.pivot != "partial":
        changes["pivot"] = "partial"  # the 2D baseline is defined by it
    if config.grid is None:
        P_target = config.P_target or len(jax.devices())
        changes["grid"] = scalapack2d_grid(N, P_target, v=config.v or 32)
    return config.with_(**changes) if changes else config


@register_strategy("baseline2d")
def build_baseline2d(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    return _build_shardmap_plan(N, config, mesh=mesh)


build_baseline2d.resolve = _resolve_baseline2d


# ---------------------------------------------------------------------------
# cholesky25d / sequential_chol — the SPD family (arXiv:2108.09337) on the
# same kernel-backend layer: no pivoting, symmetric rank-v Schur update,
# roughly half the FLOPs and communication of the LU siblings.
# ---------------------------------------------------------------------------


def _resolve_sequential_chol(N: int, config: SolverConfig) -> SolverConfig:
    v = config.v
    if v is None:
        v = default_panel_width(N)
    elif not 1 <= v <= N or N % v:
        raise ValueError(
            f"sequential_chol strategy needs a panel width dividing N: v={v}, N={N}"
        )
    # Pivoting is meaningless for SPD: normalize so every requested pivot
    # resolves to (and cache-shares) the same plan.
    return config.with_(v=v, grid=None, pivot="none")


@register_strategy("sequential_chol")
def build_sequential_chol(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    from repro.core.cholesky.sequential import (
        chol_blocked_sequential,
        chol_blocked_sequential_batched,
    )

    v = config.v
    backend = config.backend
    batched = config.B is not None
    p = FactorizationPlan(N, config, kind="cholesky")

    def _traced(A):
        p._note_trace()
        if batched:
            return chol_blocked_sequential_batched(A, v=v, backend=backend)
        return chol_blocked_sequential(A, v=v, backend=backend)

    fn = jax.jit(full_precision(_traced))

    def run(A):
        L = _factor(fn, A)
        if batched:
            rows = np.broadcast_to(
                np.arange(N, dtype=np.int64), (config.B, N)
            ).copy()
            return obs.to_host(L), rows, _devices(L)
        return obs.to_host(L), np.arange(N, dtype=np.int64), _devices(L)

    p._run = run
    p._fn = fn
    shape = (N, N) if config.B is None else (config.B, N, N)
    p._in_avals = (_compute_aval(shape, config),)
    return p


build_sequential_chol.resolve = _resolve_sequential_chol


def _resolve_cholesky25d(N: int, config: SolverConfig) -> SolverConfig:
    from repro.core.cholesky.conflux25d import chol_comm_volume

    _reject_batched("cholesky25d", config)
    changes: dict = {"pivot": "none"} if config.pivot != "none" else {}
    if config.grid is None:
        P_target = config.P_target or len(jax.devices())
        changes["grid"] = optimize_grid(
            N, P_target, config.M, v=config.v, volume=chol_comm_volume,
        )
    return config.with_(**changes) if changes else config


@register_strategy("cholesky25d")
def build_cholesky25d(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    from jax.sharding import PartitionSpec as P

    from repro.core.cholesky.conflux25d import _local_chol, chol_comm_volume
    from repro.core.lu.conflux import make_lu_mesh

    grid = config.grid
    if grid is None:
        raise ValueError("strategy 'cholesky25d' needs a resolved grid")
    validate_layout(N, grid, pivot=config.pivot)  # "none": no pow-2 Px needed
    mesh = mesh or make_lu_mesh(grid)
    p = FactorizationPlan(
        N, config, grid=grid, mesh=mesh,
        comm=chol_comm_volume(N, grid), kind="cholesky",
    )

    def _traced(blocks):
        p._note_trace()
        return _local_chol(grid, config.backend, blocks, hotloop=config.hotloop)

    fn = jax.jit(
        _shard_map(
            full_precision(_traced),
            mesh=mesh,
            in_specs=P(*_BLOCKS),
            out_specs=P(*_BLOCKS),
        )
    )

    def run(A):
        Fblocks = _factor_blocks(fn, A, grid, mesh)
        L = _gather_blocks(Fblocks, grid)
        return L, np.arange(N, dtype=np.int64), _devices(Fblocks)

    p._run = run
    p._fn = fn
    p._in_avals = (_compute_aval(_blocks_shape(N, grid), config),)
    return p


build_cholesky25d.resolve = _resolve_cholesky25d


# ---------------------------------------------------------------------------
# auto — trace-calibrated wall-time argmin (auto v2), with the analytic
# Processor Grid Optimization comm-volume ranking as fallback.
# ---------------------------------------------------------------------------


def _resolve_auto_analytic(N: int, config: SolverConfig, n_dev: int) -> SolverConfig:
    """The original auto ranking: comm-volume argmin grid on >1 device,
    sequential otherwise.  Used when no calibration covers the combo."""
    if n_dev > 1:
        try:
            grid = optimize_grid(N, config.P_target or n_dev, config.M, v=config.v)
            return config.with_(strategy="conflux", grid=grid)
        except ValueError:
            pass  # no feasible distributed grid: fall through to sequential
    return _resolve_sequential(N, config.with_(strategy="sequential", grid=None))


def _resolve_auto(N: int, config: SolverConfig) -> SolverConfig:
    n_dev = len(jax.devices())
    if config.B is not None:
        # Batched = many small independent systems; the distributed schedules
        # shard one large matrix, so auto always picks the batched sequential.
        if config.grid is not None:
            raise ValueError(
                f"auto: batched plans (B={config.B}) are sequential-only; an "
                f"explicit grid {config.grid} cannot be honored"
            )
        return _resolve_sequential(N, config.with_(strategy="sequential"))
    if config.grid is not None:
        if n_dev < config.grid.P_used:
            raise ValueError(
                f"auto: explicit grid {config.grid} needs {config.grid.P_used} "
                f"devices but only {n_dev} are available; drop the grid to let "
                f"auto choose, or use strategy='sequential'"
            )
        return config.with_(strategy="conflux")
    # auto v2: score every candidate (strategy, grid, v, backend, hotloop)
    # tuple with the trace-calibrated cost model and take the predicted
    # wall-time argmin.  The chosen tuple is recorded (keyed by the resolved
    # cache key) so plan() can attach it and execute() can report the
    # measured-vs-predicted residual; the calibration version is stamped on
    # the config so the pick never outlives the table that made it.
    from repro.analysis import costmodel

    choice = costmodel.autotune_choice(N, config, n_dev=n_dev)
    if choice is not None:
        resolved = config.with_(
            strategy=choice["strategy"], grid=choice["grid"], v=choice["v"],
            backend=choice["backend"], hotloop=choice["hotloop"],
            calibration=choice["calibration_version"],
        )
        costmodel.record_decision(resolved.cache_key(N), choice)
        return resolved
    # No calibration covering this (backend, dtype, device kind): the
    # analytic comm-volume ranking still gives the paper's near-optimal
    # schedule, just without the wall-time constants.
    return _resolve_auto_analytic(N, config, n_dev)


@register_strategy("auto")
def build_auto(N: int, config: SolverConfig, mesh=None) -> FactorizationPlan:
    raise RuntimeError("'auto' resolves to a concrete strategy before building")


build_auto.resolve = _resolve_auto
