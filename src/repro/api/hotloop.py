"""Per-primitive wall-time profiling of the factorization hot loop.

The step bodies run inside one jitted `fori_loop` under shard_map, so the
panel / TRSM / Schur / gather phases cannot be timed in situ without
breaking the single-dispatch execution model.  Instead the profiler times
each backend primitive standalone on the *representative local shapes* of
the plan — the [R, v] panel, the [v, v] triangle, and the mid-schedule
trailing window (the power-of-two bucket at t = nsteps/2, i.e. what an
average step actually touches) — with `block_until_ready` around each call,
best-of-`repeats`.

Two extra rows quantify the tentpole's two levers directly:
  gather_us       indexed pivot-row / diagonal-block movement (take or
                  dynamic_slice, masked — whichever the strategy's windowed
                  body actually runs)
  gather_dense_us the one-hot S.T @ A matmul it replaced
  fused_us        the fused TRSM->Schur primitive
  trsm_us + schur_us   the unfused composition it replaced

The profiled shapes and primitives follow the strategy kind: LU plans time
panel_lup / trsm_left_lower(unit=True) / take-gather; Cholesky plans
(pivot == "none") time panel_chol / trsm_right_upper against L00^T (its
step-4 solve) / dynamic_slice diagonal-block movement, and the fused call
runs unit=False — so the cholesky rows in BENCH_lu.json measure the body
that strategy executes, not LU's.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import full_precision
from repro.core.windows import window_buckets


def _time_once(fn, args) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) * 1e6


def _best_of_interleaved(entries: list[tuple[str, object, tuple]],
                         repeats: int = 3) -> dict[str, dict]:
    """Interleaved best-of-`repeats` over all primitives at once.

    Sequential best-of-N times primitive 1's N repeats, then primitive 2's,
    and so on — a container load spike during one primitive's slot skews
    that primitive alone, silently distorting the *ratios* the cost-model
    fitter consumes.  Interleaving rounds (the same load-robustness pattern
    the bench smoke gate uses) spreads any spike across every primitive,
    and the per-primitive relative spread (worst/best - 1) tells the fitter
    how noisy each sample was so it can down-weight it.

    entries: (name, jitted_fn, args); returns {name: {"best_us", "spread"}}.
    """
    for _, fn, args in entries:  # compile/trace outside every timer
        jax.block_until_ready(fn(*args))
    samples: dict[str, list[float]] = {name: [] for name, _, _ in entries}
    for _ in range(max(repeats, 1)):
        for name, fn, args in entries:
            samples[name].append(_time_once(fn, args))
    out = {}
    for name, ts in samples.items():
        best = min(ts)
        spread = (max(ts) / best - 1.0) if best > 0 else 0.0
        out[name] = {"best_us": best, "spread": spread}
    return out


def profile_primitives(N: int, config, grid=None, repeats: int = 3) -> dict:
    """Wall-time the hot-loop primitives on the plan's local shapes.

    Returns microsecond floats keyed panel_us / trsm_us / schur_us /
    gather_us / gather_dense_us / fused_us, a `<name>_spread` relative
    best-to-worst spread per primitive (the cost-model fitter's noise
    weight), plus the shapes profiled.  Timing is best-of-`repeats`
    *interleaved* across primitives so a transient load spike cannot skew
    one primitive's ratio against the others.
    """
    from repro.kernels.backend import get_backend

    bk = get_backend(config.backend)
    # profile on the dtype the kernels actually run in (mixed-precision
    # plans compute in config.compute_dtype, not the working dtype)
    from repro.api.config import resolve_dtype

    dtype = resolve_dtype(getattr(config, "effective_compute_dtype", config.dtype))
    if grid is not None:
        v = grid.v
        R = (N // v // grid.Px) * v
        C = (N // v // grid.Py) * v
        nb = N // v
        # mid-schedule window: the bucket an average step lands in
        cap = min(b for b in window_buckets(nb) if b >= nb - nb // 2)
        wr = min(-(-cap // grid.Px), R // v) * v
        wc = min(-(-cap // grid.Py), C // v) * v
    else:
        v = config.v or 32
        R = C = N
        wr, wc = R, C
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(dtype))

    panel = arr(R, v)
    weights = jnp.ones((R,), dtype)
    tri = jnp.tril(arr(v, v), -1) + 2.0 * jnp.eye(v, dtype=dtype)
    A = arr(wr, wc)
    L10 = arr(wr, v)
    R01 = arr(v, wc)
    Afull = arr(R, C)
    lr = jnp.arange(v, dtype=jnp.int32) * max(R // v, 1)
    own = jnp.ones((v,), dtype)
    S = jax.nn.one_hot(lr, R, dtype=dtype)  # [v, R] — the replaced one-hot

    # The primitives run as in a plan: dots at full f32 precision.
    def jit(f):
        return jax.jit(full_precision(f))

    spd_kind = config.pivot == "none"
    if spd_kind:
        spd = tri @ tri.T + jnp.eye(v, dtype=dtype)
        panel_fn, panel_args = jit(lambda a: bk.panel_chol(a)), (spd,)
        # step 4's solve: L10 = panel (L00^T)^-1
        trsm_fn, trsm_args = (
            jit(lambda p, l: bk.trsm_right_upper(p, l.T)), (panel, tri),
        )
        # diagonal-block rows live contiguously: masked dynamic_slice
        gather_fn, gather_args = (
            jit(lambda a, i, o: jax.lax.dynamic_slice_in_dim(a, i, v) * o),
            (Afull, jnp.int32(R - v), own[0]),
        )
        unit = False
    else:
        panel_fn, panel_args = (
            jit(lambda p, w: bk.panel_lup(p, w, v)), (panel, weights),
        )
        trsm_fn, trsm_args = (
            jit(lambda l, b: bk.trsm_left_lower(l, b, unit=True)), (tri, R01),
        )
        gather_fn, gather_args = (
            jit(lambda a, i, o: jnp.take(a, i, axis=0) * o[:, None]),
            (Afull, lr, own),
        )
        unit = True

    entries = [
        ("panel", panel_fn, panel_args),
        ("trsm", trsm_fn, trsm_args),
        ("schur",
         jit(lambda a, l, u: bk.schur_update(a, l, u)), (A, L10, R01)),
        ("fused",
         jit(lambda a, l00, r01, l10:
                 bk.fused_trsm_schur(a, l00, r01, l10, unit=unit)),
         (A, tri, R01, L10)),
        ("gather", gather_fn, gather_args),
        ("gather_dense", jit(lambda s, a: s @ a), (S, Afull)),
    ]
    measured = _best_of_interleaved(entries, repeats=repeats)
    timings = {}
    for name, m in measured.items():
        timings[f"{name}_us"] = m["best_us"]
        timings[f"{name}_spread"] = m["spread"]
    timings["shapes"] = {"R": R, "C": C, "v": v, "wr": wr, "wc": wc}
    return timings
