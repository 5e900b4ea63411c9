"""Time to a checked solution of one large dense system, back to back.

The timed entry is the solver's public path: `plan(N, config).execute(A)`
then `Factorization.solve(b)`, waited on with `block_until_ready`.  The
window starts solutions until `seconds` have passed, then finishes the one
in flight; `solution_s` is the window over the solutions in it.  Every
timed solution's x is checked afterwards against its A by the problem's
plain reference.
"""

from __future__ import annotations

import gc
import time

import numpy as np


def run(ctx) -> None:
    import jax
    from jax.profiler import TraceAnnotation

    from bench import harness
    from repro.api import SolverConfig, clear_plan_cache, plan

    cfg, traffic = ctx.config, ctx.traffic
    if traffic["kind"] != "back_to_back":
        raise ValueError(f"the solve runner replays back_to_back traffic, not {traffic['kind']!r}")
    n = int(cfg["N"])
    dtype = cfg["solver"].get("dtype", "float32")
    problem = harness.load_module("problems", cfg["problem"])
    want = SolverConfig(**cfg["solver"])
    p = plan(n, want)
    if p.config.backend != want.backend:
        raise RuntimeError(f"plan resolved backend {p.config.backend!r}, "
                           f"the configuration states {want.backend!r}")
    pool = problem.make_pool(ctx.seed, n, int(traffic["pool"]), dtype)

    def solution(A, b):
        with TraceAnnotation("bench.solution"):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.execute"):
                fact = p.execute(A)
            t1 = time.perf_counter()
            with TraceAnnotation("bench.solve"):
                x = jax.block_until_ready(fact.solve(b))
            t2 = time.perf_counter()
        return x, (t0, t1, t2)

    solution(*pool[0])  # warm: loads or compiles every program the window runs

    xs, used, times = [], [], []
    ctx.trace_start()
    ctx.window_open()
    t_open = time.perf_counter()
    with TraceAnnotation("bench.window"):
        k = 0
        while True:
            i = k % len(pool)
            x, t = solution(*pool[i])
            xs.append(x)
            used.append(i)
            times.append(t)
            k += 1
            if time.perf_counter() - t_open >= ctx.seconds:
                break
    t_close = time.perf_counter()
    ctx.window_closed()
    ctx.trace_stop()

    ctx.e2e["solution_s"] = (t_close - t_open) / k
    ctx.attempted = k
    ctx.record.update(
        work={"N": n, "solutions": k, "itemsize": np.dtype(dtype).itemsize},
        spans={"execute": [(a, b) for a, b, _ in times],
               "solve": [(b, c) for _, b, c in times]},
    )
    ctx.note(f"{k} solutions in {t_close - t_open:.3f} s; execute "
             f"{np.mean([b - a for a, b, _ in times]):.3f} s, solve "
             f"{np.mean([c - b for _, b, c in times]):.3f} s on average")

    ctx.memory_read()
    xs = [np.asarray(x) for x in xs]
    del p
    clear_plan_cache()
    gc.collect()
    worst = 0.0
    for i, (A, b) in enumerate(pool):
        mine = [j for j, u in enumerate(used) if u == i]
        if not mine:
            continue
        res = problem.scaled_residuals(np.asarray(A), [xs[j] for j in mine],
                                       [np.asarray(b)] * len(mine), dtype)
        worst = max([worst] + res) if all(np.isfinite(res)) else float("nan")
        if not np.isfinite(worst):
            break
    ctx.check("hpl_scaled_residual", worst, cfg["limits"]["hpl_scaled_residual"])
