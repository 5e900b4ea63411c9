"""An open-loop stream of small systems into the async serving tier.

The timed entry is `AsyncSolveEngine.submit(A, b, tenant)`.  The mix's kind
names its arrivals module (`bench/arrivals/<kind>.py`), which gives the
schedule; one generator thread submits each request at its scheduled time
and the future's callback stamps the answer's arrival.  Latency runs from the scheduled
arrival, so a generator or server that falls behind charges the wait to the
requests (as in `benchmarks/serve_load.py`'s open loop).  A refused
(`Overloaded`), failed or unanswered request counts as beyond every limit.
After the window every answer is compared with the problem's reference.

The callback copies each answer into one buffer made in set-up and keeps
no object of its own: objects that the load generator kept alive would
bring on Python's full garbage collections, and their pauses would be the
generator's, not the server's.  The full collections inside the window are
timed and printed.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

TRACE_S = 3.0  # the traced stretch at the end of the window, in seconds


class FullCollections:
    """Pauses of Python's full (generation 2) collections from `start` to `stop`."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = None

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None

    def start(self):
        gc.callbacks.append(self._on)

    def stop(self):
        gc.callbacks.remove(self._on)


def run(ctx) -> None:
    from jax.profiler import TraceAnnotation

    from bench import harness, stats
    from repro.api import clear_plan_cache
    from repro.serving import AsyncSolveEngine, Overloaded

    t_run = time.perf_counter()
    cfg, traffic = ctx.config, ctx.traffic
    arrivals = harness.load_module("arrivals", traffic["kind"])
    dtype = cfg["solver"].get("dtype", "float32")
    problem = harness.load_module("problems", cfg["problem"])
    sizes = arrivals.pool_sizes(traffic)
    pool = problem.make_pool(ctx.seed, sizes, dtype)
    sched = arrivals.schedule(traffic, ctx.seed, ctx.seconds)
    m = len(sched["t_due"])
    eng = AsyncSolveEngine(N=int(cfg["N"]), **cfg["engine"], **cfg["solver"])
    backend = eng.engine.plan.config.backend
    if backend != cfg["solver"]["backend"]:
        raise RuntimeError(f"engine plan resolved backend {backend!r}, the "
                           f"configuration states {cfg['solver']['backend']!r}")
    t_warm = time.perf_counter()
    eng.warm_slots(sizes=sorted(set(int(n) for n in sizes)))
    ctx.note(f"set-up: {t_run - ctx.t_start:.3f} s to the runner, {t_warm - t_run:.3f} s "
             f"data and engine, {time.perf_counter() - t_warm:.3f} s warm_slots")
    before = eng.stats()["async"]

    done = np.full(m, np.nan)  # when each answer arrived
    late = np.zeros(m)  # how late the generator submitted each request
    answers = np.zeros((m, int(cfg["N"])))
    answer_len = np.zeros(m, int)
    errors = np.zeros(m, bool)
    accepted = np.zeros(m, bool)
    lock = threading.Lock()
    outstanding = [0, False]  # answers still due; all submitted
    all_done = threading.Event()

    def stamp(i, fut):
        done[i] = time.perf_counter()
        if fut.exception() is None:
            x = np.ravel(fut.result())
            answer_len[i] = x.size
            answers[i, :min(x.size, answers.shape[1])] = x[:answers.shape[1]]
        else:
            errors[i] = True
        with lock:
            outstanding[0] -= 1
            if outstanding[1] and outstanding[0] == 0:
                all_done.set()

    # Counters are read before the traced stretch, which the profiler slows.
    trace_from = max(0.0, ctx.seconds - TRACE_S)
    mid = ann = None
    collections = FullCollections()
    collections.start()
    ctx.window_open()
    t0 = time.perf_counter()
    due = t0 + sched["t_due"]
    for i in range(m):
        if mid is None and sched["t_due"][i] >= trace_from:
            mid = eng.stats()
            if ctx.trace:
                ctx.trace_start()
                ann = TraceAnnotation("bench.window")  # records only once tracing
                ann.__enter__()
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[i] = time.perf_counter() - due[i]
        A, b = pool[sched["item"][i]]
        try:
            fut = eng.submit(A, b, tenant=f"tenant-{sched['tenant'][i]}")
        except Overloaded:
            continue
        accepted[i] = True
        with lock:
            outstanding[0] += 1
        fut.add_done_callback(lambda f, i=i: stamp(i, f))
    if ann is not None:
        ann.__exit__(None, None, None)
        ctx.trace_stop()
    t_close = t0 + ctx.seconds
    with lock:
        outstanding[1] = True
        if outstanding[0] == 0:
            all_done.set()
    all_done.wait(timeout=max(0.0, t_close + ctx.grace_s - time.perf_counter()))
    ctx.window_closed()
    collections.stop()
    mid = mid or eng.stats()

    answered = ~np.isnan(done) & ~errors
    unanswered = int(np.sum(accepted & np.isnan(done)))
    lat_ms = np.where(answered, (done - due) * 1e3, np.inf)
    ctx.e2e["serve_p50_ms"] = stats.nearest_rank(lat_ms.tolist(), 50)
    ctx.record["latency_p99_ms"] = stats.nearest_rank(lat_ms.tolist(), 99)
    ctx.e2e["served_per_s"] = float(np.sum(answered & (done <= t_close))) / ctx.seconds
    ctx.attempted = m
    ctx.failed = m - int(answered.sum())
    a = mid["async"]
    ctx.record.update(counters={
        "served": a["served"] - before["served"],
        "flushes": a["flushes"] - before["flushes"],
        "max_batch": eng.max_batch,
        "batch_pad_waste": mid["batch_pad_waste"],
    })
    tenth = max(1, m // 10)
    ctx.record["latency_p50_ms_first_last_tenth"] = [
        stats.nearest_rank(lat_ms[:tenth].tolist(), 50),
        stats.nearest_rank(lat_ms[-tenth:].tolist(), 50)]
    ctx.note(f"{m} requests, {int(answered.sum())} answered, "
             f"{int(np.sum(~accepted))} refused, {int(errors.sum())} failed, "
             f"{unanswered} unanswered; generator late by p99 "
             f"{stats.nearest_rank(late.tolist(), 99) * 1e3:.3f} ms, max "
             f"{late.max() * 1e3:.3f} ms; latency p50 of the first and last tenth "
             f"{ctx.record['latency_p50_ms_first_last_tenth']} ms")
    pauses = np.asarray(collections.pauses) * 1e3
    ctx.note(f"{pauses.size} full garbage collections in the window, "
             f"{pauses.sum():.3f} ms in all, longest {pauses.max(initial=0.0):.3f} ms")

    ctx.memory_read()
    eng.close(drain=False, timeout=10.0)
    clear_plan_cache()
    refs = {}
    worst = 0.0
    for i in np.flatnonzero(answered):
        j = int(sched["item"][i])
        if j not in refs:
            refs[j] = problem.reference(*pool[j])
        k = answer_len[i]
        x = answers[i, :k] if k <= answers.shape[1] else np.full(k, np.nan)
        err = problem.rel_error(x, refs[j])
        worst = err if not np.isfinite(err) else max(worst, err)
        if not np.isfinite(worst):
            break
    ctx.check("worst_rel_error", worst, cfg["limits"]["worst_rel_error"])
    ctx.check("unanswered", unanswered, 0)
