#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload hpl_lu.n16384 --seed 7 --seconds 30 --trace 0

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics from a profiler trace of the window.  Every run checks
what the timed path produced against the plain reference, prints each
compared number beside its limit as the last lines on stderr, and prints
one JSON object as the last line on stdout.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.spec()
    w = harness.cell(bench, args.workload)
    e2e, layer = harness.metrics_for(bench, args.workload)
    try:
        device = harness.device_info(w["chips"])
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    ctx = harness.Context(
        workload=args.workload, config=harness.read_json("configs", w["config"]),
        traffic=harness.read_json("traffic", w["traffic"]), seed=args.seed,
        seconds=args.seconds, chips=w["chips"], trace=bool(args.trace),
        t_start=T_START, _compiles=harness.CompileCounter())
    ctx.record["device_kind"] = device["kind"]
    harness.drive(ctx)
    counts = ctx._compiles.counts
    print(f"[bench] inside the window: {counts[harness.CompileCounter.EVENTS[0]]} "
          f"XLA compiles, {counts[harness.CompileCounter.EVENTS[1]]} traces "
          f"(should be 0)", file=sys.stderr, flush=True)
    if args.trace:
        metrics, busy, breakdown = harness.reduce_trace(ctx, layer)
        line = harness.report(ctx, device, metrics, busy, breakdown)
    else:
        missing = [m["name"] for m in e2e if m["name"] not in ctx.e2e]
        if missing:
            raise RuntimeError(f"runner did not measure {missing}")
        metrics = {m["name"]: {"value": float(ctx.e2e[m["name"]]), "unit": m["unit"]}
                   for m in e2e}
        line = harness.report(ctx, device, metrics)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
