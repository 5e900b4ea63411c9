#!/usr/bin/env python3
"""Readings the limits and the offered rate were set from, on the chip.

    # compared numbers of the program on several seeds, in one process
    python3 bench/readings.py --workload hpl_lu.n16384 --seeds 1,2,3 --seconds 10
    # the same with the configuration's lower-precision control switched on
    python3 bench/readings.py --workload hpl_lu.n16384 --seeds 1,2,3 --seconds 10 --control
    # a rate sweep of an open-loop cell (the knee)
    python3 bench/readings.py --workload batched_serve.poisson --seeds 5 --seconds 10 \
        --rates 20,200,400,800

One JSON line per run on stdout.  The benchmark's own runs (`bench/run.py`)
never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="", help="comma-separated offered rates")
    args = ap.parse_args(argv)

    bench = harness.spec()
    w = harness.cell(bench, args.workload)
    try:
        device = harness.device_info(w["chips"])
    except harness.NoDevice as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            config = harness.read_json("configs", w["config"])
            traffic = harness.read_json("traffic", w["traffic"])
            if args.control:
                config["solver"].update(config["control"])
            if rate is not None:
                traffic["rate_per_s"] = rate
            ctx = harness.Context(
                workload=args.workload, config=config, traffic=traffic, seed=seed,
                seconds=args.seconds, chips=w["chips"], trace=False,
                _compiles=counter)
            ctx.record["device_kind"] = device["kind"]
            with warnings.catch_warnings():
                # the control's plain solve rounds b to its factors' dtype
                warnings.filterwarnings("ignore", message="factors are")
                harness.drive(ctx)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": args.control,
                "rate_per_s": rate, "correct": ctx.correct,
                "attempted": ctx.attempted, "failed": ctx.failed,
                "e2e": ctx.e2e, "counters": ctx.record.get("counters"),
                "tenths_ms": ctx.record.get("latency_p50_ms_first_last_tenth"),
                "checks": {n: [v, lim] for n, v, lim in ctx.checks},
                "notes": ctx.notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
