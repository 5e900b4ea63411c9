"""Scopes and spans of a conflux plan on a [2x2x1] grid of four host devices.

Prints one JSON object: the `op_name`s of the compiled program, and the
`repro.*` host spans of one profiled execute + solve, each as [name,
start_ns, end_ns, args].  Run as a subprocess: the host device count must be
pinned before jax initializes (tests/test_obs.py asserts on the output).
"""

import glob
import json
import os
import re
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro.api import GridConfig, SolverConfig, plan  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 256
rng = np.random.default_rng(4)
A = rng.uniform(-0.5, 0.5, (N, N)).astype(np.float32)
b = rng.uniform(-0.5, 0.5, N).astype(np.float32)
p = plan(N, SolverConfig(strategy="conflux", backend="pallas",
                         grid=GridConfig(2, 2, 1, v=32, N=N)))
jax.block_until_ready(p.execute(A).solve(b))  # compile outside the trace
op_names = sorted(set(re.findall(r'op_name="([^"]*)"', p.lowered_text("hlo"))))

with tempfile.TemporaryDirectory() as d:
    with jax.profiler.trace(d):
        x = np.asarray(jax.block_until_ready(p.execute(A).solve(b)))
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    spans = [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
             for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events if e.name.startswith("repro.")]

assert np.abs(A @ x - b).max() < 1e-3 * N * np.abs(b).max()
print(json.dumps({"N": N, "op_names": op_names, "spans": spans}))
