"""Compile the solver's Pallas kernels for a TPU v5e, without the chip.

Off the TPU the kernels run in interpret mode, which accepts code that
Mosaic refuses: indexing a value with a loop counter, tiles that are not
(8, 128)-aligned, more VMEM than a core has.  These tests compile every
kernel — single-system and batched, f32 and bf16 — for one chip of a
described v5e:2x2 topology, at the widths `chip_smoke.py` runs (panels of
R=16384 rows, v=32; batched slots of 32 systems of n=256), and check that
the compiled program holds the Mosaic kernel (`tpu_custom_call`).  Two
more compile the whole in-core LU and Cholesky plans at N=16384 and check
that they fit the chip's 16 GiB; the last compiles the N=16384 conflux plan
on a [2x2x1] grid of all four chips, in f32 and with bf16 factors, and
checks that its pivot write-back is no loop that copies the local block.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.  The persistent compilation cache is off around these compiles (an
entry written without a chip cannot be read back).
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

N = 16384  # in-core panel height / matrix size of chip_smoke.py
V = 32  # panel width
B, n = 32, 256  # largest serving slot: 32 systems of n=256
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# kernel -> (ops function, operand shapes); every call runs interpret=False
KERNELS = {
    "lu_panel": (ops.lu_panel, [(N, V), (N,)]),
    "lu_panel_batched": (ops.lu_panel_batched, [(B, n, V), (B, n)]),
    "chol_panel": (ops.chol_panel, [(V, V)]),
    "chol_panel_batched": (ops.chol_panel_batched, [(B, V, V)]),
    "trsm_right_upper": (ops.trsm_right_upper, [(N, V), (V, V)]),
    "trsm_right_upper_batched": (ops.trsm_right_upper_batched,
                                 [(B, n, V), (B, V, V)]),
    "trsm_left_lower": (ops.trsm_left_lower, [(V, V), (V, N)]),
    "trsm_left_lower_batched": (ops.trsm_left_lower_batched,
                                [(B, V, V), (B, V, n)]),
    "schur_update": (ops.schur_update, [(N, N), (N, V), (V, N)]),
    "schur_update_batched": (ops.schur_update_batched,
                             [(B, n, n), (B, n, V), (B, V, n)]),
    "fused_trsm_schur": (ops.fused_trsm_schur,
                         [(N, N), (V, V), (V, N), (N, V)]),
    "fused_trsm_schur_batched": (ops.fused_trsm_schur_batched,
                                 [(B, n, n), (B, V, V), (B, V, n), (B, n, V)]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    fn, shapes = KERNELS[kernel]
    avals = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=one_chip)
             for s in shapes]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("strategy", ["sequential", "sequential_chol"])
def test_in_core_plan_compiles_for_v5e(one_chip, strategy, monkeypatch):
    """The whole N=16384 pallas plan: Mosaic kernels inside, fits in HBM."""
    from repro.api import SolverConfig, clear_plan_cache, plan

    # Off the chip the ops wrappers pick interpret mode by platform; the
    # compile is for the chip, so steer them to Mosaic here.
    monkeypatch.setattr(ops, "_interp", lambda flag: False)
    p = plan(N, SolverConfig(strategy=strategy, backend="pallas", v=V))
    try:
        aval = jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=one_chip)
        compiled = p._fn.lower(aval).compile()
    finally:
        clear_plan_cache()
        jax.clear_caches()  # drop the traces made with Mosaic kernels
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB exceeds one chip"


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_conflux_plan_compiles_for_v5e_2x2(topo, compute_dtype, monkeypatch):
    """The N=16384 conflux pallas plan on a [2x2x1] grid of the host's four
    chips: Mosaic kernels and collectives inside, each chip within HBM."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import GridConfig, SolverConfig, clear_plan_cache, plan

    monkeypatch.setattr(ops, "_interp", lambda flag: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2, 1), ("px", "py", "pz"))
    config = SolverConfig(strategy="conflux", backend="pallas",
                          grid=GridConfig(2, 2, 1, v=V, N=N), compute_dtype=compute_dtype)
    p = plan(N, config, mesh=mesh)
    try:
        aval = jax.ShapeDtypeStruct((2, 2, N // 2, N // 2), compute_dtype or jnp.float32,
                                    sharding=NamedSharding(mesh, P("px", "py", None, None)))
        compiled = p._fn.lower(aval).compile()
    finally:
        clear_plan_cache()
        jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r" all-reduce\(", text)
    assert re.search(r" collective-permute(-start)?\(", text)
    mem = compiled.memory_analysis()  # per chip: the program is one SPMD partition
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB exceeds one chip"
    # The pivot write-back stays a native scatter: no loop of its own, and
    # no copy of the whole local block in any loop inside the step loop.
    loops = [ln for ln in text.splitlines()
             if " while(" in ln and "lu.pivot_writeback" in ln]
    assert not loops, f"{len(loops)} write-back loops"
    block = f"{'bf16' if compute_dtype else 'f32'}[{N // 2},{N // 2}]"
    comps = _computations(text)
    copies = [ln for name in _nested_loop_computations(comps)
              for ln in comps[name] if re.search(re.escape(block) + r"\S* copy\(", ln)]
    assert not copies, f"{len(copies)} whole-block copies in nested loops"


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif lines is not None and line.startswith("  "):
            lines.append(line)
    return comps


def _reachable(comps, roots):
    """Computations that `roots` run, themselves included."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for ln in comps[name]:
            todo += re.findall(r"(?:body|condition|to_apply|calls)=%([\w.\-]+)", ln)
            for group in re.findall(
                    r"(?:branch_computations|called_computations)=\{([^}]*)\}", ln):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def _nested_loop_computations(comps):
    """Computations run by a `while` that itself runs inside a `while`."""
    def bodies(names):
        return [b for name in names for ln in comps[name] if " while(" in ln
                for b in re.findall(r"body=%([\w.\-]+)", ln)]

    inside_loops = _reachable(comps, bodies(comps))
    return _reachable(comps, bodies(inside_loops))
