"""Plan auditor: static I/O-model conformance, Pallas kernel lint, and
cache-key aliasing detection (`python -m repro.analysis.audit`).

The paper's claim — COnfLUX moves N^3/(P*sqrt(M)) elements per processor,
within 1/3x of the X-partitioning lower bound — is *statically derivable*, so
this module checks it from the program text instead of runtime counters: each
registered plan (strategy x backend x hotloop x compute_dtype, small N) is
lowered (never executed) and a suite of checkers emits structured
`AuditFinding`s.

Rules:
  comm-conformance   HLO-extracted per-device collective bytes must match the
                     executed-schedule model (below) within `tolerance`; the
                     instrumented schedule volume and the X-partitioning lower
                     bound are reported alongside.  In-core (sequential) plans
                     must emit zero collectives.
  mesh-uniformity    collectives inside `lax.switch` branches (the windowed
                     hot loops) must agree in op kind + replica groups across
                     branches — the invariant that keeps the SPMD program
                     deadlock-free.  Payload *shapes* legitimately differ by
                     the trailing-window width (reported at info).
  kernel-vmem        static VMEM footprint of every Pallas kernel (BlockSpec
                     blocks x double buffering + scratch) vs a budget.
  kernel-divisibility  BlockSpec block shapes must tile their operands.
  kernel-accum       sub-4-byte float inputs must accumulate in >= f32
                     (no bf16/f16 dot_general or arithmetic outputs).
  cache-key          perturbing a SolverConfig field must not produce a
                     different lowered program under an unchanged cache_key.

The *executed* comm model: XLA:CPU lowers the masked 2.5D schedules to
*unconditional* collectives (every device participates every step, with
masked payloads), so the bytes in the lowered HLO exceed the instrumented
schedule volume (`lu_comm_volume` / `chol_comm_volume`, which count only the
processors the paper's schedule has communicating).  The model below
reproduces the lowered program's per-device bytes exactly on this container
(ring all-reduce wire = 2*S*(g-1)/g per member, ppermute wire = payload,
windowed steps weighted by their `lax.switch` bucket execution counts);
`tolerance` absorbs collective-emission drift across XLA versions.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from dataclasses import dataclass, field

SEVERITIES = ("error", "warning", "info")

# VMEM per TPU core (v4/v5e ballpark; see /opt/skills/guides/pallas_guide.md).
DEFAULT_VMEM_BUDGET = 16 * 2**20

# Documented comm-conformance tolerance: the model is exact against the XLA
# pinned in this container; a different XLA may fuse/elide collectives.
DEFAULT_TOLERANCE = 0.25


@dataclass(frozen=True)
class AuditFinding:
    rule: str
    severity: str  # "error" | "warning" | "info"
    location: str  # plan ("conflux/ref/windowed N=64") or kernel ("lu_panel[f32]")
    detail: str
    data: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        return {
            "rule": self.rule, "severity": self.severity,
            "location": self.location, "detail": self.detail, "data": self.data,
        }


@dataclass
class AuditReport:
    findings: list[AuditFinding] = field(default_factory=list)
    comm_rows: list[dict] = field(default_factory=list)  # BENCH `audit` section

    def add(self, rule: str, severity: str, location: str, detail: str,
            data: dict | None = None) -> AuditFinding:
        if severity not in SEVERITIES:
            raise ValueError(f"severity must be one of {SEVERITIES}, got {severity!r}")
        f = AuditFinding(rule, severity, location, detail, data or {})
        self.findings.append(f)
        return f

    def extend(self, findings) -> None:
        self.findings.extend(findings)

    @property
    def errors(self) -> list[AuditFinding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self) -> list[AuditFinding]:
        return [f for f in self.findings if f.severity == "warning"]

    def to_json(self) -> dict:
        return {
            "findings": [f.to_json() for f in self.findings],
            "counts": {s: sum(1 for f in self.findings if f.severity == s)
                       for s in SEVERITIES},
            "comm_rows": self.comm_rows,
        }


# ---------------------------------------------------------------------------
# Executed-schedule communication model.
# ---------------------------------------------------------------------------


def _ar(bytes_: float, g: int) -> float:
    """Ring all-reduce wire bytes per member (0 for a single-member group —
    XLA emits these with replica_groups of size 1 and they move nothing)."""
    return 2.0 * bytes_ * (g - 1) / g if g > 1 else 0.0


def _window_caps(nsteps: int) -> list[int]:
    """Per-step window bucket cap (tiles) of the windowed hot loop."""
    from repro.core.windows import window_bucket_index, window_buckets

    buckets = window_buckets(nsteps)
    return [buckets[window_bucket_index(t, nsteps)] for t in range(nsteps)]


def branch_weights_for(N: int, v: int, hotloop: str) -> dict[int, tuple[float, ...]]:
    """`analyze_hlo` branch weights for the windowed hot loop's `lax.switch`:
    bucket i runs count_i of the nsteps iterations."""
    if hotloop != "windowed":
        return {}
    from repro.core.windows import window_buckets

    nsteps = N // v
    buckets = window_buckets(nsteps)
    counts = [0] * len(buckets)
    for cap in _window_caps(nsteps):
        counts[buckets.index(cap)] += 1
    return {len(buckets): tuple(c / nsteps for c in counts)}


def executed_comm_bytes(kind: str, N: int, grid, pivot: str, hotloop: str,
                        compute_itemsize: int) -> dict:
    """Per-device wire bytes of the *lowered* (unconditional) 2.5D schedule.

    Returns a per-site breakdown plus "total".  Element size: collectives
    carry f32 partials when the compute dtype is narrower than 4 bytes (the
    kernels accumulate sub-4-byte dtypes in f32), else the compute dtype.
    """
    Px, Py, c, v = grid.Px, grid.Py, grid.c, grid.v
    s = 4.0 if compute_itemsize < 4 else float(compute_itemsize)
    si = 4.0  # pivot-index payloads are int32
    nbi = N // v
    R = (nbi // Px) * v  # local row extent
    C = (nbi // Py) * v  # local col extent
    caps: list[int | None]
    caps = _window_caps(nbi) if hotloop == "windowed" else [None] * nbi

    def wc(cap):  # window col extent owned locally (cols shard over py)
        return C if cap is None else min(-(-cap // Py) * v, C)

    def wr(cap):  # window row extent (rows shard over px; Cholesky only)
        return R if cap is None else min(-(-cap // Px) * v, R)

    out = {"panel": 0.0, "pivot": 0.0, "gids": 0.0, "a00": 0.0,
           "l10": 0.0, "r01": 0.0}
    for cap in caps:
        if kind == "cholesky":
            out["panel"] += _ar(wr(cap) * v * s, c)
            out["a00"] += _ar(v * v * s, Px * Py)
            out["l10"] += _ar(wr(cap) * v * s, Py)
            out["r01"] += _ar(v * wc(cap) * s, Px * c)
            continue
        # LU: rows keep full extent (masked pivot rows stay scattered).
        out["panel"] += _ar(R * v * s, c)
        if pivot == "tournament":
            # log2(Px) butterfly rounds; each permutes the candidate block
            # (v x v values) and its row ids. ppermute wire = payload.
            out["pivot"] += math.log2(Px) * (v * v * s + v * si) if Px > 1 else 0.0
        else:
            # partial: per column, |max| + its owner are combined over px and
            # the pivot row (panel width v) is psummed over px.
            out["pivot"] += v * (_ar(s, Px) + _ar(si, Px) + _ar(v * s, Px))
        out["gids"] += _ar(v * si, Py)
        out["a00"] += _ar(v * v * s, Py)
        out["l10"] += _ar(R * v * s, Py)
        out["r01"] += _ar(v * wc(cap) * s, Px * c)
    out["total"] = sum(out.values())
    return out


# ---------------------------------------------------------------------------
# Checker: comm-conformance.
# ---------------------------------------------------------------------------


def _plan_location(p) -> str:
    cfg = p.config
    loc = f"{cfg.strategy}/{cfg.backend}/{cfg.hotloop} N={p.N}"
    if cfg.compute_dtype:
        loc += f" compute={cfg.compute_dtype}"
    return loc


def check_comm_conformance(p, tolerance: float = DEFAULT_TOLERANCE):
    """Extract per-device collective bytes from the plan's optimized HLO and
    compare with the executed-schedule model; report the instrumented schedule
    volume and the X-partitioning lower bound alongside.

    Returns (findings, row) — row is the BENCH `audit` section entry.
    """
    from repro.analysis.hlo import analyze_hlo
    from repro.api.config import resolve_dtype
    from repro.core.xpart import lu_parallel_lower_bound

    cfg = p.config
    loc = _plan_location(p)
    itemsize = resolve_dtype(cfg.effective_compute_dtype).itemsize
    findings: list[AuditFinding] = []
    rep_kw = {}
    if p.grid is not None:
        rep_kw["branch_weights"] = branch_weights_for(p.N, p.grid.v, cfg.hotloop)
    rep = analyze_hlo(p.lowered_text("hlo"), **rep_kw)
    extracted = rep.collective_wire_bytes

    row = {
        "strategy": cfg.strategy, "backend": cfg.backend,
        "hotloop": cfg.hotloop, "pivot": cfg.pivot,
        "compute_dtype": cfg.effective_compute_dtype, "N": p.N,
        "extracted_bytes": extracted,
    }
    if p.grid is None:
        row.update(grid=None, predicted_bytes=0.0, lower_bound_bytes=None,
                   schedule_bytes=0.0)
        if extracted > 0:
            findings.append(AuditFinding(
                "comm-conformance", "error", loc,
                f"in-core plan lowered with {extracted:.0f} bytes of "
                f"collectives; sequential strategies must not communicate",
                {"extracted_bytes": extracted}))
        else:
            findings.append(AuditFinding(
                "comm-conformance", "info", loc,
                "in-core plan: no collectives in lowered HLO", dict(row)))
        return findings, row

    model = executed_comm_bytes(p.kind, p.N, p.grid, cfg.pivot, cfg.hotloop,
                                itemsize)
    predicted = model["total"]
    s_sched = 4.0 if itemsize < 4 else float(itemsize)
    schedule_bytes = float(p.comm.get("total", 0.0)) * s_sched
    P_used = p.grid.Px * p.grid.Py * p.grid.c
    bound_elems = lu_parallel_lower_bound(p.N, P_used, cfg.M)
    if p.kind == "cholesky":
        # Cholesky's X-partitioning leading term is half LU's (arXiv:2108.09337).
        bound_elems /= 2.0
    bound_bytes = bound_elems * s_sched
    rel = abs(extracted - predicted) / max(predicted, 1.0)
    row.update(grid=str(p.grid), predicted_bytes=predicted,
               schedule_bytes=schedule_bytes, lower_bound_bytes=bound_bytes,
               rel_err=rel, model=model)
    data = dict(row)
    if rel > tolerance:
        findings.append(AuditFinding(
            "comm-conformance", "error", loc,
            f"lowered HLO moves {extracted:.0f} B/device but the executed "
            f"schedule model predicts {predicted:.0f} B "
            f"(rel err {rel:.1%} > tolerance {tolerance:.0%})", data))
    else:
        findings.append(AuditFinding(
            "comm-conformance", "info", loc,
            f"extracted {extracted:.0f} B/device vs model {predicted:.0f} B "
            f"(rel err {rel:.1%}); schedule volume {schedule_bytes:.0f} B, "
            f"X-partitioning bound {bound_bytes:.0f} B", data))
    return findings, row


# ---------------------------------------------------------------------------
# Checker: mesh-uniformity of lax.switch branches.
# ---------------------------------------------------------------------------


def _collect_collectives(comps, name, _depth=0):
    """In-order (kind, replica_groups, dtype, shape) walk of a computation,
    descending into while bodies / nested branches / calls."""
    import re

    from repro.analysis.hlo import _callees, _COLLECTIVES, _SHAPE_RE

    if name not in comps or _depth > 16:
        return []
    out = []
    comp = comps[name]
    for opn in comp.order:
        op = comp.ops[opn]
        base = op.kind.removesuffix("-start").removesuffix("-done")
        if base in _COLLECTIVES and not op.kind.endswith("-done"):
            gm = re.search(r"replica_groups=(\{\{[^}]*\}\}|\[[\d,]+\]<=\[\d+\])",
                           op.line)
            sm = _SHAPE_RE.search(op.out_type)
            out.append((base, gm.group(1) if gm else "",
                        sm.group(1) if sm else "", sm.group(2) if sm else ""))
        for _, callee in _callees(op):
            out.extend(_collect_collectives(comps, callee, _depth + 1))
    return out


def check_mesh_uniformity(text: str, location: str) -> list[AuditFinding]:
    """Every `conditional` (lax.switch) must issue the same collective
    sequence — same op kinds and replica groups in the same order — in every
    branch, or devices taking different branches deadlock.  Branches whose
    payload shapes differ (the shrinking-window design) are reported at info.
    """
    from repro.analysis.hlo import _callees, _parse_computations

    comps = _parse_computations(text)
    findings: list[AuditFinding] = []
    for comp in comps.values():
        for opn in comp.order:
            op = comp.ops[opn]
            if op.kind != "conditional":
                continue
            branches = [c for rel, c in _callees(op) if rel == "branch"]
            if len(branches) < 2:
                continue
            seqs = [_collect_collectives(comps, b) for b in branches]
            sigs = [[(k, g, d) for k, g, d, _ in s] for s in seqs]
            if any(sig != sigs[0] for sig in sigs[1:]):
                findings.append(AuditFinding(
                    "mesh-uniformity", "error", location,
                    f"conditional %{op.name} ({len(branches)} branches): "
                    f"collective sequences differ across branches — devices "
                    f"resolving different branches will deadlock",
                    {"branches": branches,
                     "sequences": [[list(x) for x in s] for s in seqs]}))
                continue
            shapes = [[x[3] for x in s] for s in seqs]
            if any(sh != shapes[0] for sh in shapes[1:]):
                findings.append(AuditFinding(
                    "mesh-uniformity", "info", location,
                    f"conditional %{op.name}: branch collectives agree in "
                    f"kind/replica-groups; payload shapes differ by window "
                    f"width (by design)", {"shapes": shapes}))
            elif sigs[0]:
                findings.append(AuditFinding(
                    "mesh-uniformity", "info", location,
                    f"conditional %{op.name}: {len(sigs[0])} collectives "
                    f"uniform across {len(branches)} branches", {}))
    return findings


# ---------------------------------------------------------------------------
# Checker: Pallas kernel lint (VMEM footprint, divisibility, f32 accumulation).
# ---------------------------------------------------------------------------

_ACCUM_PRIMS = {"dot_general", "add", "sub", "mul", "div"}


def _iter_pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_pallas_eqns(sub)


def _sub_jaxprs(val):
    """Nested jaxprs hiding inside an eqn param (pjit/scan/cond bodies)."""
    if hasattr(val, "jaxpr") and hasattr(val, "consts"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns") and hasattr(val, "invars"):  # Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def _ref_aval(aval):
    return getattr(aval, "inner_aval", aval)


def _block_extent(b) -> int | None:
    """A block dim's extent as an int (a squeezed dim is 1); None for
    element-indexed dims.  Pallas stores a plain extent as `Blocked`."""
    from jax.experimental import pallas as pl

    if isinstance(b, pl.Squeezed):
        return 1
    return b.block_size if isinstance(b, pl.Blocked) else None


def lint_pallas_fn(fn, avals, name: str,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> list[AuditFinding]:
    """Trace `fn(*avals)` and statically lint every pallas_call inside it."""
    import jax
    import numpy as np

    try:
        closed = jax.make_jaxpr(fn)(*avals)
    except Exception as e:  # tracing failure is itself a finding
        return [AuditFinding("kernel-lint", "error", name,
                             f"tracing failed: {type(e).__name__}: {e}", {})]
    findings: list[AuditFinding] = []
    eqns = list(_iter_pallas_eqns(closed.jaxpr))
    if not eqns:
        return [AuditFinding("kernel-lint", "warning", name,
                             "no pallas_call found in traced function", {})]
    for eqn in eqns:
        gm = eqn.params["grid_mapping"]
        kjaxpr = eqn.params["jaxpr"]
        grid = tuple(gm.grid)
        n_dyn = getattr(gm, "num_dynamic_grid_bounds", 0)  # lead the operands
        n_idx = getattr(gm, "num_index_operands", 0)
        n_in = gm.num_inputs
        n_out = gm.num_outputs
        bms = list(gm.block_mappings)
        arrays = [v.aval for v in eqn.invars][n_dyn + n_idx:n_dyn + n_idx + n_in]
        arrays += [v.aval for v in eqn.outvars][:n_out]

        # -- grid/block divisibility --------------------------------------
        for i, (aval, bm) in enumerate(zip(arrays, bms)):
            block = tuple(bm.block_shape)
            dims = tuple(aval.shape)
            ints = [n for n in map(_block_extent, block) if n is not None]
            if len(ints) != len(dims):
                continue  # squeezed/mapped dims: skip rather than misalign
            bad = [(d, b) for d, b in zip(dims, ints) if b > 0 and d % b]
            if bad:
                findings.append(AuditFinding(
                    "kernel-divisibility", "error", name,
                    f"operand {i}: block {block} does not tile array "
                    f"{dims} (grid {grid}) — partial edge blocks on TPU "
                    f"read out of bounds", {"operand": i, "block": list(ints),
                                            "shape": list(dims)}))

        # -- VMEM footprint ------------------------------------------------
        kinner = getattr(kjaxpr, "jaxpr", kjaxpr)  # ClosedJaxpr or Jaxpr
        refs = [_ref_aval(v.aval) for v in kinner.invars]
        block_refs = refs[n_idx:n_idx + n_in + n_out]
        scratch_refs = refs[n_idx + n_in + n_out:]
        def _bytes(a):
            return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize

        # double buffering; a dynamic extent may exceed 1
        static = all(isinstance(g, int) for g in grid)
        pipelined = 2 if not static or math.prod(grid) > 1 else 1
        block_bytes = sum(_bytes(a) for a in block_refs)
        scratch_bytes = sum(_bytes(a) for a in scratch_refs)
        vmem = block_bytes * pipelined + scratch_bytes
        data = {"grid": [g if isinstance(g, int) else None for g in grid],
                "block_bytes": block_bytes,
                "scratch_bytes": scratch_bytes, "vmem_bytes": vmem,
                "budget": vmem_budget}
        if vmem > vmem_budget:
            findings.append(AuditFinding(
                "kernel-vmem", "error", name,
                f"estimated VMEM {vmem / 2**20:.2f} MiB (blocks "
                f"{block_bytes / 2**20:.2f} x{pipelined} + scratch "
                f"{scratch_bytes / 2**20:.2f}) exceeds budget "
                f"{vmem_budget / 2**20:.0f} MiB", data))
        else:
            findings.append(AuditFinding(
                "kernel-vmem", "info", name,
                f"estimated VMEM {vmem / 2**20:.2f} MiB within "
                f"{vmem_budget / 2**20:.0f} MiB budget", data))

        # -- f32 accumulation for sub-4-byte inputs ------------------------
        def _is_lowfloat(dtype) -> bool:
            d = np.dtype(dtype)
            # bf16/f8 are numpy *extension* dtypes (kind 'V'), so go through
            # jax's float lattice instead of d.kind.
            return jax.dtypes.issubdtype(d, np.floating) and d.itemsize < 4

        in_dtypes = [np.dtype(a.dtype) for a in refs[n_idx:n_idx + n_in]]
        if any(_is_lowfloat(d) for d in in_dtypes):
            low = []
            for keqn in _all_eqns(kinner):
                if keqn.primitive.name not in _ACCUM_PRIMS:
                    continue
                for ov in keqn.outvars:
                    d = np.dtype(ov.aval.dtype)
                    if _is_lowfloat(d):
                        low.append((keqn.primitive.name, d.name))
            if low:
                findings.append(AuditFinding(
                    "kernel-accum", "error", name,
                    f"sub-4-byte input dtypes but {len(low)} arithmetic op(s) "
                    f"accumulate below f32 (e.g. {low[0][0]} -> {low[0][1]}); "
                    f"cast to f32 before accumulating", {"ops": low[:8]}))
            else:
                findings.append(AuditFinding(
                    "kernel-accum", "info", name,
                    "sub-4-byte inputs accumulate in >= f32", {}))
    return findings


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _all_eqns(sub)


def _kernel_cases(v: int = 32, R: int = 256, C: int = 256, B: int = 4):
    """(name, fn, aval-shapes) for every factorization kernel in kernels/
    (the LM-stack kernels — flash_attention, mamba_scan — are out of scope).
    Shapes mirror the hot-loop call sites; dtypes are swept by the caller."""
    from repro.kernels import ops

    return [
        ("lu_panel", lambda p, w: ops.lu_panel(p, w, interpret=True),
         [(R, v), (R,)]),
        ("lu_panel_batched",
         lambda p, w: ops.lu_panel_batched(p, w, interpret=True),
         [(B, R, v), (B, R)]),
        ("chol_panel", lambda a: ops.chol_panel(a, interpret=True), [(v, v)]),
        ("chol_panel_batched",
         lambda a: ops.chol_panel_batched(a, interpret=True), [(B, v, v)]),
        ("trsm_right_upper",
         lambda b, u: ops.trsm_right_upper(b, u, interpret=True),
         [(R, v), (v, v)]),
        ("trsm_right_upper_batched",
         lambda b, u: ops.trsm_right_upper_batched(b, u, interpret=True),
         [(B, R, v), (B, v, v)]),
        ("trsm_left_lower",
         lambda l, b: ops.trsm_left_lower(l, b, interpret=True),
         [(v, v), (v, C)]),
        ("trsm_left_lower_batched",
         lambda l, b: ops.trsm_left_lower_batched(l, b, interpret=True),
         [(B, v, v), (B, v, C)]),
        ("schur_update",
         lambda a, l, u: ops.schur_update(a, l, u, interpret=True),
         [(R, C), (R, v), (v, C)]),
        ("schur_update_batched",
         lambda a, l, u: ops.schur_update_batched(a, l, u, interpret=True),
         [(B, R, C), (B, R, v), (B, v, C)]),
        ("fused_trsm_schur",
         lambda a, l00, r01, l10: ops.fused_trsm_schur(
             a, l00, r01, l10, interpret=True),
         [(R, C), (v, v), (v, C), (R, v)]),
        ("fused_trsm_schur_batched",
         lambda a, l00, r01, l10: ops.fused_trsm_schur_batched(
             a, l00, r01, l10, interpret=True),
         [(B, R, C), (B, v, v), (B, v, C), (B, R, v)]),
    ]


def check_kernels(vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  dtypes: tuple[str, ...] = ("float32", "bfloat16"),
                  v: int = 32) -> list[AuditFinding]:
    """Lint every registered factorization kernel at representative shapes."""
    import jax

    from repro.api.config import resolve_dtype

    findings: list[AuditFinding] = []
    for dtype in dtypes:
        dt = resolve_dtype(dtype)
        for name, fn, shapes in _kernel_cases(v=v):
            avals = [jax.ShapeDtypeStruct(s, dt) for s in shapes]
            findings.extend(
                lint_pallas_fn(fn, avals, f"{name}[{dt.name}]",
                               vmem_budget=vmem_budget))
    return findings


# ---------------------------------------------------------------------------
# Checker: cache-key completeness fuzzer.
# ---------------------------------------------------------------------------


def _fuzz_perturbations(cfg, N: int) -> list[tuple[str, dict]]:
    """One perturbed value per SolverConfig field (None = not applicable)."""
    from repro.core.lu.grid import GridConfig

    v = cfg.v or 8
    perts: list[tuple[str, dict]] = [
        ("dtype", {"dtype": "float64" if cfg.dtype == "float32" else "float32"}),
        ("compute_dtype", {"compute_dtype": "bfloat16"
                           if cfg.compute_dtype != "bfloat16" else "float16"}),
        ("v", {"v": v * 2 if N % (v * 2) == 0 else max(v // 2, 1)}),
        ("hotloop", {"hotloop": "flat" if cfg.hotloop == "windowed" else "windowed"}),
        ("pivot", {"pivot": "partial" if cfg.pivot == "tournament" else "tournament"}),
        ("backend", {"backend": "pallas" if cfg.backend == "ref" else "ref"}),
        ("B", {"B": 2 if cfg.B is None else cfg.B * 2}),
        ("M", {"M": cfg.M / 4}),
        ("P_target", {"P_target": 4 if cfg.P_target != 4 else 2}),
    ]
    if cfg.grid is not None:
        g = cfg.grid
        perts.append(("grid", {"grid": GridConfig(g.Py, g.Px, g.c, g.v, g.N)}))
    return perts


def check_cache_keys(N: int, base_cfg, key_fn=None) -> list[AuditFinding]:
    """Perturb each SolverConfig field; any perturbation that changes the
    lowered StableHLO but not the cache key is an aliasing bug (two distinct
    programs sharing one plan-cache slot).

    key_fn(resolved_cfg, N) defaults to `cfg.cache_key(N)` — injectable so the
    mutation tests can prove the fuzzer catches a key with a dropped field.
    Plans are built directly from the builders (never through `plan()`): the
    plan cache keys on the very function under test, so going through it
    would hand back the aliased plan and mask the bug.
    """
    from repro.api.plan import resolve
    from repro.api.registry import get_strategy

    key_fn = key_fn or (lambda cfg, n: cfg.cache_key(n))
    findings: list[AuditFinding] = []

    def build_text(cfg):
        resolved = resolve(N, cfg)
        p = get_strategy(resolved.strategy)(N, resolved)
        return resolved, p.lowered_text("stablehlo")

    try:
        base_resolved, base_text = build_text(base_cfg)
    except Exception as e:
        return [AuditFinding("cache-key", "error",
                             f"{base_cfg.strategy} N={N}",
                             f"base config failed to lower: {e}", {})]
    base_key = key_fn(base_resolved, N)
    loc_base = f"{base_resolved.strategy}/{base_resolved.backend} N={N}"

    for fieldname, change in _fuzz_perturbations(base_resolved, N):
        try:
            pert_cfg = base_cfg.with_(**change)
        except (ValueError, TypeError):
            continue  # invalid for this config: nothing to alias
        try:
            pert_resolved, pert_text = build_text(pert_cfg)
        except Exception:
            continue  # strategy rejects the perturbation: nothing to alias
        if pert_resolved == base_resolved:
            continue  # normalized away (e.g. pivot on Cholesky): same plan
        pert_key = key_fn(pert_resolved, N)
        same_key = pert_key == base_key
        same_text = pert_text == base_text
        data = {"field": fieldname, "perturbation": repr(change),
                "same_key": same_key, "same_text": same_text}
        if same_key and not same_text:
            findings.append(AuditFinding(
                "cache-key", "error", loc_base,
                f"field {fieldname!r}: perturbation changes the lowered "
                f"program but not cache_key — two distinct programs would "
                f"share one plan-cache entry", data))
        elif not same_key and same_text:
            findings.append(AuditFinding(
                "cache-key", "info", loc_base,
                f"field {fieldname!r}: distinct keys lower to identical "
                f"programs (benign over-keying: plans never shared)", data))
    if not any(f.severity == "error" for f in findings):
        findings.append(AuditFinding(
            "cache-key", "info", loc_base,
            "no cache-key aliasing across field perturbations", {}))
    return findings


# ---------------------------------------------------------------------------
# Audit driver + CLI.
# ---------------------------------------------------------------------------


def _default_plan_matrix(N: int, v: int, n_devices: int):
    """The strategy x backend x hotloop x compute_dtype combos to audit."""
    from repro.api.config import SolverConfig
    from repro.core.lu.grid import GridConfig

    combos: list = []
    for backend in ("ref", "pallas"):
        combos.append(SolverConfig(strategy="sequential", v=v, backend=backend))
        combos.append(SolverConfig(strategy="sequential", v=v, backend=backend,
                                   compute_dtype="bfloat16"))
        combos.append(SolverConfig(strategy="sequential_chol", v=v,
                                   backend=backend, pivot="none"))
    if n_devices >= 8:
        g222 = GridConfig(2, 2, 2, v, N)
        g221 = GridConfig(2, 2, 1, v, N)
        for backend in ("ref", "pallas"):
            for hotloop in ("windowed", "flat"):
                combos.append(SolverConfig(strategy="conflux", grid=g222,
                                           backend=backend, hotloop=hotloop))
                combos.append(SolverConfig(strategy="cholesky25d", grid=g222,
                                           backend=backend, hotloop=hotloop,
                                           pivot="none"))
            combos.append(SolverConfig(strategy="baseline2d", grid=g221,
                                       backend=backend, pivot="partial",
                                       hotloop="windowed"))
        combos.append(SolverConfig(strategy="conflux", grid=g222,
                                   hotloop="windowed",
                                   compute_dtype="bfloat16"))
        combos.append(SolverConfig(strategy="baseline2d", grid=g221,
                                   pivot="partial", hotloop="flat"))
    return combos


def run_audit(N: int = 64, v: int = 8, tolerance: float = DEFAULT_TOLERANCE,
              vmem_budget: int = DEFAULT_VMEM_BUDGET,
              rules: set[str] | None = None) -> AuditReport:
    """Lower every registered plan combo (never executing) and run all
    checkers.  `rules` restricts to a subset of
    {"comm", "mesh", "kernels", "cache-key"}."""
    import jax

    from repro.api import plan

    rules = rules or {"comm", "mesh", "kernels", "cache-key"}
    report = AuditReport()
    n_dev = len(jax.devices())
    if n_dev < 8:
        report.add(
            "audit", "warning", "devices",
            f"only {n_dev} device(s) visible: distributed combos skipped "
            f"(run via `python -m repro.analysis.audit` to get 8 host devices)")

    if rules & {"comm", "mesh"}:
        for cfg in _default_plan_matrix(N, v, n_dev):
            try:
                p = plan(N, cfg)
            except Exception as e:
                report.add("audit", "error",
                           f"{cfg.strategy}/{cfg.backend}/{cfg.hotloop}",
                           f"plan build failed: {type(e).__name__}: {e}")
                continue
            if "comm" in rules:
                findings, row = check_comm_conformance(p, tolerance=tolerance)
                report.extend(findings)
                report.comm_rows.append(row)
            if "mesh" in rules and p.grid is not None:
                report.extend(check_mesh_uniformity(
                    p.lowered_text("hlo"), _plan_location(p)))

    if "kernels" in rules:
        report.extend(check_kernels(vmem_budget=vmem_budget))

    if "cache-key" in rules:
        from repro.api.config import SolverConfig

        report.extend(check_cache_keys(
            32, SolverConfig(strategy="sequential", v=8)))
        report.extend(check_cache_keys(
            32, SolverConfig(strategy="sequential_chol", v=8, pivot="none")))
        if n_dev >= 8:
            from repro.core.lu.grid import GridConfig

            report.extend(check_cache_keys(
                64, SolverConfig(strategy="conflux",
                                 grid=GridConfig(2, 2, 2, 8, 64))))
    return report


def bench_audit_rows(N: int = 64, v: int = 8,
                     tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """The BENCH_lu.json schema-v8 `audit` section: static comm-conformance
    numbers per strategy x backend plus the finding counts."""
    report = run_audit(N=N, v=v, tolerance=tolerance,
                       rules={"comm", "mesh"})
    return {
        "N": N, "v": v, "tolerance": tolerance,
        "rows": report.comm_rows,
        "errors": len(report.errors),
        "warnings": len(report.warnings),
    }


def _format_findings(report: AuditReport, verbose: bool = False) -> str:
    lines = []
    order = {"error": 0, "warning": 1, "info": 2}
    for f in sorted(report.findings, key=lambda f: order[f.severity]):
        if not verbose and f.severity == "info":
            continue
        lines.append(f"[{f.severity.upper():7s}] {f.rule:20s} {f.location}")
        lines.append(f"          {f.detail}")
    counts = report.to_json()["counts"]
    lines.append(
        f"audit: {counts['error']} error(s), {counts['warning']} warning(s), "
        f"{counts['info']} info finding(s) across {len(report.findings)} total")
    return "\n".join(lines)


def main(argv=None) -> int:
    # 8 host devices for the distributed combos — must land in XLA_FLAGS
    # before the backend initializes (safe here: `python -m` runs us first).
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="Static audit of every registered factorization plan: "
                    "comm-model conformance, mesh-uniform collectives, Pallas "
                    "kernel lint, cache-key completeness.")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--n", type=int, default=64, help="audit problem size")
    ap.add_argument("--v", type=int, default=8, help="panel width")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="comm-conformance relative tolerance")
    ap.add_argument("--vmem-budget", type=int, default=DEFAULT_VMEM_BUDGET,
                    help="Pallas VMEM budget in bytes")
    ap.add_argument("--rules", default="comm,mesh,kernels,cache-key",
                    help="comma-separated subset of comm,mesh,kernels,cache-key")
    ap.add_argument("--verbose", action="store_true",
                    help="also print info-severity findings")
    args = ap.parse_args(argv)

    report = run_audit(N=args.n, v=args.v, tolerance=args.tolerance,
                       vmem_budget=args.vmem_budget,
                       rules=set(args.rules.split(",")))
    print(_format_findings(report, verbose=args.verbose))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report.to_json(), fh, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return 1 if report.errors else 0


if __name__ == "__main__":
    sys.exit(main())
