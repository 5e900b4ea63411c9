"""Names of the solver's trace spans and device scopes, with one helper each.

Host spans are `jax.profiler.TraceAnnotation`s (the profiler's TraceMe):
they land on the profiler's `/host:CPU` plane, on the clock of the device
ops, and record only while a profiler session runs — otherwise entering one
is a single check.  Device scopes are `jax.named_scope`s: they change only
the `op_name` metadata of the HLO ops traced inside them, never the program.

    with jax.profiler.trace("trace"):
        x = plan(N, config).execute(A).solve(b)

Every site, test and reader takes its names from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# host spans
EXECUTE = "repro.execute"  # all of FactorizationPlan.execute
FACTOR = "repro.execute.factor"  # factor program: dispatch to a ready F
FACTOR_ARGS = ("schur_tile_share",)  # sequential plans: Schur tiles computed
SOLVE = "repro.solve"  # Factorization.solve: uploads and dispatch
COPY_H2D = "repro.copy.h2d"  # host -> device upload; arg bytes
COPY_D2H = "repro.copy.d2h"  # device -> host download; arg bytes
COPY_PREFIX = "repro.copy."
LAYOUT = "repro.layout"  # host block-cyclic scatter or gather; arg bytes
SERVE_FLUSH = "repro.serve.flush"  # one AsyncSolveEngine flush; args below
SERVE_STAGE = "repro.serve.stage"  # stacking and padding of one size bucket
SERVE_COMPLETE = "repro.serve.complete"  # set_result and latency records
FLUSH_ARGS = ("flush", "systems", "wait_s_sum", "wait_s_max")
SPANS = (EXECUTE, FACTOR, SOLVE, COPY_H2D, COPY_D2H, LAYOUT,
         SERVE_FLUSH, SERVE_STAGE, SERVE_COMPLETE)

# device scopes of the LU schedule (core/lu/sequential.py, kernels/lu_panel.py)
LU_PANEL = "lu.panel"  # panel slice, panel LUP, write-back into F
LU_LAYOUT = "lu.layout"  # transposes in and out of the panel kernel
LU_PIVOT_GATHER = "lu.pivot_gather"  # one-hot, U00, L00, pivot rows R01
LU_UPDATE = "lu.update"  # fused TRSM -> Schur and its glue
LU_PIVOT_WRITEBACK = "lu.pivot_writeback"  # U01 into the pivot rows
LU_SCOPES = (LU_PANEL, LU_LAYOUT, LU_PIVOT_GATHER, LU_UPDATE, LU_PIVOT_WRITEBACK)
# steps of the distributed schedule (core/lu/conflux.py) that the sequential
# one lacks; its other steps take the LU_SCOPES name of the same job
LU_PANEL_REDUCE = "lu.panel_reduce"  # panel block-column summed over pz
LU_TOURNAMENT = "lu.tournament"  # butterfly rounds over px and the last LUP
LU_BCAST = "lu.bcast"  # A00, pivot ids and L10 broadcast over py
CONFLUX_SCOPES = (LU_PANEL_REDUCE, LU_TOURNAMENT, LU_BCAST)


# The copy helpers check this first: the serving executor is near
# saturation, so even microseconds a copy would pay it per flush show.
_recording = jax.profiler.TraceAnnotation.is_enabled


def span(name: str, **args):
    """A host span named `name` carrying `args` as trace arguments."""
    return jax.profiler.TraceAnnotation(name, **args)


def scope(name: str):
    """A device scope: ops traced inside carry `name` in their op_name."""
    return jax.named_scope(name)


def to_device(x, dtype=None) -> jax.Array:
    """`jnp.asarray(x, dtype)`; while a profiler runs, a host array's upload
    runs in a `repro.copy.h2d` span.  The span covers the host's part of
    the copy: the call returns once the runtime has taken the host buffer,
    and the device may still be receiving (waiting there stalls every
    flush)."""
    if not _recording() or isinstance(x, jax.Array):
        return jnp.asarray(x, dtype=dtype)
    with span(COPY_H2D) as s:
        out = jnp.asarray(x, dtype=dtype)
        s.set_metadata(bytes=out.nbytes)
    return out


def to_devices(x, sharding) -> jax.Array:
    """`jax.device_put(x, sharding)`: a host array split over the devices
    of `sharding`; while a profiler runs, in one `repro.copy.h2d` span
    carrying the host array's bytes, which ends as `to_device`'s does."""
    if not _recording():
        return jax.device_put(x, sharding)
    with span(COPY_H2D, bytes=x.nbytes):
        return jax.device_put(x, sharding)


def to_host(x) -> np.ndarray:
    """`np.asarray(x)`; while a profiler runs, a device array is waited on
    first, so its `repro.copy.d2h` span holds the download alone.  An array
    whose host copy jax already keeps (`_npy_value`, set by an earlier
    download) copies nothing and gets no span."""
    if (not _recording() or not isinstance(x, jax.Array)
            or getattr(x, "_npy_value", None) is not None):
        return np.asarray(x)
    x = jax.block_until_ready(x)
    with span(COPY_D2H, bytes=x.nbytes):
        return np.asarray(x)
