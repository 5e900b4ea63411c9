"""Masked panel LUP kernel (COnfLUX tournament local factorization, step 1).

One program factorizes an [R, v] panel held entirely in VMEM: v rounds of
(masked argmax pivot -> scale column -> rank-1 trailing update), with row
masking instead of swaps (paper §7.3).

The kernel works on the transposed panel [v, R]: panel column k is then
sublane row k, and the long R axis runs along the 128 lanes, so the panel
takes v*R*4 bytes of VMEM (2 MiB at R=16384, v=32) instead of the
4x-padded [R, v] layout.  The wrappers transpose in XLA on the way in and
out.  Mosaic cannot index a value with the loop counter, so each round
reads row k of a VMEM scratch ref (`pl.ds`), finds the pivot with a max
and an iota-min (first maximal entry, as `jnp.argmax`), and extracts the
pivot's column and entry with iota-masked sums, which are exact.

`lu_panel_batched` factorizes B independent panels from a single launch by
adding a batch grid dimension — one program per system, same per-panel
rounds — which is what keeps the MXU busy when the systems are individually
small (the many-small-systems serving workload).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs


def _kernel(pt_ref, w_ref, ft_ref, order_ref, ok_ref, t_ref):
    """The v pivot/scale/update rounds on one transposed panel [v, R].

    The rounds run in fp32 regardless of the panel dtype (a no-op for f32
    panels): bf16/f16 -> f32 is exact, so the pivot choice matches the ref
    backend's fp32-accumulating masked_lup, and only the final packed
    factors round back down.
    """
    v, R = t_ref.shape
    t_ref[...] = pt_ref[...].astype(jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (v, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    klane = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)

    def body(k, carry):
        w, order, ok = carry
        colk = t_ref[pl.ds(k, 1), :]  # panel column k, [1, R]
        cand = jnp.abs(colk) * w
        best = jnp.max(cand, axis=1, keepdims=True)
        # First maximal entry; a NaN wins, as in jnp.argmax.
        hit = (cand == best) | jnp.isnan(cand)
        p = jnp.min(jnp.where(hit, lane, R), axis=1, keepdims=True)  # [1, 1]
        at_p = lane == p
        ok = jnp.where(klane == k, (best > 0.0).astype(jnp.int32), ok)
        order = jnp.where(klane == k, p, order)
        w = w * (1.0 - at_p.astype(jnp.float32))
        pivval = jnp.sum(jnp.where(at_p, colk, 0.0), axis=1, keepdims=True)
        safe = jnp.where(jnp.abs(pivval) > 0.0, pivval, 1.0)
        active = w > 0.0
        mult = jnp.where(active, colk / safe, colk)
        t_ref[pl.ds(k, 1), :] = mult
        T = t_ref[...]
        # Pivot row p over the trailing columns (> k), as a [v, 1] column.
        prow = jnp.sum(jnp.where(at_p, T, 0.0), axis=1, keepdims=True)
        prow = prow * (sub > k).astype(jnp.float32)
        t_ref[...] = T - prow * jnp.where(active, mult, 0.0)
        return w, order, ok

    zeros = jnp.zeros((1, v), jnp.int32)
    _, order, ok = jax.lax.fori_loop(
        0, v, body, (w_ref[...].astype(jnp.float32), zeros, zeros)
    )
    ft_ref[...] = t_ref[...].astype(ft_ref.dtype)
    order_ref[...] = order
    ok_ref[...] = ok


def _vmem_limit(v: int, R: int) -> int:
    """Scoped VMEM for one [v, R] panel program: the double-buffered in/out
    blocks, the fp32 scratch and a few fp32 panel-sized temporaries, within
    the 128 MiB of a v5e core."""
    return min(100 * 2**20, max(32 * 2**20, 12 * v * R * 4))


def _call(pt, w, batch: int | None, *, interpret: bool):
    v, R = pt.shape[-2:]
    lead = () if batch is None else (batch,)
    bdim = () if batch is None else (None,)

    def spec(*tail):
        if batch is None:
            return pl.BlockSpec(tail, lambda: (0,) * len(tail),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec(bdim + tail, lambda b: (b,) + (0,) * len(tail),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        _kernel,
        grid=lead,
        in_specs=[spec(v, R), spec(1, R)],
        out_specs=[spec(v, R), spec(1, v), spec(1, v)],
        out_shape=[
            jax.ShapeDtypeStruct(lead + (v, R), pt.dtype),
            jax.ShapeDtypeStruct(lead + (1, v), jnp.int32),
            jax.ShapeDtypeStruct(lead + (1, v), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((v, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(v, R)),
        interpret=interpret,
    )(pt, w)


def lu_panel(panel, weights, *, interpret: bool = False):
    """Masked LUP of panel [R, v] with candidate weights [R].

    Returns (F [R, v] packed factors, order [v] pivot rows, ok [v] validity).
    """
    R, v = panel.shape
    with obs.scope(obs.LU_LAYOUT):
        pt = panel.T
    ft, order, ok = _call(pt, weights.reshape(1, R), None,
                          interpret=interpret)
    with obs.scope(obs.LU_LAYOUT):
        f = ft.T
    return f, order.reshape(v), ok.reshape(v)


def lu_panel_batched(panel, weights, *, interpret: bool = False):
    """Masked LUP of B independent panels [B, R, v], weights [B, R].

    One (b,) grid program per system — B small panel factorizations from a
    single kernel launch.  Returns (F [B, R, v], order [B, v], ok [B, v]).
    """
    B, R, v = panel.shape
    with obs.scope(obs.LU_LAYOUT):
        pt = jnp.swapaxes(panel, 1, 2)
    ft, order, ok = _call(pt, weights.reshape(B, 1, R), B, interpret=interpret)
    with obs.scope(obs.LU_LAYOUT):
        f = jnp.swapaxes(ft, 1, 2)
    return f, order.reshape(B, v), ok.reshape(B, v)
