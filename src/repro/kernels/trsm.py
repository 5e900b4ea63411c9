"""Block triangular solves for the COnfLUX panel updates (steps 7/9).

trsm_right_upper:  X = B @ U^-1   (L10 computation; U upper-triangular)
trsm_left_lower:   X = L^-1 @ B   (U01 computation; L unit-lower)

The v x v triangle sits in VMEM; the long dimension is tiled by the grid.
Inside a tile the solve is a fori over the v rows (forward substitution) —
v is the paper's blocking parameter (MXU-sized, <= 256).  Both solves run
the one row-oriented `forward_solve`: X U = B is U^T X^T = B^T, so the
right-upper kernel transposes its tile, the triangle and the result inside
VMEM.

Mosaic cannot index a value with the loop counter, so row i of the
right-hand side and of the triangle are read with iota-masked sums (exact:
one nonzero term), and row i of X is written with an iota select.

The `*_batched` variants solve B independent systems from one launch by
prepending a batch grid dimension — one (b, tile) program per tile, each
system with its own triangle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def forward_solve(L, B, *, unit: bool):
    """Forward substitution L X = B on fp32 values: L [v, v] lower, B [v, C]."""
    v = L.shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (v, 1), 0)
    klane = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)
    LT = L.T  # column i of LT is row i of L, laid out along the sublanes

    def body(i, X):
        li = jnp.sum(jnp.where(klane == i, LT, 0.0), axis=1, keepdims=True)
        # X rows >= i are still zero, so only the solved rows contribute.
        partial = jnp.sum(jnp.where(sub < i, li, 0.0) * X, axis=0,
                          keepdims=True)
        xi = jnp.sum(jnp.where(sub == i, B, 0.0), axis=0, keepdims=True) - partial
        if not unit:
            xi = xi / jnp.sum(jnp.where(sub == i, li, 0.0), axis=0, keepdims=True)
        return jnp.where(sub == i, xi, X)

    return jax.lax.fori_loop(0, v, body, jnp.zeros_like(B))


def _right_upper_kernel(b_ref, u_ref, x_ref):
    XT = forward_solve(u_ref[...].astype(jnp.float32).T,
                       b_ref[...].astype(jnp.float32).T, unit=False)
    x_ref[...] = XT.T.astype(x_ref.dtype)


def _left_lower_kernel(l_ref, b_ref, x_ref, *, unit: bool):
    X = forward_solve(l_ref[...].astype(jnp.float32),
                      b_ref[...].astype(jnp.float32), unit=unit)
    x_ref[...] = X.astype(x_ref.dtype)


def _vmem(*block):
    return pl.BlockSpec(*block, memory_space=pltpu.VMEM)


def trsm_right_upper(B, U, *, br: int = 256, interpret: bool = False):
    """X U = B  ->  X = B U^-1.  B [R, v], U [v, v] upper."""
    R, v = B.shape
    br = min(br, R)
    assert R % br == 0
    return pl.pallas_call(
        _right_upper_kernel,
        grid=(R // br,),
        in_specs=[_vmem((br, v), lambda i: (i, 0)),
                  _vmem((v, v), lambda i: (0, 0))],
        out_specs=_vmem((br, v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, v), B.dtype),
        interpret=interpret,
    )(B, U)


def trsm_right_upper_batched(B, U, *, br: int = 256, interpret: bool = False):
    """X_b U_b = B_b per system.  B [Bb, R, v], U [Bb, v, v] upper."""
    Bb, R, v = B.shape
    br = min(br, R)
    assert R % br == 0
    return pl.pallas_call(
        _right_upper_kernel,
        grid=(Bb, R // br),
        in_specs=[_vmem((None, br, v), lambda b, i: (b, i, 0)),
                  _vmem((None, v, v), lambda b, i: (b, 0, 0))],
        out_specs=_vmem((None, br, v), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, R, v), B.dtype),
        interpret=interpret,
    )(B, U)


def trsm_left_lower(L, B, *, bc: int = 256, unit: bool = True, interpret: bool = False):
    """L X = B  ->  X = L^-1 B.  L [v, v] (unit-)lower, B [v, C]."""
    v, C = B.shape
    bc = min(bc, C)
    assert C % bc == 0
    return pl.pallas_call(
        functools.partial(_left_lower_kernel, unit=unit),
        grid=(C // bc,),
        in_specs=[_vmem((v, v), lambda i: (0, 0)),
                  _vmem((v, bc), lambda i: (0, i))],
        out_specs=_vmem((v, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((v, C), B.dtype),
        interpret=interpret,
    )(L, B)


def trsm_left_lower_batched(L, B, *, bc: int = 256, unit: bool = True,
                            interpret: bool = False):
    """L_b X_b = B_b per system.  L [Bb, v, v] (unit-)lower, B [Bb, v, C]."""
    Bb, v, C = B.shape
    bc = min(bc, C)
    assert C % bc == 0
    return pl.pallas_call(
        functools.partial(_left_lower_kernel, unit=unit),
        grid=(Bb, C // bc),
        in_specs=[_vmem((None, v, v), lambda b, i: (b, 0, 0)),
                  _vmem((None, v, bc), lambda b, i: (b, 0, i))],
        out_specs=_vmem((None, v, bc), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((Bb, v, C), B.dtype),
        interpret=interpret,
    )(L, B)
