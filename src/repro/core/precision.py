"""Matmul precision: float32 factors that really are float32 on a TPU.

On a TPU, a float32 matmul at the default precision rounds both operands to
bfloat16 and makes one MXU pass.  The factorizations use matmuls for more
than FLOPs: one-hot row gathers (`S.T @ A`, `piv_onehot @ F`), the Schur
update and the refinement residual.  At the default precision a gather
would round every pivot row to 8 mantissa bits, so a "float32" plan would
give bfloat16 accuracy.  Every traced program of the solver therefore runs
its dots at full float32 precision; sub-4-byte operands (the bfloat16 /
float16 compute dtypes) multiply exactly at any precision, so the choice
only changes float32 work.  On the CPU both settings compute the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dot_precision(dtype) -> jax.lax.Precision:
    """Precision for a Pallas dot over `dtype` operands.

    Mosaic takes HIGHEST only for float32 operands; narrower operands get
    the default, which is exact for them."""
    if jnp.dtype(dtype).itemsize >= 4:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def full_precision(fn):
    """Trace `fn` with every XLA dot at full float32 precision.

    Wrap a function before `jax.jit`: the precision is fixed when the dots
    are traced, so the compiled program keeps it whatever the caller's
    `jax_default_matmul_precision` is."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped
