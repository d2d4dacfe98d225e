"""The precision a reference computes in."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rounded(x, dtype):
    """``x`` held in ``dtype``; a float8 format with one scale per tensor,
    as a float8 path holds it (scaled so its largest magnitude is the
    format's largest).  The gradient passes through unrounded: the backward pass
    works on the rounded values in float32, as a low-precision forward
    with a wider backward does."""
    top = float(jnp.finfo(dtype).max)
    if top > 1e5:        # a format with float32's range needs no scale
        q = x.astype(dtype).astype(jnp.float32)
    else:
        scale = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
        q = (x / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def at(act_dtype: str):
    """``(act, matmul precision)``: identity at ``highest`` for float32,
    else rounding to ``act_dtype`` at JAX's default precision."""
    if act_dtype == "float32":
        return (lambda x: x), "highest"
    dt = jnp.dtype(act_dtype)
    return (lambda x: rounded(x, dt)), "default"
