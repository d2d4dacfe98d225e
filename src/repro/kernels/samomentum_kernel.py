"""Fused SAMomentum update — Pallas TPU kernel.

The SAMomentum inner loop (velocity accumulate -> threshold compare ->
rescale unsent) is four elementwise HBM passes when written naively
(u read, g read, u write, out write, plus the compare).  On TPU this is
purely memory-bound, so fusing it into one pass over VMEM tiles halves the
HBM traffic of the optimizer stage (see EXPERIMENTS.md §Perf).

Layout: the flattened tensor is viewed as (rows, 128) — lane dim 128, tile
sublane 8 — and the grid walks row-blocks.  The magnitude threshold ``thr``
(computed by block_topk.py or a sampled estimator) arrives as a (1, 1)
scalar prefetch block.

Semantics contract: kernels/ref.py::samomentum_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

LANE = 128
BLOCK_ROWS = 256     # (256, 128) f32 tile = 128 KiB VMEM per operand


def _kernel(thr_ref, u_ref, g_ref, out_ref, unew_ref, *, momentum, lr):
    u = u_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    thr = thr_ref[0, 0]
    uacc = momentum * u + lr * g
    sent = jnp.abs(uacc) >= thr
    out_ref[...] = jnp.where(sent, uacc, 0.0).astype(out_ref.dtype)
    unew_ref[...] = jnp.where(sent, uacc, uacc / momentum).astype(
        unew_ref.dtype)


def samomentum_fused_2d(u2d, g2d, thr, *, momentum: float, lr: float,
                        interpret: bool | None = None):
    """u2d/g2d: (rows, 128) with rows % BLOCK_ROWS == 0. thr: (1,1) f32."""
    rows = u2d.shape[0]
    assert u2d.shape[1] == LANE and rows % BLOCK_ROWS == 0, u2d.shape
    grid = (rows // BLOCK_ROWS,)
    spec = pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct(u2d.shape, u2d.dtype),
        jax.ShapeDtypeStruct(u2d.shape, u2d.dtype),
    ]
    return pl.pallas_call(
        functools.partial(_kernel, momentum=momentum, lr=lr),
        grid=grid,
        in_specs=[scalar_spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(thr.reshape(1, 1).astype(jnp.float32), u2d, g2d)
