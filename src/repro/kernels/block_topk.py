"""Hierarchical block top-k candidate selection — Pallas TPU kernel.

Exact ``lax.top_k`` over a 100M-element gradient is a full sort on TPU.  The
paper's threshold ("R% of |v|") doesn't need a sort: DGS only needs the top
~k set.  This kernel adapts the hierarchical-selection idea to the TPU
memory hierarchy: each VMEM-resident block of 1024 elements emits its local
top-r by magnitude via r (max, mask) reduction sweeps on the VPU, run as
one loop (no unroll, so any r up to the block size compiles) — no sort, no
gather, one HBM pass.  A cheap host-side ``lax.top_k`` over the nb*r
candidates then yields the final selection:

* exact whenever r >= k (every global winner is a block winner), used by
  tests;
* with r = oversample * k/nb it is the production approximation (same
  spirit as DGC's sampled threshold; gradient sparsification tolerates it —
  unsent mass stays in the SAMomentum velocity).

Layout: (nb, block) view, block = 8 sublanes x 128 lanes; grid walks
row-groups of G blocks.

Semantics contract: kernels/ref.py::block_topk_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

BLOCK = 1024     # elements per block (8 x 128 tile)
GROUP = 8        # blocks per kernel invocation


def _kernel(x_ref, vals_ref, idx_ref):
    x = x_ref[...].astype(jnp.float32)          # (G, BLOCK)
    g, r = vals_ref.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (g, r), 1)

    def sweep(j, carry):
        # one (max, mask) selection sweep: the lowest column holding the
        # row's largest remaining |x| (lax.top_k's tie order), its signed
        # value read by a masked max reduction — no gather
        mag, vals, idx = carry
        top = jnp.max(mag, axis=1, keepdims=True)
        m = jnp.min(jnp.where(mag == top, cols, x.shape[1]), axis=1,
                    keepdims=True)
        hit = cols == m
        v = jnp.max(jnp.where(hit, x, -jnp.inf), axis=1, keepdims=True)
        vals = jnp.where(slots == j, v, vals)
        idx = jnp.where(slots == j, m, idx)
        return jnp.where(hit, -jnp.inf, mag), vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, r, sweep, (jnp.abs(x), jnp.zeros((g, r), jnp.float32),
                      jnp.zeros((g, r), jnp.int32)))
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx


def block_topk_2d(x2d, *, r: int, interpret: bool | None = None):
    """x2d: (nb, BLOCK), nb % GROUP == 0 -> (vals (nb, r), idx (nb, r) local
    per-block indices)."""
    nb = x2d.shape[0]
    assert x2d.shape[1] == BLOCK and nb % GROUP == 0, x2d.shape
    assert 1 <= r <= BLOCK, r
    grid = (nb // GROUP,)
    in_spec = pl.BlockSpec((GROUP, BLOCK), lambda i: (i, 0))
    out_spec = pl.BlockSpec((GROUP, r), lambda i: (i, 0))
    vals, idx = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nb, r), x2d.dtype),
            jax.ShapeDtypeStruct((nb, r), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(x2d)
    return vals, idx
