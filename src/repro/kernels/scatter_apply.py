"""Blocked sparse scatter-apply — Pallas TPU kernel.

Applying a decoded sparse update (``dense.at[idx].add(vals)``) is a random
scatter: on TPU the efficient form is to pre-bucket the updates by parameter
block (a sort on the XLA side of the op), then stream each dense block
through VMEM exactly once and apply its updates with on-chip dynamic row
stores.  One HBM round-trip for the parameter vector, no atomics (the TPU
grid is sequential), contiguous DMA for the parameter blocks.

Layout: the dense rows are viewed as ``(n_blocks, ROWS, LANE)`` f32 tiles
(one block = ``BLOCK`` = ``ROWS * LANE`` elements, tile-aligned); the
updates arrive pre-bucketed as ``(n_blocks, cap)`` value/offset pairs with
offset == -1 marking padding.  The bucket rows sit in SMEM, where the
kernel reads one scalar (offset, value) pair per update and rewrites the
single ``(1, LANE)`` row it lands in.  Bucket slots are filled in rank
order, so the first padding slot ends a block's loop.

``cap`` is bounded (``ops.SCATTER_CAP``), not ``k``: a block holding more
than ``cap`` updates spills the surplus to an XLA scatter in the wrapper,
so the op stays exact while the bucket operands stay
``O(n_blocks * cap)`` however large ``k`` grows.

Every block of every row streams through the kernel, touched or not, so
it pays off only where updates are dense in the blocks: the blockwise
engine's support repair (``ops.scatter_apply``).  The flat-arena
runtime's receive, commit and apply, a few updates per mille of a row,
take the in-place XLA scatter of ``ops.scatter_add*`` instead.  The
multi-row form (``ops.scatter_apply_rows``) stacks every lane's blocks
into one ``(n_rows * n_blocks, ROWS, LANE)`` grid.

Semantics contract: kernels/ref.py::scatter_accumulate_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

LANE = 128
ROWS = 64                  # f32 sublane rows per block (8 tiles of 8 x 128)
BLOCK = ROWS * LANE        # dense elements per block


def _kernel(vals_ref, offs_ref, dense_ref, out_ref):
    out_ref[...] = dense_ref[...]
    cap = offs_ref.shape[2]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

    def cond(j):
        return jnp.logical_and(j < cap, offs_ref[0, 0, jnp.minimum(j, cap - 1)]
                               >= 0)

    def body(j):
        off = offs_ref[0, 0, j]
        r = off // LANE
        row = out_ref[0, pl.ds(r, 1), :]
        out_ref[0, pl.ds(r, 1), :] = jnp.where(
            lanes == off % LANE, row + vals_ref[0, 0, j], row)
        return j + 1

    jax.lax.while_loop(cond, body, jnp.int32(0))


def scatter_apply_blocked(dense3d, vals2d, offs2d, *,
                          interpret: bool | None = None):
    """dense3d: (nb, ROWS, LANE); vals2d/offs2d: (nb, cap) bucketed updates
    (offset local to the block, -1 = padding, padding only after every
    real slot of its block).  Returns the updated dense3d."""
    nb, cap = vals2d.shape
    assert dense3d.shape == (nb, ROWS, LANE), (dense3d.shape, nb)
    spec_d = pl.BlockSpec((1, ROWS, LANE), lambda i: (i, 0, 0))
    # (1, 1, cap) blocks of an (nb, 1, cap) view: the last two block dims
    # equal the array's, as the TPU block rule asks of SMEM operands too
    spec_u = pl.BlockSpec((1, 1, cap), lambda i: (i, 0, 0),
                          memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[spec_u, spec_u, spec_d],
        out_specs=spec_d,
        out_shape=jax.ShapeDtypeStruct(dense3d.shape, dense3d.dtype),
        interpret=resolve_interpret(interpret),
    )(vals2d.astype(jnp.float32).reshape(nb, 1, cap),
      offs2d.reshape(nb, 1, cap), dense3d)
