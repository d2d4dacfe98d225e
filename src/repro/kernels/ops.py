"""Jit'd public wrappers around the Pallas kernels (padding, reshaping,
candidate combine).  These are what the rest of the framework calls.

The arena's sparse scatter-adds (:func:`scatter_add`,
:func:`scatter_add_row`, :func:`scatter_add_rows`) are XLA scatters on
every backend, one per arena row, in place in the donated buffer.  The
blocked Pallas scatter (:func:`scatter_apply`, :func:`scatter_apply_rows`)
streams every block of its rows through VMEM; the blockwise engine's
support repair uses it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import resolve_interpret
from .block_topk import BLOCK, GROUP, block_topk_2d
from .samomentum_kernel import BLOCK_ROWS, LANE, samomentum_fused_2d


# Most updates one dense block of the scatter kernel takes in-kernel; the
# rest of that block's updates spill to an XLA scatter (exact either way).
# Bounds the bucket operands at n_blocks * SCATTER_CAP slots whatever k is.
SCATTER_CAP = 256


def _pad_to(x, multiple):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, (0, pad))
    return x, pad


@partial(jax.jit, static_argnames=("momentum", "lr", "interpret"))
def samomentum_fused(u, g, thr, *, momentum: float, lr: float,
                     interpret: bool | None = None):
    """Fused SAMomentum over an arbitrary-shape tensor.

    Returns (sent_dense, u_new): sent_dense is the thresholded velocity in
    dense layout (zeros where unsent); u_new the rescaled velocity.
    """
    shape = u.shape
    flat_u, _ = _pad_to(u.reshape(-1), BLOCK_ROWS * LANE)
    flat_g, _ = _pad_to(g.reshape(-1).astype(u.dtype), BLOCK_ROWS * LANE)
    u2d = flat_u.reshape(-1, LANE)
    g2d = flat_g.reshape(-1, LANE)
    out, unew = samomentum_fused_2d(u2d, g2d, jnp.asarray(thr),
                                    momentum=momentum, lr=lr,
                                    interpret=interpret)
    n = u.size
    return (out.reshape(-1)[:n].reshape(shape),
            unew.reshape(-1)[:n].reshape(shape))


@partial(jax.jit, static_argnames=("r", "interpret"))
def block_topk_candidates(x, *, r: int, interpret: bool | None = None):
    """Per-block top-r winners of |x|.  Returns (vals, global_idx), each
    (nb, r); padding elements (|x| = 0 at index >= x.size) may appear only
    when a block is entirely padding."""
    flat, _ = _pad_to(x.reshape(-1), BLOCK * GROUP)
    x2d = flat.reshape(-1, BLOCK)
    vals, idx = block_topk_2d(x2d, r=r, interpret=interpret)
    gidx = idx + (jnp.arange(x2d.shape[0], dtype=jnp.int32) * BLOCK)[:, None]
    return vals, gidx


@partial(jax.jit, static_argnames=("k", "r", "interpret"))
def hierarchical_topk(x, *, k: int, r: int | None = None,
                      interpret: bool | None = None):
    """Top-k |x| selection via block winners + candidate top-k.

    Exact iff r >= k; production callers pass r << k for the approximate
    (oversampled) mode.  Returns (values, indices) into flattened x.
    """
    if r is None:
        r = k
    r = min(r, BLOCK)
    vals, gidx = block_topk_candidates(x, r=r, interpret=interpret)
    cvals = vals.reshape(-1)
    cidx = gidx.reshape(-1)
    # padding candidates (index >= x.size, |x| = 0) rank strictly below every
    # real candidate, so they can only be selected when k exceeds the number
    # of real candidates
    mag = jnp.where(cidx < x.size, jnp.abs(cvals), -1.0)
    _, sel = jax.lax.top_k(mag, min(k, cvals.shape[0]))
    return cvals[sel], cidx[sel]


def scatter_add(dense, indices, values):
    """One fused scatter-add on a flat arena: ``dense.at[indices].add(v)``.

    The entry point behind the arena runtime's flat scatters (server
    receive, worker apply).  One XLA scatter on every backend: inside a
    stage program that donates ``dense`` it updates the buffer in place,
    touching only the indexed values.  Duplicate indices accumulate.
    """
    return dense.at[indices].add(values.astype(dense.dtype))


def scatter_add_row(dense2d, row, indices, values):
    """``dense2d.at[row, indices].add(values)`` — one worker row of the
    server's ``v`` buffer.

    The row is sliced out, scattered into as a flat arena
    (:func:`scatter_add`) and written back in place.  A 2-D scatter
    straight into ``dense2d`` would be simpler, but the TPU compiler
    lowers it by copying the whole ``(n_workers, total)`` buffer to a
    flat layout and back; this form moves one row each way.
    """
    flat = jax.lax.dynamic_index_in_dim(dense2d, row, 0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        dense2d, scatter_add(flat, indices, values), row, 0)


def scatter_add_rows(dense2d, rows, idx2d, vals2d):
    """Batched multi-row scatter-add — the batched commit and apply
    stages' scatter.

    ``dense2d.at[rows[b], idx2d[b]].add(vals2d[b])`` for every batch lane
    ``b``: one :func:`scatter_add_row` per lane, in lane order, in place
    where the stage program donates ``dense2d``.  The batching rule
    (``async_sim.batch_schedule``) makes ``rows`` pairwise distinct, so
    the lanes touch disjoint rows and any serial order of
    :func:`scatter_add_row` calls gives the same bits.
    """
    for b in range(rows.shape[0]):
        dense2d = scatter_add_row(dense2d, rows[b], idx2d[b], vals2d[b])
    return dense2d


def _bucket_blocked(nb: int, cap: int, indices, values):
    """Bucket flat scatter updates by dense block (stable sort + rank).

    Returns ``(vals2d, offs2d, i_s, v_s, ok)``: the ``(nb, cap)`` kernel
    inputs (block-local offsets, -1 = padding, real slots first in each
    bucket) and the sorted updates with ``ok`` marking the ones that got a
    slot; the rest (rank >= ``cap`` in their block) are the spill the
    wrapper applies with an XLA scatter, so the op stays exact.
    """
    from .scatter_apply import BLOCK
    k = values.shape[0]
    block_of = indices // BLOCK
    order = jnp.argsort(block_of, stable=True)
    b_s = block_of[order]
    i_s = indices[order]
    v_s = values[order].astype(jnp.float32)
    rank = jnp.arange(k, dtype=jnp.int32) - jnp.searchsorted(
        b_s, b_s, side="left").astype(jnp.int32)
    ok = rank < cap
    slot = jnp.where(ok, b_s * cap + rank, nb * cap)
    vals2d = jnp.zeros((nb * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(ok, v_s, 0.0))[:-1].reshape(nb, cap)
    offs2d = jnp.full((nb * cap + 1,), -1, jnp.int32).at[slot].set(
        jnp.where(ok, i_s % BLOCK, -1))[:-1].reshape(nb, cap)
    return vals2d, offs2d, i_s, v_s, ok


@partial(jax.jit, static_argnames=("cap", "interpret"))
def scatter_apply(dense, indices, values, *, cap: int | None = None,
                  interpret: bool | None = None):
    """dense.at[indices].add(values) via the blocked Pallas kernel.

    One row of :func:`scatter_apply_rows`.  Duplicate indices accumulate.
    """
    out = scatter_apply_rows(dense.reshape(1, -1), indices[None],
                             values[None], cap=cap, interpret=interpret)
    return out.reshape(dense.shape)


@partial(jax.jit, static_argnames=("cap", "interpret"))
def scatter_apply_rows(dense2d, idx2d, vals2d, *, cap: int | None = None,
                       interpret: bool | None = None):
    """Row-wise ``dense2d[b].at[idx2d[b]].add(vals2d[b])`` via ONE blocked
    Pallas dispatch.

    Each lane's updates are bucketed by dense block (sort + rank, vmapped
    over the lanes) into at most ``cap`` slots per block (default
    ``min(k, SCATTER_CAP)``); every lane's blocks then stream through VMEM
    in a single pallas_call.  Updates past ``cap`` in their block are
    applied afterwards by an XLA scatter, so the result is exact for any
    index distribution while the bucket operands stay
    ``n_rows * n_blocks * cap`` slots.
    """
    from .scatter_apply import BLOCK, LANE, ROWS, scatter_apply_blocked
    n_rows, n = dense2d.shape
    pad = (-n) % BLOCK
    flat = jnp.pad(dense2d, ((0, 0), (0, pad))) if pad else dense2d
    nb = flat.shape[1] // BLOCK
    k = vals2d.shape[1]
    cap = max(1, min(k, cap or SCATTER_CAP))
    v3, o3, i_s, v_s, ok = jax.vmap(
        lambda i, v: _bucket_blocked(nb, cap, i, v))(idx2d, vals2d)
    out = scatter_apply_blocked(
        flat.reshape(n_rows * nb, ROWS, LANE), v3.reshape(-1, cap),
        o3.reshape(-1, cap), interpret=resolve_interpret(interpret))
    out = out.reshape(n_rows, nb * BLOCK)
    # spill: entries with a slot index past the padded row are dropped
    out = out.at[jnp.arange(n_rows, dtype=jnp.int32)[:, None],
                 jnp.where(ok, nb * BLOCK, i_s)].add(
        v_s.astype(out.dtype), mode="drop")
    return out[:, :n]


# ---------------------------------------------------------------------------
# shard routing — the in-graph half of the alltoallv exchange
# ---------------------------------------------------------------------------

def route_by_shard(indices, values, *, bounds, n_shards: int, cap: int):
    """Bucket one global-index sparse message into per-shard slots.

    ``indices``: ``(k,)`` int32 global arena indices (``-1`` marks padding);
    ``values``: ``(k,)``.  ``bounds`` is the ``(S+1,)`` ascending
    ``ShardSpec.bounds`` array; ownership is the in-graph twin of the
    host-side ``ShardSpec.owner_of`` (``searchsorted(bounds, i, "right")-1``,
    so duplicate bounds from empty shards resolve to the non-empty owner).

    Returns ``(local_idx, vals, overflow)``: ``(S, cap)`` shard-LOCAL
    indices (``-1`` = empty slot) and values, plus a scalar int32 count of
    real entries dropped because their shard already held ``cap`` — callers
    that need exactness must size ``cap >= k`` (or prove a tighter bound,
    see ``distributed.shard_exchange_batch``).

    The slot math is the same stable sort + rank idiom as
    :func:`_bucket_blocked`.
    """
    ri, rv, ovf = route_by_shard_batch(indices[None], values[None],
                                       bounds=bounds, n_shards=n_shards,
                                       cap=cap)
    return ri[0], rv[0], ovf


def route_by_shard_batch(indices, values, *, bounds, n_shards: int, cap: int):
    """Batched :func:`route_by_shard` over ``(N, k)`` chunks with ONE
    scatter dispatch.

    Every chunk's slots are offset by ``chunk * (S*cap + 1)`` into a
    single flat buffer, so one XLA scatter places the whole batch.  Each
    real entry owns its slot (only the per-chunk dump slot is shared, and
    it is cut off), so the placement is a ``set``: no accumulation, and
    nothing for the blocked scatter kernel to do.
    Returns ``(local_idx, vals, overflow)`` shaped ``(N, S, cap)`` /
    ``(N, S, cap)`` / scalar.
    """
    S = int(n_shards)
    cap = int(cap)
    n, k = indices.shape
    bounds = jnp.asarray(bounds, jnp.int32)
    # padding entries (-1) route to the virtual shard S and are dropped
    owner = jnp.where(
        indices < 0, jnp.int32(S),
        jnp.searchsorted(bounds, indices, side="right").astype(jnp.int32) - 1)
    order = jnp.argsort(owner, axis=1, stable=True)
    o_s = jnp.take_along_axis(owner, order, axis=1)
    i_s = jnp.take_along_axis(indices, order, axis=1)
    v_s = jnp.take_along_axis(values, order, axis=1).astype(jnp.float32)
    first = jax.vmap(
        lambda o: jnp.searchsorted(o, o, side="left"))(o_s).astype(jnp.int32)
    rank = jnp.arange(k, dtype=jnp.int32)[None, :] - first
    real = o_s < S
    ok = (rank < cap) & real
    row_len = S * cap + 1  # one dump slot per chunk
    slot = jnp.where(ok, o_s * cap + rank, S * cap)
    local = i_s - bounds[jnp.clip(o_s, 0, S - 1)]
    flat = (slot + jnp.arange(n, dtype=jnp.int32)[:, None] * row_len).reshape(-1)
    rv = jnp.zeros((n * row_len,), jnp.float32).at[flat].set(
        jnp.where(ok, v_s, 0.0).reshape(-1))
    rv = rv.reshape(n, row_len)[:, :-1].reshape(n, S, cap)
    ri = jnp.full((n * row_len,), -1, jnp.int32).at[flat].set(
        jnp.where(ok, local, -1).reshape(-1))
    ri = ri.reshape(n, row_len)[:, :-1].reshape(n, S, cap)
    overflow = jnp.sum(real & (rank >= cap)).astype(jnp.int32)
    return ri, rv, overflow
