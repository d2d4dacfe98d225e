"""Model-difference-tracking parameter server (paper §4, Algorithm 2).

The server never stores the global model. It stores

* ``M``   — the accumulated update,  M_t = theta_t - theta_0   (Eq. 2)
* ``v_k`` — per worker k, the accumulation of everything already shipped to
            worker k.  Invariant (Eq. 4): after serving worker k at time t,
            v_k == M_t (without secondary compression).

Upward:   M <- M - decode(g_k)                      (Alg. 2 line 3; the worker
          message already contains the learning rate, see samomentum.py)
Downward: G_k <- M - v_k ;  v_k <- v_k + G_k        (Eq. 3/4)
          with optional secondary compression        (Eq. 6a/6b):
          G_k <- sparse(M - v_k) ; v_k <- v_k + G_k  (remainder implicitly
          accumulates in (M - v_k) and ships once large enough)

State lives in the FLAT PARAMETER ARENA (core/paramspace.py, DESIGN.md §8):
``M`` is one contiguous ``(total,)`` f32 buffer and ``v`` one
``(n_workers, total)`` buffer; messages are a single global-index
:class:`~repro.core.sparsify.SparseLeaf` over the arena (or one dense
``(total,)`` vector).  Receive, commit, and worker apply are therefore ONE
fused scatter-add each (``kernels.ops.scatter_add*`` — one XLA scatter
per arena row, in place in the buffer the stage program donates) instead
of one small scatter per tensor per event.
Secondary *selection* stays paper-faithful per-tensor top-k: the arena is
offset-sliced back into leaf views, each selected through the engine
registry, and the indices rebased by leaf offset (``ParamSpace.select``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.telemetry.trace import LAYER

from . import engine as engine_lib
from .engine import CompressionSpec
from .paramspace import ParamSpace, ShardSpec
from .sparsify import SparseLeaf


def _scatter_add(dense: jax.Array, idx: jax.Array, vals: jax.Array):
    from repro.kernels import ops
    return ops.scatter_add(dense, idx, vals)


def _scatter_add_row(dense2d, row, idx, vals):
    from repro.kernels import ops
    return ops.scatter_add_row(dense2d, row, idx, vals)


class ServerState(NamedTuple):
    M: jax.Array        # (total,) f32 arena
    v: jax.Array        # (n_workers, total) f32
    t: jax.Array        # scalar int32 update timestamp
    space: ParamSpace   # static arena descriptor (registered-static pytree)


def init(params, n_workers: int) -> ServerState:
    space = ParamSpace.from_tree(params)
    return ServerState(M=jnp.zeros((space.total,), jnp.float32),
                       v=jnp.zeros((n_workers, space.total), jnp.float32),
                       t=jnp.zeros((), jnp.int32),
                       space=space)


def receive(state: ServerState, msg) -> ServerState:
    """Apply one worker's (sparse or dense) arena update message to M."""
    with jax.named_scope(LAYER.receive):
        if isinstance(msg, SparseLeaf):
            new_M = _scatter_add(state.M, msg.indices, -msg.values)
        else:  # dense flat arena (ASGD)
            new_M = state.M - msg
        return state._replace(M=new_M, t=state.t + 1)


def send_select(
    state: ServerState,
    worker_id,
    *,
    secondary_density: float | None = None,
    spec: CompressionSpec = engine_lib.EXACT_SPEC,
):
    """Select the RAW (unquantized) downward message G_k; no state change.

    Splitting selection from the ``v_k`` update lets the cluster runtime
    interpose the wire codec: the codec quantizes values during encode and
    :func:`send_commit` is then fed exactly what the client decoded, so
    server bookkeeping always tracks the shipped bits.
    """
    with jax.named_scope(LAYER.receive):
        diff = state.M - state.v[worker_id]
    if secondary_density is None:
        return diff
    spec_raw = dataclasses.replace(spec, quantize="none")
    return state.space.select(diff, state.space.ks(secondary_density),
                              spec_raw)


def send_commit(state: ServerState, worker_id, G) -> ServerState:
    """Account the SHIPPED message into v_k (Eq. 4).

    ``G`` must be what the worker actually receives — after any wire
    quantization.  A dense G means "everything": v_k snaps to M exactly
    (``v + (M - v)`` would lose bits to f32 cancellation).
    """
    with jax.named_scope(LAYER.commit):
        if isinstance(G, SparseLeaf):
            new_v = _scatter_add_row(state.v, worker_id, G.indices,
                                     G.values)
        else:
            new_v = state.v.at[worker_id].set(state.M)
    return state._replace(v=new_v)


def send_commit_rows(state: ServerState, worker_ids, G,
                     M_rows=None) -> ServerState:
    """Account a whole batch of SHIPPED messages into their ``v`` rows.

    The batched event loop's commit stage (Eq. 4, one event per batch
    lane).  ``worker_ids`` must be pairwise distinct — the scheduler's
    batching rule (``async_sim.batch_schedule``) guarantees it — so the
    rows are disjoint and the multi-row scatter
    (``kernels.ops.scatter_add_rows``, one flat scatter per row, in place
    in the donated ``v``) is bit-equal to committing the events one
    :func:`send_commit` at a time in any order.

    ``G`` is the stacked shipped batch: one SparseLeaf with ``(B, k)``
    values/indices, or a dense ``(B, total)`` stack.  Dense commits snap
    each row to the server's M *as of that event* (the same
    cancellation-avoiding rule as :func:`send_commit`), which is the
    ``M_rows[i]`` prefix state the batched receive scan captured — not
    the post-batch M.
    """
    if isinstance(G, SparseLeaf):
        from repro.kernels import ops
        with jax.named_scope(LAYER.commit):
            new_v = ops.scatter_add_rows(state.v, worker_ids, G.indices,
                                         G.values)
    else:
        if M_rows is None:
            raise ValueError("dense batched commit needs the per-event "
                             "prefix M_rows (see batched_server_step_fn)")
        with jax.named_scope(LAYER.commit):
            new_v = state.v.at[worker_ids].set(M_rows)
    return state._replace(v=new_v)


def send(
    state: ServerState,
    worker_id,
    *,
    secondary_density: float | None = None,
    spec: CompressionSpec = engine_lib.EXACT_SPEC,
):
    """Produce the model-difference message G_k for ``worker_id``.

    Returns (new_state, G) where G is one dense ``(total,)`` arena vector
    (no secondary compression — G is *implicitly* sparse, its true nnz is
    accounted for communication metrics) or one global-index SparseLeaf
    (secondary compression, Alg. 2 lines 5-11, per-tensor selection through
    the engine named by ``spec``).  Composition of :func:`send_select` +
    in-spec wire quantization + :func:`send_commit`.
    """
    G = send_select(state, worker_id,
                    secondary_density=secondary_density, spec=spec)
    if isinstance(G, SparseLeaf):
        G = engine_lib.quantize_arena(G, spec.quantize,
                                      state.space.ks(secondary_density))
    return send_commit(state, worker_id, G), G


def add_worker(state: ServerState) -> tuple[ServerState, int]:
    """Grow v by one zero row (elastic join); returns the new slot id.

    A fresh slot has v_k = 0, so a joining client starting from theta_0 is
    brought fully up to date by its first downward message (G = M - 0).
    """
    new_id = int(state.v.shape[0])
    new_v = jnp.concatenate(
        [state.v, jnp.zeros((1,) + state.v.shape[1:], state.v.dtype)])
    return state._replace(v=new_v), new_id


def reset_worker(state: ServerState, worker_id: int) -> ServerState:
    """Zero a departed worker's v row so the slot can serve a new client
    (which starts from theta_0 and must receive all of M on first send)."""
    return state._replace(v=state.v.at[worker_id].set(0.0))


def apply_update(theta: jax.Array, G) -> jax.Array:
    """Worker-side arena update  theta <- theta + G  (Eq. 5) — ONE scatter."""
    with jax.named_scope(LAYER.apply):
        if isinstance(G, SparseLeaf):
            return _scatter_add(theta, G.indices, G.values)
        return theta + G.astype(theta.dtype)


def apply_to_params(params, G):
    """Pytree convenience wrapper around :func:`apply_update`."""
    space = ParamSpace.from_tree(params)
    return space.unpack(apply_update(space.pack(params), G))


def global_model(params0, state):
    """theta_t = theta_0 + M_t (Eq. 2) — used by tests and evaluation.

    Accepts the flat :class:`ServerState` or the stacked
    :class:`MeshServerState` (whose padded M concatenates back to the same
    global arena bit-for-bit)."""
    space = state.space
    M = mesh_arena(state) if isinstance(state, MeshServerState) else state.M
    return space.unpack(space.pack(params0) + M)


def message_nnz(G) -> int:
    """True non-zero count of a downward message (comm accounting)."""
    if isinstance(G, SparseLeaf):
        return int(G.values.shape[0])
    return int(jnp.sum(G != 0.0))


# ---------------------------------------------------------------------------
# Sharded parameter server (DESIGN.md §12).  A shard is NOT a new state
# type: it is a plain ServerState over the sub-arena of the tensors a
# leaf-aligned ShardSpec assigns to it.  Every per-shard stage is therefore
# literally the fused single-scatter op above, and because shard index
# ranges are disjoint, running the shards independently reproduces the
# single-server arithmetic bit-for-bit (scatter-adds over disjoint ranges
# commute) while per-shard M/v memory and commit work scale down with S.
# ---------------------------------------------------------------------------

def shard_params(params, shard_spec: ShardSpec) -> list[list]:
    """Per-shard leaf lists of a parameter pytree (leaf-aligned spec)."""
    leaves = jax.tree.leaves(params)
    return [shard_spec.shard_leaves(leaves, s)
            for s in range(shard_spec.n_shards)]


def init_shards(params, n_workers: int, n_shards: int,
                shard_spec: ShardSpec | None = None,
                ) -> tuple[ShardSpec, tuple[ServerState, ...]]:
    """Range-partition the arena into ``n_shards`` independent servers.

    Returns ``(shard_spec, states)`` where ``states[s]`` is a regular
    :class:`ServerState` whose arena is shard ``s``'s contiguous index
    range ``[bounds[s], bounds[s+1])`` of the global arena — M, v, and
    every derived buffer are per-shard slices.
    """
    space = ParamSpace.from_tree(params)
    if shard_spec is None:
        shard_spec = ShardSpec.for_space(space, n_shards)
    if shard_spec.leaf_splits is None:
        raise ValueError("the sharded server needs a leaf-aligned "
                         "ShardSpec (ShardSpec.for_space)")
    states = tuple(init(part, n_workers)
                   for part in shard_params(params, shard_spec))
    return shard_spec, states


def global_model_shards(params0, states) -> "object":
    """theta_t from per-shard states: shard M slices concatenate (shard
    order == leaf order for a leaf-aligned spec) back into the global
    arena — bit-equal to the single-server :func:`global_model`."""
    space = ParamSpace.from_tree(params0)
    M = jnp.concatenate([st.M for st in states if st.space.total])
    return space.unpack(space.pack(params0) + M)


# ---------------------------------------------------------------------------
# Device-mesh sharded server (DESIGN.md §14).  Instead of S host threads
# each owning a ServerState slice (above), ALL shard arenas live in one
# stacked (S, width) / (n_workers, S, width) pair so one jitted stage runs
# every shard server at once — a `shards` mesh axis places the stacks
# across devices, and global-index messages reach their owner shard via
# the in-graph alltoallv route (`distributed.shard_exchange_batch`).
# Rows are padded to a common width and masked at the true shard bounds:
# padding columns hold zeros, are never routed to (local indices are
# < sizes[s] by construction), and are sliced away by `mesh_concat` —
# so ragged and empty shards stay legal and the arithmetic is bit-equal
# to the flat server.
# ---------------------------------------------------------------------------

class MeshServerState(NamedTuple):
    M: jax.Array        # (S, width) f32, row s = shard s's arena, padded
    v: jax.Array        # (n_workers, S, width) f32
    t: jax.Array        # scalar int32 update timestamp
    overflow: jax.Array  # scalar int32 route-capacity drops (0 with the
                         # default cap — see shard_exchange_batch)
    space: ParamSpace   # static GLOBAL arena descriptor
    spec: ShardSpec     # static range partition (registered-static)


def mesh_width(spec: ShardSpec) -> int:
    """Common padded row width: ``even_stride`` unless a leaf-aligned
    shard is bigger (``for_space`` keeps tensors whole, so a shard may
    exceed the even stride)."""
    return max([ShardSpec.even_stride(spec.total, spec.n_shards),
                *spec.sizes])


def init_mesh_shards(params, n_workers: int, n_shards: int,
                     shard_spec: ShardSpec | None = None) -> MeshServerState:
    """Stacked mesh twin of :func:`init_shards` — one state, all shards."""
    space = ParamSpace.from_tree(params)
    if shard_spec is None:
        shard_spec = ShardSpec.for_space(space, n_shards)
    if shard_spec.leaf_splits is None:
        raise ValueError("the mesh-sharded server needs a leaf-aligned "
                         "ShardSpec (ShardSpec.for_space)")
    if shard_spec.total != space.total:
        raise ValueError("shard_spec does not cover the parameter arena")
    w = mesh_width(shard_spec)
    S = shard_spec.n_shards
    return keep_on_shards(MeshServerState(
        M=jnp.zeros((S, w), jnp.float32),
        v=jnp.zeros((n_workers, S, w), jnp.float32),
        t=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.int32),
        space=space,
        spec=shard_spec))


def keep_on_shards(state: MeshServerState) -> MeshServerState:
    """Place each shard's M row and v column on its own device of the
    ``shards`` mesh (``distributed.shard_mesh``); inside a jitted stage
    this pins the outputs so no stage gathers the arenas back onto one
    device.  A no-op where there is no such mesh (CPU, too few devices)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .distributed import shard_mesh

    mesh = shard_mesh(state.spec.n_shards)
    if mesh is None:
        return state
    return state._replace(
        M=jax.lax.with_sharding_constraint(
            state.M, NamedSharding(mesh, P("shards", None))),
        v=jax.lax.with_sharding_constraint(
            state.v, NamedSharding(mesh, P(None, "shards", None))))


def mesh_split(spec: ShardSpec, x, width: int | None = None) -> jax.Array:
    """Cut one global ``(total,)`` arena vector into the padded ``(S,
    width)`` stack (static slices; padding columns zero)."""
    width = mesh_width(spec) if width is None else width
    rows = [jnp.pad(x[a:b], (0, width - (b - a)))
            for a, b in zip(spec.bounds[:-1], spec.bounds[1:])]
    return jnp.stack(rows)


def mesh_concat(spec: ShardSpec, xs) -> jax.Array:
    """Undo :func:`mesh_split`: mask each row at its true shard bound and
    concatenate (shard order == leaf order) back to ``(total,)``."""
    parts = [xs[s, :sz] for s, sz in enumerate(spec.sizes) if sz]
    if not parts:
        return jnp.zeros((0,), xs.dtype)
    return jnp.concatenate(parts)


def mesh_arena(state: MeshServerState) -> jax.Array:
    """The global M arena of a mesh state (checkpoints / serving / eval)."""
    return mesh_concat(state.spec, state.M)
