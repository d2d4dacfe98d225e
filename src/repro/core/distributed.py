"""DGS as a data-parallel gradient-exchange strategy on a TPU mesh.

This is the production-mesh face of the paper (DESIGN.md §3/§4): the
data-parallel axis is the worker fleet; the parameter server is *sharded
across that axis* (each device owns 1/W of the flattened parameter space).
Three exchange modes, all running inside ``jax.shard_map`` with manual
``("pod","data")`` axes and the ``"model"`` axis left to GSPMD:

* ``dense``     — baseline: ``psum`` (the classic all-reduce).  Comm per
                  device ~ 2 * P * bytes.
* ``allgather`` — paper-faithful port: each worker top-k's its SAMomentum
                  velocity and all-gathers (values, indices); every device
                  scatter-adds the union locally.  Comm ~ W * k * 8 bytes.
* ``shardedps`` — TPU-native dual-way form (beyond-paper, §Perf): entries are
                  bucketed by owner shard and exchanged with ``all_to_all``
                  (upward ~ k * overprovision), shard-owners aggregate into
                  their M shard and return the secondary-compressed
                  model-difference shard via all-gather (downward ~ W * k2).
                  With k2 = k/W this is ~3k per device vs allgather's 2Wk —
                  the PS bandwidth asymmetry reproduced on a flat fabric.
                  Dropped-overflow and the unsent remainder accumulate in the
                  persistent (M - v) difference exactly as paper Eq. (6).

All modes consume *per-worker* gradients (computed on the local batch shard)
and return the aggregated global update (mean over workers), plus new
persistent exchange state.

Every selection (upward SAMomentum top-k, per-row hinted top-k, downward
secondary compression) routes through core/engine.py — ``ExchangeConfig``
names the engine ("auto" dispatches exact vs sampled by tensor size via
``sampled_threshold_above``) and the wire quantization mode.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import engine as engine_lib
from .engine import CompressionSpec
from .paramspace import ShardSpec
from .sparsify import density_to_k


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    mode: str = "dense"            # dense | allgather | shardedps
    density: float = 0.01          # upward top-k density (1 - R%)
    momentum: float = 0.9          # SAMomentum m
    secondary_density: float | None = None  # shardedps downward density;
                                            # default density/W at call site
    bucket_factor: float = 2.0     # all_to_all bucket overprovisioning
    engine: str = "auto"           # compression engine (core/engine.py):
                                   # exact | sampled | blockwise | auto
    quantize: str = "none"         # wire quantization of message values
    sampled_threshold_above: int = 1 << 20  # auto engine: sampled thr for
                                            # leaves/rows at least this big
    wire_dtype: str = "float32"    # collective payload dtype (bf16 halves
                                   # value bytes; §Perf change)

    def spec(self) -> CompressionSpec:
        """The compression-engine spec every selection in this exchange
        uses."""
        return CompressionSpec(
            engine=self.engine,
            quantize=self.quantize,
            sampled_threshold_above=self.sampled_threshold_above,
        )


class ExchangeState(NamedTuple):
    """Persistent per-device exchange state (replicated over model axis)."""

    velocity: Any        # SAMomentum velocity pytree (per-worker, local)
    m_shard: Any         # sharded-PS: accumulated update, own shard only
    v_shard: Any         # sharded-PS: what has been broadcast already
    overflow: Any = ()   # sharded-PS: entries dropped at the W*cap bucket
                         # slot, () when the mode has no buckets — a
                         # read-only tap, never fed back into the data plane


def init_state(params, cfg: ExchangeConfig, n_workers: int) -> ExchangeState:
    vel = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if cfg.mode == "shardedps":
        def shard_zeros(p):
            shard = ShardSpec.even_stride(int(p.size), n_workers)
            return jnp.zeros((shard,), jnp.float32)
        m = jax.tree.map(shard_zeros, params)
        v = jax.tree.map(shard_zeros, params)
        ovf = jnp.zeros((), jnp.int32)
    else:
        m = v = jax.tree.map(lambda p: jnp.zeros((0,), jnp.float32), params)
        ovf = ()
    return ExchangeState(velocity=vel, m_shard=m, v_shard=v, overflow=ovf)


# ---------------------------------------------------------------------------
# dense (psum) baseline
# ---------------------------------------------------------------------------

def dense_exchange(grads, axis_names):
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis_names), grads)


def dense_momentum_exchange(state, grads, *, cfg, lr, axis_names):
    """Classic DP baseline: all-reduce mean grads, heavy-ball momentum."""
    g_mean = dense_exchange(grads, axis_names)
    new_u = jax.tree.map(
        lambda u, g: engine_lib.velocity_accumulate(
            u, g.astype(jnp.float32), momentum=cfg.momentum, lr=lr),
        state.velocity, g_mean)
    return new_u, state._replace(velocity=new_u)


# ---------------------------------------------------------------------------
# model-shard-aware leaf exchange (mesh path)
#
# When a parameter dim is sharded over the (GSPMD-auto) "model" axis, a flat
# per-tensor top-k would force XLA to gather the whole gradient across model
# shards.  Instead we top-k along the UNSHARDED dims, per slice of the
# sharded dim: every step of the selection and the scatter-back is then local
# to the model shard, and only the k-sized (values, indices) tuples move
# across the data axes.  Per-slice thresholds are a structured variant of the
# paper's per-tensor threshold (DESIGN.md §3/§4).
# ---------------------------------------------------------------------------

def _leaf_allgather_hinted(u, g, *, k, shard_axis, momentum, lr, axis_names,
                           n_workers, spec, wire_dtype="float32"):
    """SAMomentum + top-k + sparse all-gather for one leaf.

    Returns (update_to_subtract, new_velocity)."""
    if (shard_axis is None or u.ndim == 1) and u.size < (1 << 24):
        msg, u2 = engine_lib.samomentum_step(
            u, g.astype(jnp.float32), momentum=momentum, lr=lr, k=k,
            spec=spec)
        gvals = jax.lax.all_gather(msg.values, axis_names)   # (W, k)
        gidx = jax.lax.all_gather(msg.indices, axis_names)
        size = int(u.size)
        dense = (jnp.zeros((size,), jnp.float32)
                 .at[gidx.reshape(-1)].add(gvals.reshape(-1)))
        return (dense / n_workers).reshape(u.shape), u2
    # 2D row view: shard_axis first (so selection is local per model shard),
    # then fold further leading dims until each row is small enough for a
    # cheap (and int32-safe) per-row top_k.
    ax = shard_axis if shard_axis is not None else 0
    um = jnp.moveaxis(u, ax, 0)
    gm = jnp.moveaxis(g, ax, 0)
    rows, rest = um.shape[0], int(um.size) // um.shape[0]
    dims = list(um.shape[1:])
    while dims and rest > (1 << 22) and len(dims) > 1:
        rows *= dims.pop(0)
        rest = 1
        for d in dims:
            rest *= d
    S = rows
    u2d = um.reshape(S, -1)
    g2d = gm.reshape(S, -1).astype(jnp.float32)
    rest = u2d.shape[1]
    k_row = max(1, min(rest, -(-k // S)))
    vals, idx, u_new = engine_lib.samomentum_step_rows(
        u2d, g2d, momentum=momentum, lr=lr, k=k_row, spec=spec)
    rows_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    wdt = jnp.dtype(wire_dtype)
    gvals = jax.lax.all_gather(vals.astype(wdt), axis_names)  # (W, S, k_row)
    gidx = jax.lax.all_gather(idx, axis_names)
    gv = jnp.moveaxis(gvals, 0, 1).reshape(S, -1).astype(jnp.float32)
    gi = jnp.moveaxis(gidx, 0, 1).reshape(S, -1)
    dense = jnp.zeros((S, rest), jnp.float32).at[rows_idx, gi].add(gv)
    upd = jnp.moveaxis((dense / n_workers).reshape(um.shape), 0, ax)
    u_new = jnp.moveaxis(u_new.reshape(um.shape), 0, ax)
    return upd, u_new


# ---------------------------------------------------------------------------
# allgather sparse exchange (paper-faithful port)
# ---------------------------------------------------------------------------

def allgather_exchange(state, grads, *, cfg, lr, axis_names, n_workers,
                       shard_axes=None):
    """Per-leaf: SAMomentum -> top-k -> all_gather sparse -> local scatter.

    Returns (updates, new_state): ``updates`` is the mean lr-scaled update to
    subtract from the (replicated-over-data) parameters.  ``shard_axes`` is
    an optional per-leaf list of model-sharded dim indices (see above).
    """
    spec = cfg.spec()
    u_leaves, treedef = jax.tree.flatten(state.velocity)
    g_leaves = jax.tree.leaves(grads)
    if shard_axes is None:
        shard_axes = [None] * len(u_leaves)
    upd, new_u = [], []
    for u, g, ax in zip(u_leaves, g_leaves, shard_axes):
        k = density_to_k(int(u.size), cfg.density)
        up, u2 = _leaf_allgather_hinted(
            u, g, k=k, shard_axis=ax, momentum=cfg.momentum, lr=lr,
            axis_names=axis_names, n_workers=n_workers, spec=spec,
            wire_dtype=cfg.wire_dtype)
        upd.append(up)
        new_u.append(u2)
    updates = jax.tree.unflatten(treedef, upd)
    return updates, state._replace(velocity=jax.tree.unflatten(treedef, new_u))


def _leaf_shardedps_hinted(u, g, m_sh, v_sh, *, k, shard_axis, cfg, lr,
                           axis_names, n_workers, spec):
    """Row-wise sharded-PS dual-way exchange for one (model-sharded) leaf.

    View: (S, rest) rows with S on the (GSPMD-auto) model axis.  The data
    axis doubles as a sharded parameter server: data-worker w owns columns
    [w*shard_rest, (w+1)*shard_rest) of every row.

    Upward:  per-row top-k entries are bucketed by owner and exchanged with
             ONE all_to_all (~k entries per device instead of W*k).
    Server:  each owner scatter-adds into its M shard and tracks v (what it
             has broadcast); the difference M - v accumulates every unsent
             remainder and bucket-overflow EXACTLY as paper Eq. (6).
    Down:    top-k2 of the difference shard, all-gathered (~W*k2 = k per
             device with the default k2 = k/W).

    Returns (update, u_new, m_new, v_new, overflow): ``overflow`` is the
    scalar int32 count of selected entries dropped at the ``W*cap`` slot
    this step (their mass stays in the velocity — exactness is never lost,
    but the count is the telemetry satellite's visibility into how tight
    ``bucket_factor`` is)."""
    W = n_workers
    S, rest, ax = rows_view(u.shape, shard_axis)
    if ax is None:
        um = u.reshape(1, -1)
        gm = g.reshape(1, -1)
        ax = 0  # round-trip via reshape below is shape-safe
        um_shape = um.shape
    else:
        um = jnp.moveaxis(u, ax, 0)
        gm = jnp.moveaxis(g, ax, 0)
        um_shape = um.shape
    u2d = um.reshape(S, rest)
    g2d = gm.reshape(S, rest).astype(jnp.float32)
    # the mesh PS and the cluster PS share ONE partition rule: this stride
    # is ShardSpec.even(rest, W)'s shard width, and `idx // shard_rest`
    # below is exactly ShardSpec.owner_of for that even spec — so the
    # in-graph sharded exchange and coordinator sharding agree on which
    # worker owns any flat index
    shard_rest = ShardSpec.even_stride(rest, W)
    k_row = max(1, min(rest, -(-k // S)))
    uacc = engine_lib.velocity_accumulate(u2d, g2d, momentum=cfg.momentum,
                                          lr=lr)
    vals, idx = engine_lib.select_rows(uacc, k_row, spec)    # (S, k_row)
    rows_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    # ---- bucket by owner, per row ----
    owner = idx // shard_rest                                 # (S, k_row)
    cap = max(1, int(round(k_row / W * cfg.bucket_factor)))
    order = jnp.argsort(owner, axis=1)
    owner_s = jnp.take_along_axis(owner, order, axis=1)
    idx_s = jnp.take_along_axis(idx, order, axis=1)
    vals_s = jnp.take_along_axis(vals, order, axis=1)
    first = jax.vmap(
        lambda o: jnp.searchsorted(o, o, side="left"))(owner_s)
    pos = jnp.arange(k_row, dtype=jnp.int32)[None] - first.astype(jnp.int32)
    ok = pos < cap
    slot = jnp.where(ok, owner_s * cap + pos, W * cap)        # (S, k_row)
    buf_v = jnp.zeros((S, W * cap + 1), jnp.float32).at[
        rows_idx, slot].set(jnp.where(ok, vals_s, 0.0))[:, :-1]
    buf_i = jnp.full((S, W * cap + 1), -1, jnp.int32).at[
        rows_idx, slot].set(jnp.where(ok, idx_s % shard_rest, -1))[:, :-1]
    # SAMomentum rescale: only actually-shipped coords keep u (bucket
    # overflow is NOT shipped — its mass must stay in the velocity)
    shipped = jnp.zeros((S, rest + 1), bool).at[
        rows_idx, jnp.where(ok, idx_s, rest)].set(True)[:, :-1]
    u_new = engine_lib.samomentum_rescale(uacc, shipped, cfg.momentum)
    # ---- all_to_all: (S, W, cap) -> (W, S, cap) ----
    wdt = jnp.dtype(cfg.wire_dtype)
    send_v = jnp.moveaxis(buf_v.reshape(S, W, cap), 1, 0)
    send_i = jnp.moveaxis(buf_i.reshape(S, W, cap), 1, 0)
    recv_v = _all_to_all(send_v.astype(wdt), axis_names).astype(
        jnp.float32)                                          # (W, S, cap)
    recv_i = _all_to_all(send_i, axis_names)
    # ---- server shard update: M -= sum of received ----
    ri = jnp.where(recv_i >= 0, recv_i, shard_rest)           # (W, S, cap)
    ri2 = jnp.moveaxis(ri, 0, 1).reshape(S, W * cap)
    rv2 = jnp.moveaxis(recv_v, 0, 1).reshape(S, W * cap)
    m_flat = jnp.concatenate(
        [m_sh.reshape(S, shard_rest), jnp.zeros((S, 1), jnp.float32)],
        axis=1)
    m_flat = m_flat.at[rows_idx, ri2].add(-rv2)
    m_new = m_flat[:, :shard_rest]
    # ---- downward: secondary-compressed difference shard ----
    v2d = v_sh.reshape(S, shard_rest)
    diff = m_new - v2d
    k2 = max(1, min(shard_rest,
                    int(round(k_row / W)) if cfg.secondary_density is None
                    else density_to_k(shard_rest, cfg.secondary_density)))
    dvals, didx = engine_lib.select_rows(diff, k2, spec)      # (S, k2)
    v_new = v2d.at[rows_idx, didx].add(dvals)
    me = _linear_index(
        (axis_names,) if isinstance(axis_names, str) else tuple(axis_names))
    gidx = jax.lax.all_gather(didx + me * shard_rest, axis_names)  # (W,S,k2)
    gvals = jax.lax.all_gather(dvals.astype(wdt), axis_names).astype(
        jnp.float32)
    gi = jnp.moveaxis(gidx, 0, 1).reshape(S, -1)
    gv = jnp.moveaxis(gvals, 0, 1).reshape(S, -1)
    dense = jnp.zeros((S, W * shard_rest), jnp.float32).at[
        rows_idx, gi].add(gv)[:, :rest]
    if shard_axis is None:
        upd = (-dense / W).reshape(u.shape)
        u_new = u_new.reshape(u.shape)
    else:
        upd = jnp.moveaxis((-dense / W).reshape(um_shape), 0, ax)
        u_new = jnp.moveaxis(u_new.reshape(um_shape), 0, ax)
    ovf = jnp.sum(~ok).astype(jnp.int32)
    return upd, u_new, m_new.reshape(-1), v_new.reshape(-1), ovf


def rows_view(shape, shard_axis):
    """(S, rest, ax) row view used by the hinted exchanges and their state
    shapes.  shard_axis None -> single row (per-tensor selection)."""
    size = 1
    for d in shape:
        size *= int(d)
    if shard_axis is None or len(shape) <= 1:
        return 1, size, None
    dims = [int(d) for d in shape]
    lead = dims.pop(shard_axis)
    rows, rest = lead, size // lead
    while dims and rest > (1 << 22) and len(dims) > 1:
        rows *= dims.pop(0)
        rest = 1
        for d in dims:
            rest *= d
    return rows, rest, shard_axis


def shardedps_state_size(shape, shard_axis, n_workers: int) -> int:
    """Per-device M/v shard length for one leaf (row-major layout)."""
    S, rest, _ = rows_view(shape, shard_axis)
    return S * ShardSpec.even_stride(rest, n_workers)


# ---------------------------------------------------------------------------
# sharded-PS all_to_all exchange (TPU-native dual-way DGS)
# ---------------------------------------------------------------------------

def shardedps_exchange(
    state, grads, *, cfg, lr, axis_names, n_workers, shard_axes=None
):
    """Dual-way sparse exchange against a parameter server sharded over the
    data axis — per-leaf dispatch to the row-wise implementation above."""
    spec = cfg.spec()
    u_leaves, treedef = jax.tree.flatten(state.velocity)
    m_leaves = jax.tree.leaves(state.m_shard)
    v_leaves = jax.tree.leaves(state.v_shard)
    g_leaves = jax.tree.leaves(grads)
    if shard_axes is None:
        shard_axes = [None] * len(u_leaves)
    upd, new_u, new_m, new_v = [], [], [], []
    step_ovf = jnp.zeros((), jnp.int32)
    for u, m_sh, v_sh, g, ax in zip(u_leaves, m_leaves, v_leaves, g_leaves,
                                    shard_axes):
        k = density_to_k(int(u.size), cfg.density)
        up, u2, m2, v2, ovf = _leaf_shardedps_hinted(
            u, g, m_sh, v_sh, k=k, shard_axis=ax, cfg=cfg, lr=lr,
            axis_names=axis_names, n_workers=n_workers, spec=spec)
        upd.append(up)
        new_u.append(u2)
        new_m.append(m2)
        new_v.append(v2)
        step_ovf = step_ovf + ovf
    updates = jax.tree.unflatten(treedef, upd)
    # states built by older callers carry the defaulted () — start at zero
    prev = state.overflow
    base = prev if jax.tree_util.tree_leaves(prev) else jnp.zeros(
        (), jnp.int32)
    return updates, ExchangeState(
        velocity=jax.tree.unflatten(treedef, new_u),
        m_shard=jax.tree.unflatten(treedef, new_m),
        v_shard=jax.tree.unflatten(treedef, new_v),
        overflow=base + step_ovf,
    )


def _all_to_all(x, axis_names):
    """all_to_all over possibly-multiple manual axes: (W, c) -> (W, c) where
    row i of the result is the row this device received from device i."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if len(axis_names) == 1:
        return jax.lax.all_to_all(
            x, axis_names[0], split_axis=0, concat_axis=0, tiled=True
        )
    # fold multiple manual axes: gather then slice own column — functionally
    # identical, XLA rewrites to all-to-all when profitable; used only for
    # the (pod, data) multi-pod case.
    W = x.shape[0]
    g = jax.lax.all_gather(x, axis_names)      # (W, W, c)
    me = _linear_index(axis_names)
    return g[:, me, :] if g.ndim == 3 else jnp.take(g, me, axis=1)


def _linear_index(axis_names):
    idx = jnp.zeros((), jnp.int32)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


# ---------------------------------------------------------------------------
# mesh-shard alltoallv: the in-graph exchange behind the cluster's
# `mesh_shards` server stage (DESIGN.md §14)
# ---------------------------------------------------------------------------

def shard_mesh(n_shards: int):
    """The ``shards`` mesh the mesh-shard server spreads over: the first
    ``n_shards`` devices, on an accelerator with at least that many, else
    ``None`` — forced-host CPU "devices" share the same cores, so the
    multi-device program would replicate the surrounding stage work S
    times for zero parallel gain (the single-device layout is
    bit-identical)."""
    import numpy as np
    from jax.sharding import Mesh

    if (n_shards < 2 or len(jax.devices()) < n_shards
            or jax.default_backend() == "cpu"):
        return None
    return Mesh(np.asarray(jax.devices()[:n_shards]), ("shards",))


def shard_exchange_batch(spec: ShardSpec, indices, values, *,
                         cap: int | None = None,
                         use_mesh: bool | None = None):
    """Route a batch of global-index sparse messages to shard-local slots.

    ``indices``/``values``: ``(B, k)`` with int32 global arena indices
    (``-1`` = padding).  Each message is cut into ``S`` even source chunks
    of ``kp = ShardSpec.even_stride(k, S)`` — one per mesh device — each
    chunk is bucketed by ``kernels.ops.route_by_shard_batch`` (the same
    ``owner_of`` partition rule the coordinator sharding uses), and the
    per-(source, destination) buckets are swapped with one alltoallv-style
    ``_all_to_all`` over a ``shards`` mesh axis.  With fewer than S local
    devices the collective degenerates to the bit-identical pure
    permutation ``swapaxes(src, dst)`` — all_to_all IS that permutation,
    so the two paths agree bit-for-bit (pinned in tests/test_shardspec.py).

    Capacity rule: ``cap`` bounds entries per (source chunk, destination
    shard) pair and defaults to ``kp`` — a chunk only holds ``kp`` entries,
    so the default can NEVER overflow; callers passing a tighter ``cap``
    trade slots for a nonzero ``overflow`` count.

    ``use_mesh`` picks the path explicitly (tests pin their bit-equality
    with it); the ``None`` default takes the collective exactly when
    :func:`shard_mesh` finds a mesh.

    Returns ``(local_idx, vals, overflow)``: ``(B, S, S*cap)`` shard-local
    indices (``-1`` = empty slot) / values, and the scalar int32 count of
    entries dropped by ``cap``.
    """
    from repro.kernels import ops

    S = spec.n_shards
    B, k = indices.shape
    kp = ShardSpec.even_stride(k, S)
    cap = int(cap) if cap is not None else kp
    pad = S * kp - k
    idx3 = jnp.pad(indices.astype(jnp.int32), ((0, 0), (0, pad)),
                   constant_values=-1).reshape(B, S, kp)
    val3 = jnp.pad(values, ((0, 0), (0, pad))).reshape(B, S, kp)
    bounds = jnp.asarray(spec.bounds, jnp.int32)

    if use_mesh is None:
        use_mesh = shard_mesh(S) is not None
    if use_mesh and S > 1 and len(jax.devices()) >= S:
        # device-mesh leg: each device routes ITS source chunk and the
        # buckets cross the fabric with the native collective
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:S]), ("shards",))

        def stage(idx_c, val_c):
            # (B, 1, kp): this device's source chunk of every message
            ri_c, rv_c, ovf = ops.route_by_shard_batch(
                idx_c[:, 0], val_c[:, 0], bounds=bounds, n_shards=S,
                cap=cap)                             # (B, S_dst, cap)
            send_i = jnp.moveaxis(ri_c, 1, 0).reshape(S, B * cap)
            send_v = jnp.moveaxis(rv_c, 1, 0).reshape(S, B * cap)
            recv_i = _all_to_all(send_i, "shards")   # (S_src, B * cap)
            recv_v = _all_to_all(send_v, "shards")
            ri = jnp.moveaxis(recv_i.reshape(S, B, cap), 1, 0)
            rv = jnp.moveaxis(recv_v.reshape(S, B, cap), 1, 0)
            return (ri.reshape(B, 1, S * cap), rv.reshape(B, 1, S * cap),
                    ovf[None])

        ri, rv, ovf = jax.shard_map(
            stage, mesh=mesh, axis_names={"shards"},
            in_specs=(P(None, "shards"), P(None, "shards")),
            out_specs=(P(None, "shards"), P(None, "shards"), P("shards")),
            check_vma=False)(idx3, val3)
        return ri, rv, jnp.sum(ovf).astype(jnp.int32)

    # single-device fallback: route every chunk, then apply the identical
    # (src, dst) permutation all_to_all performs
    ri, rv, ovf = ops.route_by_shard_batch(
        idx3.reshape(B * S, kp), val3.reshape(B * S, kp), bounds=bounds,
        n_shards=S, cap=cap)
    ri = jnp.swapaxes(ri.reshape(B, S, S, cap), 1, 2)
    rv = jnp.swapaxes(rv.reshape(B, S, S, cap), 1, 2)
    return (ri.reshape(B, S, S * cap), rv.reshape(B, S, S * cap),
            ovf.astype(jnp.int32))


# ---------------------------------------------------------------------------
# unified entry point
# ---------------------------------------------------------------------------

def exchange(state, grads, *, cfg: ExchangeConfig, lr, axis_names, n_workers,
             shard_axes=None):
    if cfg.mode == "dense":
        return dense_momentum_exchange(
            state, grads, cfg=cfg, lr=lr, axis_names=axis_names)
    if cfg.mode == "allgather":
        return allgather_exchange(
            state, grads, cfg=cfg, lr=lr, axis_names=axis_names,
            n_workers=n_workers, shard_axes=shard_axes,
        )
    if cfg.mode == "shardedps":
        return shardedps_exchange(
            state, grads, cfg=cfg, lr=lr, axis_names=axis_names,
            n_workers=n_workers, shard_axes=shard_axes,
        )
    raise ValueError(f"unknown exchange mode {cfg.mode!r}")
