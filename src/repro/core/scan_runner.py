"""Fully-jitted asynchronous PS simulation: one ``lax.scan`` over events.

The python event loop in async_sim.py is flexible (per-event python
callbacks); this runner trades that for speed — the entire schedule
compiles into ONE XLA program.  It is built from the SAME four stage
functions as ``AsyncTrainer`` and the cluster runtime
(``async_sim.client_step_fn`` / ``server_step_fn`` / ``ps.send_commit`` /
``ps.apply_update``), with the codec's jitted segment-wise quantizer
(``wire.quantize_message``) between the stages IN-GRAPH — so losses, final
params, and byte accounting reproduce the python loop bit-for-bit
(tests/test_scan_runner.py) while the flat-arena state makes each event a
single fused scatter per stage:

* worker models:   one ``(n_workers, total)`` arena (dynamic row update),
* worker strategy: arena vectors stacked on a leading worker axis,
* server M / v:    ``(total,)`` and ``(n_workers, total)`` arenas.

Byte accounting never leaves the host for sparse messages: frame sizes are
static per ``(mode, seg, total)`` (``wire.frame_bytes_static``), so the
totals are ``n_events * cost``.  Dense messages (ASGD upward, downward
without secondary compression) have data-dependent frames; the scan emits
their per-event nnz as a stacked output and the exact codec formula
(``wire.dense_frame_bytes``) is applied vectorized afterwards — identical
to what ``wire.frame_bytes`` measures event-by-event in the python loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import async_sim
from . import engine as engine_lib
from . import server as ps
from .baselines import Strategy
from .engine import CompressionSpec
from .paramspace import ParamSpace
from .sparsify import SparseLeaf


def run_async_scan(
    strategy: Strategy,
    grad_fn,
    params0,
    schedule,
    batches,
    *,
    n_workers: int,
    lr: float,
    secondary_density: float | None = None,
    secondary_spec: CompressionSpec = engine_lib.EXACT_SPEC,
    metrics: bool = False,
):
    """Run the whole schedule in one jitted scan.

    schedule: (n_events,) int32 worker ids.
    batches:  pytree stacked on a leading n_events axis.
    Returns (final global model, History) — the History carries the same
    losses/staleness/byte totals as ``AsyncTrainer.run``.

    ``metrics=True`` threads a ``telemetry.MetricsState`` through the scan
    carry as an optional extra leg (DESIGN.md §11): the fold reads only
    the optimization-barrier-staged stage outputs, so the data-plane op
    sequence — and therefore every loss/param/byte bit — is unchanged.
    With it off, the compiled program is literally the pre-telemetry one.
    """
    from repro.cluster import wire  # codec quantizer + byte accounting
    from repro.telemetry import metrics as metrics_lib

    space = ParamSpace.from_tree(params0)
    up_mode = strategy.quantize
    down_mode = secondary_spec.quantize
    up_seg = strategy.message_seg(space)
    down_seg = (space.ks(secondary_density)
                if secondary_density is not None else None)

    sstate0 = ps.init(params0, n_workers)
    theta0 = space.pack(params0)
    wp0 = jnp.broadcast_to(theta0[None], (n_workers, space.total))
    ws0 = jax.tree.map(
        lambda s: jnp.broadcast_to(s[None], (n_workers,) + s.shape),
        strategy.init(params0))

    client_step = async_sim.client_step_fn(strategy, grad_fn, space)
    server_step = async_sim.server_step_fn(secondary_density, secondary_spec)

    def dense_nnz(m):
        if isinstance(m, SparseLeaf):
            return jnp.zeros((), jnp.int32)
        return jnp.count_nonzero(m).astype(jnp.int32)

    def stage(x):
        """Materialization boundary mirroring the python loop's jit-stage
        edges: without it XLA fuses across stages and the scan can drift a
        ulp from the staged runners."""
        if isinstance(x, SparseLeaf):
            vals, idx = jax.lax.optimization_barrier((x.values, x.indices))
            return SparseLeaf(values=vals, indices=idx, size=x.size)
        if isinstance(x, ps.ServerState):
            M, v, t = jax.lax.optimization_barrier((x.M, x.v, x.t))
            return x._replace(M=M, v=v, t=t)
        return jax.lax.optimization_barrier(x)

    def materialize_dense(x):
        """Kernel boundary for a DENSE upward message.

        ``optimization_barrier`` is erased by XLA before fusion, so a bare
        ``lr * g`` message would fuse into the server's ``M - msg`` and
        LLVM would contract it to an FMA — one ulp off the staged runners
        (where the jit edge materializes the message).  A scatter-add into
        zeros is a real kernel XLA neither elides nor contracts across.
        """
        idx = jnp.arange(x.shape[0], dtype=jnp.int32)
        return jnp.zeros_like(x).at[idx].add(x)

    def event(carry, xs):
        if metrics:
            (sstate, wp, ws, ms), (k, stal, batch) = carry, xs
        else:
            (sstate, wp, ws), (k, batch) = carry, xs
        theta_k = stage(wp[k])
        strat_k = jax.tree.map(lambda x: stage(x[k]), ws)
        strat_k, loss, msg = client_step(theta_k, strat_k, stage(batch), lr)
        strat_k, loss = jax.tree.map(stage, strat_k), stage(loss)
        if not isinstance(msg, SparseLeaf):
            msg = materialize_dense(msg)
        msg = stage(wire.quantize_message(stage(msg), up_mode, seg=up_seg))
        sstate, G = server_step(sstate, msg, k)
        sstate, G = stage(sstate), stage(G)
        G = stage(wire.quantize_message(G, down_mode, seg=down_seg))
        sstate = ps.send_commit(sstate, k, G)
        theta_k = stage(ps.apply_update(theta_k, G))
        wp = wp.at[k].set(theta_k)
        ws = jax.tree.map(lambda x, v: x.at[k].set(v), ws, strat_k)
        if metrics:
            # fold the flight-recorder metrics from the ALREADY-staged
            # values — read-only taps, nothing flows back into the data
            # plane, so the staged op sequence (and its bits) is unchanged
            ms = metrics_lib.update(ms, k, stal,
                                    metrics_lib.msg_nnz(msg),
                                    metrics_lib.msg_nnz(G),
                                    metrics_lib.msg_sqnorm(G))
            return (sstate, wp, ws, ms), (loss, dense_nnz(msg),
                                          dense_nnz(G))
        return (sstate, wp, ws), (loss, dense_nnz(msg), dense_nnz(G))

    stal_np = async_sim.staleness_of(schedule, n_workers)

    # ``sstate0`` is built fresh above and returned updated, so its arenas
    # (M and the fleet-sized v buffer) alias the output in place.  wp0/ws0
    # are scan-carry-only (never returned), so donating them could not
    # alias anything — XLA double-buffers scan carries internally.
    if metrics:
        @partial(jax.jit, donate_argnums=(0,))
        def run(sstate0, wp0, ws0, schedule, batches, ms0, stal):
            (sstate, _, _, ms), out = jax.lax.scan(
                event, (sstate0, wp0, ws0, ms0),
                (jnp.asarray(schedule, jnp.int32),
                 jnp.asarray(stal, jnp.int32), batches))
            return sstate, out, ms

        sstate, (losses, up_nnz, down_nnz), ms = run(
            sstate0, wp0, ws0, schedule, batches,
            metrics_lib.init(n_workers), stal_np)
    else:
        @partial(jax.jit, donate_argnums=(0,))
        def run(sstate0, wp0, ws0, schedule, batches):
            (sstate, _, _), out = jax.lax.scan(
                event, (sstate0, wp0, ws0),
                (jnp.asarray(schedule, jnp.int32), batches))
            return sstate, out

        ms = None
        sstate, (losses, up_nnz, down_nnz) = run(
            sstate0, wp0, ws0, schedule, batches)

    n_events = len(schedule)
    env = wire.ENVELOPE_BYTES

    def total_bytes(seg, mode, nnz):
        if seg is not None:  # static sparse frames: no device data needed
            return n_events * wire.frame_bytes_static(seg, space.total, mode)
        per_event = env + wire.dense_frame_bytes(
            np.asarray(nnz, dtype=np.int64), space.total)
        return int(per_event.sum())

    hist = async_sim.History(
        losses=np.asarray(losses, np.float64),
        worker_ids=np.asarray(schedule),
        staleness=stal_np,
        up_bytes=total_bytes(up_seg, up_mode, up_nnz),
        down_bytes=total_bytes(down_seg, down_mode, down_nnz),
        evals=[],
        metrics=metrics_lib.drain(ms) if ms is not None else None,
    )
    return ps.global_model(params0, sstate), hist
