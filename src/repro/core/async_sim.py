"""Deterministic event-driven simulator for asynchronous PS training.

The paper's cluster is 32 GPU workers hitting one TCP parameter server at
their own pace.  On a single host we reproduce the *algorithmic* behaviour
exactly and deterministically:

* every worker owns a local model copy + strategy state (velocity/residual)
  — both packed into the flat parameter arena (core/paramspace.py),
* a schedule (sequence of worker ids, derived from simulated heterogeneous
  worker speeds) fixes the global order in which workers reach the server,
* each event executes: local backward on the worker's *stale* model ->
  strategy.step (sparsify) -> server.receive -> server.send (model diff,
  optionally secondary-compressed) -> worker applies G.

Staleness therefore emerges naturally: a slow worker computes gradients on a
model that is many server-updates old — exactly the regime the paper's
SAMomentum is designed to survive.

Each event runs as four jitted stages — client compute, server
receive+select, server commit, worker apply — the SAME stage functions the
federated cluster runtime (repro.cluster) executes on either side of its
wire and the scan runner (core/scan_runner.py) compiles into its fused
event body, with the codec's quantizer between them.  That shared
decomposition is what makes the simulator's losses bit-for-bit reproducible
on the real transport AND in the scan; byte accounting is the codec's
measured frame sizes (wire.frame_bytes) — static per event for sparse
messages, so it is computed ONCE per run (no per-event host sync).

Two event loops share those stages (DESIGN.md §9):

* ``AsyncTrainer.run``          — serial reference: one event at a time.
* ``AsyncTrainer.run_batched``  — ``batch_schedule`` groups maximal runs of
  PAIRWISE-DISTINCT workers into one dispatch per stage: the client stage
  vmaps over the batch (independent stale models), the server receives run
  as a ``lax.scan`` inside one jit (each event's select must see the M its
  predecessors left — prefix-dependent, so sequential-in-graph), and the
  commits fuse into ONE multi-row scatter (disjoint ``v`` rows commute
  bitwise).  Bit-for-bit equal to the serial loop — losses, params, AND
  byte accounting (tests/test_async_sim.py).

All batched stages and the serial server/commit/apply stages donate their
state arguments (``M``/``v``/theta/velocity arenas update in place — no
per-event buffer churn).  The one exception is the serial client step: see
``make_client_step`` for why its state stays un-donated.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry.trace import LAYER

from . import engine as engine_lib
from . import server as ps
from .baselines import Strategy, msgd_step
from .engine import CompressionSpec
from .paramspace import ParamSpace
from .sparsify import SparseLeaf


def make_schedule(
    n_workers: int,
    n_events: int,
    *,
    seed: int = 0,
    hetero: float = 0.5,
) -> np.ndarray:
    """Event order from simulated worker speeds.

    Worker service times are exponential with per-worker rates drawn
    lognormal(0, hetero); hetero=0 degenerates to round-robin-ish fair
    interleaving, larger hetero produces stragglers and thus higher staleness.

    A heap-ordered event queue makes this O(n_events * log n_workers) — a
    million-event schedule for the scalability sweeps generates in seconds
    where the old per-event ``np.argmin`` scan was O(n_events * n_workers).
    The draw sequence is identical to the argmin loop (one exponential per
    event for the completing worker; ties resolve to the lowest worker id),
    so schedules are bit-for-bit what they always were.
    """
    rng = np.random.default_rng(seed)
    speeds = np.exp(rng.normal(0.0, hetero, n_workers))
    scale = 1.0 / speeds
    # next completion time per worker
    t_next = rng.exponential(scale)
    heap = [(float(t_next[k]), k) for k in range(n_workers)]
    heapq.heapify(heap)
    order = np.empty(n_events, dtype=np.int32)
    for e in range(n_events):
        t, k = heapq.heappop(heap)
        order[e] = k
        heapq.heappush(heap, (t + rng.exponential(scale[k]), k))
    return order


class History(NamedTuple):
    losses: np.ndarray          # (n_events,)
    worker_ids: np.ndarray      # (n_events,)
    staleness: np.ndarray       # (n_events,) server updates since last sync
    up_bytes: int               # total upward wire bytes
    down_bytes: int             # total downward wire bytes
    evals: list                 # [(event_idx, metric), ...]
    # drained flight-recorder metrics (repro.telemetry) when the run was
    # told to collect them; None otherwise — the data plane is identical
    # either way (test_metrics_do_not_change_bits)
    metrics: dict | None = None


def _jsonable(x):
    """Best-effort scalarization of an eval metric for the JSONL log."""
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


def _record_run_summary(rec, runner: str, hist: History,
                        up_cost, down_cost, per_up, per_down) -> None:
    """Emit the end-of-run JSONL summary the report renderer consumes:
    staleness + per-event wire-byte histograms (host data only — the run
    is over, so this syncs nothing)."""
    if not rec.enabled:
        return
    from repro.telemetry import metrics as metrics_lib

    n = len(hist.losses)
    if per_up is None:
        per_up = np.full(n, up_cost if up_cost is not None else 0)
    if per_down is None:
        per_down = np.full(n, down_cost if down_cost is not None else 0)
    rec.event(
        "run_summary", runner=runner, n_events=n,
        up_bytes=int(hist.up_bytes), down_bytes=int(hist.down_bytes),
        loss_first=float(hist.losses[0]) if n else None,
        loss_last=float(hist.losses[-1]) if n else None,
        staleness_hist=metrics_lib.summarize_log2(hist.staleness),
        up_bytes_hist=metrics_lib.summarize_log2(per_up),
        down_bytes_hist=metrics_lib.summarize_log2(per_down),
        metrics=hist.metrics,
    )


def staleness_of(schedule, n_workers: int) -> np.ndarray:
    """Per-event staleness (server updates since the worker last synced) —
    a pure function of the schedule, shared by every runner."""
    last_sync = np.zeros(n_workers, dtype=np.int64)
    out = np.zeros(len(schedule), dtype=np.int64)
    for e, k in enumerate(schedule):
        out[e] = e - last_sync[k]
        last_sync[k] = e + 1
    return out


def batch_schedule(
    schedule,
    *,
    max_batch: int | None = None,
    cut_every: int | None = None,
) -> list[np.ndarray]:
    """Group a schedule into batches of independent events (the batched
    scheduler view).

    A batch is a maximal run of CONSECUTIVE events with pairwise-distinct
    workers, truncated to a power-of-two length.  Distinctness is the
    commutation rule (DESIGN.md §9): within such a run every event reads a
    different worker model and commits to a different ``v`` row, so the
    client computes vmap and the commits fuse into one multi-row scatter
    while remaining bit-equal to serial execution.  The power-of-two
    truncation bounds jit specialization to O(log n_workers) batch sizes.

    ``cut_every`` forces batch boundaries at multiples of that many events
    (evaluation points); ``max_batch`` caps the batch size.  Invariant:
    ``np.concatenate(batch_schedule(s)) == s`` — batching never reorders.
    """
    sched = np.asarray(schedule)
    n = len(sched)
    batches = []
    i = 0
    while i < n:
        limit = n
        if cut_every:
            limit = min(limit, (i // cut_every + 1) * cut_every)
        if max_batch is not None:
            limit = min(limit, i + max_batch)
        seen = set()
        j = i
        while j < limit and sched[j] not in seen:
            seen.add(sched[j])
            j += 1
        size = 1 << ((j - i).bit_length() - 1)   # pow2 truncation
        batches.append(sched[i:i + size])
        i += size
    return batches


# ---------------------------------------------------------------------------
# The four per-event stages, decomposed exactly as the cluster runtime runs
# them (client compute | server receive+select | server commit | client
# apply).  AsyncTrainer and repro.cluster jit THESE SAME functions — and
# core/scan_runner.py inlines the raw ``*_fn`` forms into its scan body —
# so XLA compiles one identical op sequence for each stage and every runner
# is bit-for-bit reproducible on every other (tests/test_cluster.py,
# tests/test_scan_runner.py).  Wire quantization happens BETWEEN stages via
# wire.quantize_message — the codec's jitted segment-wise quantizer — never
# inside the strategy jit.
# ---------------------------------------------------------------------------

def strip_quantize(strategy: Strategy) -> Strategy:
    """The strategy with in-engine wire quantization disabled — message
    values leave the compute stage raw; the wire (or its in-process stand-in
    ``wire.quantize_message``) owns value quantization."""
    if strategy.quantize == "none":
        return strategy
    return dataclasses.replace(strategy, quantize="none")


def client_step_fn(strategy: Strategy, grad_fn, space: ParamSpace):
    """client compute: grads on the stale local model + strategy step.

    The worker model lives as a ``(total,)`` arena ``theta``; it is
    unpacked to the parameter pytree only for ``grad_fn``.  Returns
    (new strategy state, loss, RAW upward arena message).
    """
    strategy = strip_quantize(strategy)

    def client_step(theta, wstrat, batch, lr):
        with jax.named_scope(LAYER.grad):
            loss, grads = grad_fn(space.unpack(theta), batch)
        with jax.named_scope(LAYER.strategy):
            wstrat, msg = strategy.step(wstrat, grads, lr)
        return wstrat, loss, msg

    return client_step


def make_client_step(strategy: Strategy, grad_fn, space: ParamSpace):
    """jit(client compute) over the arena model.

    The strategy state is deliberately NOT donated here: donating it lets
    XLA fuse the momentum update in place, and on CPU that compiles to a
    program whose DGC velocity arithmetic differs by 1 ulp from the
    non-donated (and vmapped batched) compilation — which breaks the
    bit-for-bit serial/batched contract this loop is the reference for.
    The serial loop is the baseline, not the fast path; the batched loop
    donates everything (verified bit-equal against this reference).
    """
    return jax.jit(client_step_fn(strategy, grad_fn, space))


def server_step_fn(secondary_density, spec: CompressionSpec):
    """server: apply the upward message, select the RAW downward one."""

    def server_step(sstate, msg, worker_id):
        sstate = ps.receive(sstate, msg)
        G = ps.send_select(sstate, worker_id,
                           secondary_density=secondary_density, spec=spec)
        return sstate, G

    return server_step


def make_server_step(secondary_density, spec: CompressionSpec):
    """jit(server): one fused scatter in, one subtract + per-tensor select
    out (the arena descriptor rides statically inside ServerState).
    ``sstate`` is donated — the M arena updates in place."""
    return jax.jit(server_step_fn(secondary_density, spec),
                   donate_argnums=(0,))


def make_commit():
    """jit(server commit): fold the SHIPPED downward message into v_k.
    ``sstate`` is donated — the v buffer updates in place."""
    return jax.jit(ps.send_commit, donate_argnums=(0,))


def make_apply():
    """jit(worker apply): theta <- theta + G (Eq. 5) — one arena scatter.
    ``theta`` is donated — the worker model updates in place."""
    return jax.jit(ps.apply_update, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Batched stage factories (run_batched / cluster batched drain).  Worker
# models and strategy states live STACKED — wp: (n_workers, total), ws: the
# strategy pytree with a leading (n_workers,) axis — and every stage takes
# the batch's worker ids, gathering/scattering rows in-graph.  Each factory
# mirrors its serial twin's jit boundary, so XLA materializes the same
# stage edges and the arithmetic stays bit-equal (DESIGN.md §9).
# ---------------------------------------------------------------------------

def make_batched_client_step(strategy: Strategy, grad_fn, space: ParamSpace):
    """jit(vmap(client compute)) across the ready-worker batch.

    Takes the stacked worker arenas, gathers the batch rows, vmaps the
    SAME ``client_step_fn`` body across them (independent stale models —
    the batching rule guarantees distinct workers), and writes the updated
    strategy rows back.  Also emits the per-event nnz of dense upward
    messages (byte accounting without a per-event host sync); donates the
    stacked strategy state.
    """
    vstep = jax.vmap(client_step_fn(strategy, grad_fn, space))
    dense_msg = not strategy.sparse

    def run(wp, ws, ids, batches, lrs):
        with jax.named_scope(LAYER.strategy):
            st = jax.tree.map(lambda s: s[ids], ws)
        with jax.named_scope(LAYER.grad):
            theta = wp[ids]
        st2, losses, msgs = vstep(theta, st, batches, lrs)
        with jax.named_scope(LAYER.strategy):
            ws = jax.tree.map(lambda s, r: s.at[ids].set(r), ws, st2)
        nnz = (jnp.sum(msgs != 0.0, axis=-1) if dense_msg
               else jnp.zeros(ids.shape, jnp.int32))
        return ws, losses, msgs, nnz

    return jax.jit(run, donate_argnums=(1,))


def make_batched_quantize(mode: str, seg):
    """jit(vmap(wire.quantize_message)) over a stacked sparse message, or
    None when quantization is a no-op (mode "none", or dense messages —
    they travel f32)."""
    from repro.cluster import wire
    if mode == "none" or seg is None:
        return None
    seg = tuple(int(s) for s in seg)
    return jax.jit(jax.vmap(
        lambda m: wire.quantize_message(m, mode, seg=seg)))


def batched_server_step_fn(secondary_density, spec: CompressionSpec):
    """server over a whole batch: receive each message, select each RAW
    downward message against the M its predecessors left.

    The receives into M are PREFIX-dependent — event i's select must see
    exactly the post-receive M of events 0..i — so they run as a
    ``lax.scan`` carrying ``(M, t)`` inside ONE jit: sequential in the
    graph, one dispatch on the host.  The ``v`` rows read are untouched
    within the batch (pairwise-distinct workers), so they gather up front.

    Returns ``(sstate, G, M_rows)``: G the stacked raw downward batch;
    ``M_rows`` the per-event prefix M stack when the downward message is
    dense (``secondary_density is None`` — the commit's ``v_k <- M`` snap
    must use M *as of that event*, see ``server.send_commit_rows``), else
    ``None``.  Everything but the secondary selection runs under the
    receive layer's scope.
    """
    dense_down = secondary_density is None
    spec_raw = dataclasses.replace(spec, quantize="none")

    def server_batch(sstate, msgs, ids):
        def body(carry, x):
            M, t = carry
            msg, v_k = x
            st = ps.receive(sstate._replace(M=M, t=t), msg)
            diff = st.M - v_k
            if dense_down:
                out = (diff, st.M)
            else:
                out = (st.space.select(
                    diff, st.space.ks(secondary_density), spec_raw),)
            return (st.M, st.t), out

        with jax.named_scope(LAYER.receive):
            v_rows = sstate.v[ids]
            (M, t), outs = jax.lax.scan(body, (sstate.M, sstate.t),
                                        (msgs, v_rows))
        sstate = sstate._replace(M=M, t=t)
        if dense_down:
            return sstate, outs[0], outs[1]
        return sstate, outs[0], None

    return server_batch


def make_batched_server_step(secondary_density, spec: CompressionSpec):
    """jit(batched server); donates ``sstate``."""
    return jax.jit(batched_server_step_fn(secondary_density, spec),
                   donate_argnums=(0,))


def make_batched_commit(dense_down: bool):
    """jit(batched commit): fold a whole SHIPPED batch into its ``v`` rows
    with ONE fused multi-row scatter (``server.send_commit_rows``).

    The dense variant takes the batched server step's prefix ``M_rows``
    (snap rule) and also emits each event's downward nnz for byte
    accounting.  Donates ``sstate``.
    """
    if dense_down:
        def commit(sstate, ids, G, M_rows):
            sstate = ps.send_commit_rows(sstate, ids, G, M_rows)
            return sstate, jnp.sum(G != 0.0, axis=-1)
    else:
        def commit(sstate, ids, G):
            return ps.send_commit_rows(sstate, ids, G)
    return jax.jit(commit, donate_argnums=(0,))


def mesh_batched_server_step_fn(secondary_density, spec: CompressionSpec):
    """Mesh-sharded twin of :func:`batched_server_step_fn` — same call
    signature, same outputs, but ``sstate`` is a
    :class:`server.MeshServerState` and ALL S shard servers run inside
    this one stage (DESIGN.md §14).

    A sparse upward batch is routed ONCE through the in-graph alltoallv
    (``distributed.shard_exchange_batch``) before the prefix scan; each
    scan step then applies one fused per-shard scatter into the stacked
    ``(S, width)`` M.  Selection happens on the re-concatenated GLOBAL
    diff through the same ``ParamSpace.select``, so the downward message
    (and its wire bytes) are bit-identical to the flat server's.
    """
    dense_down = secondary_density is None
    spec_raw = dataclasses.replace(spec, quantize="none")

    def server_batch(sstate, msgs, ids):
        from repro.core import distributed
        sspec = sstate.spec
        S = sspec.n_shards
        width = sstate.M.shape[1]
        v_rows = sstate.v[ids]                       # (B, S, width)
        rows2d = jnp.arange(S, dtype=jnp.int32)[:, None]
        sparse_up = isinstance(msgs, SparseLeaf)
        if sparse_up:
            ri, rv, ovf = distributed.shard_exchange_batch(
                sspec, msgs.indices, msgs.values)    # (B, S, slots)
            xs = (ri, rv, v_rows)
        else:
            ups = jax.vmap(
                lambda m: ps.mesh_split(sspec, m, width))(msgs)
            ovf = jnp.zeros((), jnp.int32)
            xs = (ups, v_rows)

        def body(carry, x):
            M, t = carry
            if sparse_up:
                ri_b, rv_b, v_k = x
                # one fused scatter per shard: empty (-1) slots dump into
                # the padding column width, which is sliced away
                cols = jnp.where(ri_b >= 0, ri_b, width)
                Mp = jnp.concatenate(
                    [M, jnp.zeros((S, 1), M.dtype)], axis=1)
                M = Mp.at[rows2d, cols].add(-rv_b)[:, :-1]
            else:
                up_b, v_k = x
                M = M - up_b
            t = t + 1
            diff_flat = ps.mesh_concat(sspec, M - v_k)
            if dense_down:
                out = (diff_flat, M)
            else:
                out = (sstate.space.select(
                    diff_flat, sstate.space.ks(secondary_density),
                    spec_raw),)
            return (M, t), out

        (M, t), outs = jax.lax.scan(body, (sstate.M, sstate.t), xs)
        sstate = ps.keep_on_shards(
            sstate._replace(M=M, t=t, overflow=sstate.overflow + ovf))
        if dense_down:
            return sstate, outs[0], outs[1]
        return sstate, outs[0], None

    return server_batch


def make_mesh_batched_server_step(secondary_density, spec: CompressionSpec):
    """jit(mesh batched server); donates ``sstate``."""
    return jax.jit(mesh_batched_server_step_fn(secondary_density, spec),
                   donate_argnums=(0,))


def make_mesh_batched_commit(dense_down: bool):
    """Mesh twin of :func:`make_batched_commit` — same call signature.

    Sparse commits route the SHIPPED batch through the same alltoallv as
    the receive and land in ``v`` with ONE fused 3-D scatter (distinct
    worker rows x per-shard slots); dense commits snap each ``v`` row to
    the per-event prefix ``M_rows`` stack.  Donates ``sstate``.
    """
    if dense_down:
        def commit(sstate, ids, G, M_rows):
            # M_rows: (B, S, width) mesh prefix states; G: (B, total)
            sstate = ps.keep_on_shards(
                sstate._replace(v=sstate.v.at[ids].set(M_rows)))
            return sstate, jnp.sum(G != 0.0, axis=-1)
    else:
        def commit(sstate, ids, G):
            from repro.core import distributed
            sspec = sstate.spec
            S = sspec.n_shards
            width = sstate.v.shape[-1]
            ri, rv, ovf = distributed.shard_exchange_batch(
                sspec, G.indices, G.values)          # (B, S, slots)
            cols = jnp.where(ri >= 0, ri, width)
            vp = jnp.concatenate(
                [sstate.v,
                 jnp.zeros(sstate.v.shape[:2] + (1,), sstate.v.dtype)],
                axis=2)
            new_v = vp.at[
                ids[:, None, None],
                jnp.arange(S, dtype=jnp.int32)[None, :, None],
                cols].add(rv)[:, :, :-1]
            return ps.keep_on_shards(
                sstate._replace(v=new_v, overflow=sstate.overflow + ovf))
    return jax.jit(commit, donate_argnums=(0,))


def make_batched_apply():
    """jit(worker apply) over the batch rows of the stacked worker models;
    donates ``wp`` (the (n_workers, total) buffer updates in place).

    A sparse batch is scattered straight into ``wp``, one flat scatter
    per row (``kernels.ops.scatter_add_rows``): the batch's ``ids`` are
    pairwise distinct (``batch_schedule``), so it is bit-equal to the
    serial :func:`ps.apply_update` of each row.  A dense batch (ASGD) is the
    vmapped ``apply_update`` of the gathered rows, written back.
    """
    vapply = jax.vmap(ps.apply_update)

    def apply_rows(wp, ids, G):
        with jax.named_scope(LAYER.apply):
            if isinstance(G, SparseLeaf):
                from repro.kernels import ops
                return ops.scatter_add_rows(wp, ids, G.indices, G.values)
            return wp.at[ids].set(vapply(wp[ids], G))

    return jax.jit(apply_rows, donate_argnums=(0,))


@dataclasses.dataclass
class AsyncTrainer:
    """Asynchronous PS training loop over a gradient function.

    grad_fn(params, batch) -> (loss, grads)   [pure, jittable]
    """

    strategy: Strategy
    grad_fn: Callable
    n_workers: int
    lr: float
    secondary_density: float | None = None
    # engine/quantize spec for the server's secondary (downward) compression
    secondary_spec: CompressionSpec = engine_lib.EXACT_SPEC

    def _serial_stages(self, space: ParamSpace):
        """The four jitted serial stages, memoized per arena layout.

        ``jax.jit`` caches compilations per wrapper object, so rebuilding
        the wrappers every :meth:`run` would recompile every stage on
        every call — the trainer instance keeps them across runs.
        """
        cached = getattr(self, "_serial_cache", None)
        if cached is None or cached[0] != space:
            stages = (make_client_step(self.strategy, self.grad_fn, space),
                      make_server_step(self.secondary_density,
                                       self.secondary_spec),
                      make_commit(), make_apply())
            self._serial_cache = cached = (space, stages)
        return cached[1]

    def _batched_stages(self, space: ParamSpace):
        """The batched stage bundle (client/server/commit/apply + the two
        vmapped wire quantizers), memoized like :meth:`_serial_stages`."""
        cached = getattr(self, "_batched_cache", None)
        if cached is None or cached[0] != space:
            up_seg = self.strategy.message_seg(space)
            down_seg = (None if self.secondary_density is None
                        else space.ks(self.secondary_density))
            stages = (
                make_batched_client_step(self.strategy, self.grad_fn,
                                         space),
                make_batched_server_step(self.secondary_density,
                                         self.secondary_spec),
                make_batched_commit(self.secondary_density is None),
                make_batched_apply(),
                make_batched_quantize(self.strategy.quantize, up_seg),
                make_batched_quantize(self.secondary_spec.quantize,
                                      down_seg),
            )
            self._batched_cache = cached = (space, stages)
        return cached[1]

    def _metrics_step(self):
        """The jitted telemetry fold (repro.telemetry.metrics), memoized
        like the stages.  It is a SEPARATE executable that only reads
        stage outputs, so enabling metrics never changes the data-plane
        compilations (the bit-for-bit invariant)."""
        cached = getattr(self, "_metrics_cache", None)
        if cached is None:
            from repro.telemetry import metrics as metrics_lib
            self._metrics_cache = cached = metrics_lib.make_metrics_step()
        return cached

    def init(self, params0):
        space = ParamSpace.from_tree(params0)
        theta0 = space.pack(params0)
        workers = [
            # per-worker theta COPIES: the apply stage donates its theta
            # argument, and donating a buffer shared by every worker would
            # invalidate the others' models
            {"theta": jnp.copy(theta0), "strat": self.strategy.init(params0)}
            for _ in range(self.n_workers)
        ]
        return ps.init(params0, self.n_workers), workers

    def run(
        self,
        params0,
        schedule: np.ndarray,
        batch_fn: Callable[[int, int], Any],
        *,
        lr_fn: Callable[[int], float] | None = None,
        eval_fn: Callable | None = None,
        eval_every: int = 0,
        metrics: bool = False,
    ):
        """Run the full schedule.  batch_fn(event_idx, worker_id) -> batch.

        ``metrics=True`` folds every event into an on-device
        :class:`~repro.telemetry.metrics.MetricsState` (drained into
        ``History.metrics`` at the end).  It defaults OFF, leaving this
        loop byte-identical to the untelemetered path.
        """
        from repro.cluster import wire  # codec quantizer + byte accounting
        from repro.telemetry import metrics as metrics_lib

        space = ParamSpace.from_tree(params0)
        sstate, workers = self.init(params0)
        client_step, server_step, commit, apply_G = \
            self._serial_stages(space)
        up_mode = self.strategy.quantize
        down_mode = self.secondary_spec.quantize
        up_seg = self.strategy.message_seg(space)
        down_seg = (space.ks(self.secondary_density)
                    if self.secondary_density is not None else None)
        # frame sizes are static per (mode, seg, total) for sparse messages:
        # memoize the per-event cost once instead of re-deriving it from
        # on-device message structure every event (which cost a host sync);
        # dense messages stay per-event (their nnz is data-dependent)
        up_cost = (wire.frame_bytes_static(up_seg, space.total, up_mode)
                   if up_seg is not None else None)
        down_cost = (wire.frame_bytes_static(down_seg, space.total, down_mode)
                     if down_seg is not None else None)
        # history stays ON DEVICE during the loop (scalars per event); it
        # materializes ONCE at the end — a per-event float(loss) would
        # round-trip the host and stall the dispatch pipeline every event
        losses: list = []
        up_nnz: list = []       # dense up messages: data-dependent nnz
        down_nnz: list = []     # dense down messages: data-dependent nnz
        up_bytes = down_bytes = 0
        evals = []
        stal = staleness_of(schedule, self.n_workers)  # host precomputed
        ms = metrics_lib.init(self.n_workers) if metrics else None
        mstep = self._metrics_step() if metrics else None
        for e, k in enumerate(schedule):
            k = int(k)
            lr = self.lr if lr_fn is None else float(lr_fn(e))
            batch = batch_fn(e, k)
            wst, loss, msg = client_step(
                workers[k]["theta"], workers[k]["strat"], batch, lr)
            msg = wire.quantize_message(msg, up_mode, seg=up_seg)
            sstate, G = server_step(sstate, msg, jnp.int32(k))
            G = wire.quantize_message(G, down_mode, seg=down_seg)
            sstate = commit(sstate, jnp.int32(k), G)
            workers[k]["theta"] = apply_G(workers[k]["theta"], G)
            workers[k]["strat"] = wst
            losses.append(loss)
            if ms is not None:
                # one extra dispatch reading the SHIPPED messages; device
                # scalars only — no host sync in the loop
                ms = mstep(ms, np.int32(k), np.int32(stal[e]), msg, G)
            if up_cost is not None:
                up_bytes += up_cost
            else:
                up_nnz.append(jnp.count_nonzero(msg))
            if down_cost is not None:
                down_bytes += down_cost
            else:
                down_nnz.append(jnp.count_nonzero(G))
            if eval_fn is not None and eval_every and (e + 1) % eval_every == 0:
                model = ps.global_model(params0, sstate)
                evals.append((e + 1, eval_fn(model)))
        final = ps.global_model(params0, sstate)
        per_up = per_down = None
        if up_nnz:
            per_up = (wire.ENVELOPE_BYTES + wire.dense_frame_bytes(
                np.asarray(jnp.stack(up_nnz)), space.total))
            up_bytes += int(np.sum(per_up))
        if down_nnz:
            per_down = (wire.ENVELOPE_BYTES + wire.dense_frame_bytes(
                np.asarray(jnp.stack(down_nnz)), space.total))
            down_bytes += int(np.sum(per_down))
        hist = History(
            losses=np.asarray(jnp.stack(losses), np.float64),
            worker_ids=np.asarray(schedule),
            staleness=stal,
            up_bytes=up_bytes,
            down_bytes=down_bytes,
            evals=evals,
            metrics=metrics_lib.drain(ms) if ms is not None else None,
        )
        return final, sstate, hist

    def batched_programs(self, params0, schedule, batch_fn, *,
                         max_batch: int | None = None, eval_every: int = 0):
        """Every stage program :meth:`run_batched` dispatches for
        ``schedule``, as ``(jitted_stage, abstract_args)`` pairs: one per
        stage per batch size, with one batch's shapes from
        ``batch_fn(0, worker)``.

        ``jitted_stage.lower(*args).compile()`` builds the executable the
        run then uses.  Compiled one after another on the first batch of
        each size, these programs add up to minutes at real widths; a
        caller can compile them side by side in threads before the run.
        """
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        space = ParamSpace.from_tree(params0)
        client, server, commit, apply_rows, q_up, q_down = \
            self._batched_stages(space)
        n = self.n_workers
        wp = sds((n, space.total), jnp.float32)
        ws = jax.eval_shape(lambda p: jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (n,) + s.shape),
            self.strategy.init(p)), params0)
        sstate = jax.eval_shape(lambda p: ps.init(p, n), params0)
        one = jax.tree.map(jnp.asarray, batch_fn(0, int(schedule[0])))
        sizes = sorted({len(b) for b in batch_schedule(
            schedule, max_batch=max_batch, cut_every=eval_every or None)})
        progs = []
        for b in sizes:
            ids, lrs = sds((b,), jnp.int32), sds((b,), jnp.float32)
            data = jax.tree.map(lambda x: sds((b,) + x.shape, x.dtype), one)
            progs.append((client, (wp, ws, ids, data, lrs)))
            msgs = jax.eval_shape(client, *progs[-1][1])[2]
            if q_up is not None:
                progs.append((q_up, (msgs,)))
                msgs = jax.eval_shape(q_up, msgs)
            progs.append((server, (sstate, msgs, ids)))
            _, G, M_rows = jax.eval_shape(server, *progs[-1][1])
            if self.secondary_density is None:
                progs.append((commit, (sstate, ids, G, M_rows)))
            else:
                if q_down is not None:
                    progs.append((q_down, (G,)))
                    G = jax.eval_shape(q_down, G)
                progs.append((commit, (sstate, ids, G)))
            progs.append((apply_rows, (wp, ids, G)))
        return progs

    def run_batched(
        self,
        params0,
        schedule: np.ndarray,
        batch_fn: Callable[[int, int], Any],
        *,
        lr_fn: Callable[[int], float] | None = None,
        eval_fn: Callable | None = None,
        eval_every: int = 0,
        max_batch: int | None = None,
        recorder=None,
        metrics: bool = False,
    ):
        """Batched event loop — bit-for-bit equal to :meth:`run`.

        ``batch_schedule`` groups runs of pairwise-distinct workers; each
        batch then costs ONE dispatch per stage (vmapped client compute,
        scanned server receive+select, fused multi-row commit, vmapped
        apply) instead of four-plus dispatches per event.  Worker models
        and strategy states live stacked — ``(n_workers, total)`` arenas —
        and every stage donates its state, so the whole fleet updates in
        place.  Losses, final params, and byte accounting match the serial
        loop exactly on the same schedule (tests/test_async_sim.py).

        ``recorder`` (a :class:`repro.telemetry.Recorder`) gets host spans
        — ``batched/init``, per batch ``batched/batch_build``, ``client``
        (with the upward quantize), ``server``, ``commit`` and ``apply``,
        each with ``batch=<index>`` and ``size=<b>``, then
        ``batched/finish`` — which also land on the JAX profiler's clock,
        the count of programs built during the run, and the run summary.
        ``metrics=True`` folds each batch into an on-device
        :class:`~repro.telemetry.metrics.MetricsState` in one dispatch.
        Neither adds a host sync or changes a data-plane bit.
        """
        from repro.cluster import wire
        from repro import telemetry
        from repro.telemetry import metrics as metrics_lib

        rec = recorder if recorder is not None else telemetry.NULL
        with rec.counting_programs():
            n = self.n_workers
            with rec.span("batched/init"):
                space = ParamSpace.from_tree(params0)
                sstate = ps.init(params0, n)
                theta0 = space.pack(params0)
                # jnp.copy: donation needs owned buffers, not broadcast
                # views
                wp = jnp.copy(jnp.broadcast_to(theta0[None],
                                               (n, space.total)))
                ws = jax.tree.map(
                    lambda s: jnp.copy(jnp.broadcast_to(s[None],
                                                        (n,) + s.shape)),
                    self.strategy.init(params0))
            client, server, commit, apply_rows, q_up, q_down = \
                self._batched_stages(space)
            dense_down = self.secondary_density is None
            up_mode = self.strategy.quantize
            down_mode = self.secondary_spec.quantize
            up_seg = self.strategy.message_seg(space)
            down_seg = None if dense_down else space.ks(self.secondary_density)
            up_cost = (wire.frame_bytes_static(up_seg, space.total, up_mode)
                       if up_seg is not None else None)
            down_cost = (wire.frame_bytes_static(down_seg, space.total,
                                                 down_mode)
                         if down_seg is not None else None)

            batches = batch_schedule(schedule, max_batch=max_batch,
                                     cut_every=eval_every or None)
            stal = staleness_of(schedule, self.n_workers)
            ms = metrics_lib.init(self.n_workers) if metrics else None
            mstep = self._metrics_step() if metrics else None
            losses, up_nnz, down_nnz, evals = [], [], [], []
            e = 0
            for i, ids_np in enumerate(batches):
                b = len(ids_np)
                # numpy operands: the jit call converts them on its C++ fast
                # path — cheaper than one eager device dispatch per array
                ids = np.asarray(ids_np, np.int32)
                lrs = np.asarray(
                    [self.lr if lr_fn is None else float(lr_fn(e + j))
                     for j in range(b)], np.float32)
                with rec.span("batched/batch_build", batch=i, size=b):
                    data = [batch_fn(e + j, int(k))
                            for j, k in enumerate(ids_np)]
                    data = jax.tree.map(lambda *xs: jnp.stack(xs), *data)
                with rec.span("batched/client", batch=i, size=b):
                    ws, batch_losses, msgs, nnz_up = client(wp, ws, ids, data,
                                                            lrs)
                    if q_up is not None:
                        msgs = q_up(msgs)
                with rec.span("batched/server", batch=i, size=b):
                    sstate, G, M_rows = server(sstate, msgs, ids)
                with rec.span("batched/commit", batch=i, size=b):
                    if dense_down:
                        sstate, nnz_dn = commit(sstate, ids, G, M_rows)
                        down_nnz.append(nnz_dn)
                    else:
                        if q_down is not None:
                            G = q_down(G)
                        sstate = commit(sstate, ids, G)
                with rec.span("batched/apply", batch=i, size=b):
                    wp = apply_rows(wp, ids, G)
                losses.append(batch_losses)
                if ms is not None:
                    # whole batch folded in one dispatch; staleness is the
                    # host-precomputed schedule function — still no syncs
                    ms = mstep(ms, ids, stal[e:e + b].astype(np.int32),
                               msgs, G)
                if up_cost is None:
                    up_nnz.append(nnz_up)
                e += b
                if eval_fn is not None and eval_every and e % eval_every == 0:
                    with rec.span("batched/eval", event=e):
                        model = ps.global_model(params0, sstate)
                        evals.append((e, eval_fn(model)))
                    rec.event("eval", event=e, metric=_jsonable(evals[-1][1]),
                                  **({"metrics": metrics_lib.drain(ms)}
                                 if ms is not None else {}))
            with rec.span("batched/finish"):
                final = ps.global_model(params0, sstate)
                n_events = len(schedule)
                per_up = per_down = None
                if up_cost is not None:
                    up_bytes = up_cost * n_events
                else:
                    per_up = (wire.ENVELOPE_BYTES + wire.dense_frame_bytes(
                        np.asarray(jnp.concatenate(up_nnz)), space.total))
                    up_bytes = int(np.sum(per_up))
                if down_cost is not None:
                    down_bytes = down_cost * n_events
                else:
                    per_down = (wire.ENVELOPE_BYTES + wire.dense_frame_bytes(
                        np.asarray(jnp.concatenate(down_nnz)), space.total))
                    down_bytes = int(np.sum(per_down))
                hist = History(
                    losses=np.asarray(jnp.concatenate(losses), np.float64),
                    worker_ids=np.asarray(schedule),
                    staleness=stal,
                    up_bytes=up_bytes,
                    down_bytes=down_bytes,
                    evals=evals,
                    metrics=metrics_lib.drain(ms) if ms is not None else None,
                )
            _record_run_summary(rec, "batched", hist, up_cost, down_cost,
                                per_up, per_down)
            return final, sstate, hist


def run_msgd(
    params0,
    grad_fn,
    batches,
    *,
    lr: float,
    momentum: float = 0.7,
    lr_fn=None,
):
    """Single-node momentum SGD baseline (paper's MSGD)."""
    velocity = jax.tree.map(jnp.zeros_like, params0)

    @jax.jit
    def step(params, velocity, batch, lr):
        loss, grads = grad_fn(params, batch)
        params, velocity = msgd_step(
            params, velocity, grads, lr=lr, momentum=momentum
        )
        return params, velocity, loss

    params = params0
    losses = []
    for e, b in enumerate(batches):
        cur_lr = lr if lr_fn is None else float(lr_fn(e))
        params, velocity, loss = step(params, velocity, b, cur_lr)
        losses.append(float(loss))
    return params, np.asarray(losses)
