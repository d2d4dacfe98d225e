"""Cluster runtime: simulator bit-parity, TCP, federated scenarios."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import async_sim, make_strategy, server as ps
from repro.core.engine import CompressionSpec
from repro.cluster import run_inprocess
from repro.cluster.client import ClusterClient
from repro.cluster.coordinator import Coordinator
from repro.cluster.scenarios import (ClientPlan, dirichlet_class_weights,
                                     hetero_plans, participates)
from repro.cluster.transport import (TcpClientTransport,
                                     TcpCoordinatorTransport)


def _problem():
    key = jax.random.PRNGKey(0)
    Wt = jax.random.normal(key, (6, 4))

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        return jax.value_and_grad(loss)(params)

    def batch_fn(e, k):
        kk = jax.random.PRNGKey(int(e) * 131 + int(k) + 1)
        x = jax.random.normal(kk, (8, 6))
        return x, x @ Wt

    params0 = {"w": jnp.zeros((6, 4)), "b": jnp.zeros((4,))}
    return grad_fn, batch_fn, params0


# ---------------------------------------------------------------------------
# the keystone contract: bit-parity with AsyncTrainer on the same schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kw,sd,spec", [
    ("asgd", {}, None, CompressionSpec(engine="exact")),
    ("dgs", {"density": 0.2, "momentum": 0.7}, 0.1,
     CompressionSpec(engine="exact")),
    ("dgs", {"density": 0.2, "momentum": 0.7, "quantize": "int8"}, 0.1,
     CompressionSpec(engine="exact", quantize="bf16")),
    ("gd_async", {"density": 0.2, "quantize": "tern"}, None,
     CompressionSpec(engine="exact")),
])
def test_inprocess_cluster_bit_parity(name, kw, sd, spec):
    """Same schedule -> bit-identical losses/params, and the simulator's
    byte accounting == the bytes actually moved through the transport."""
    grad_fn, batch_fn, params0 = _problem()
    sched = async_sim.make_schedule(3, 40, seed=7, hetero=0.9)
    strat = make_strategy(name, **kw)
    tr = async_sim.AsyncTrainer(strat, grad_fn, 3, lr=0.03,
                                secondary_density=sd, secondary_spec=spec)
    f_sim, _, h_sim = tr.run(params0, sched, batch_fn)
    f_cl, h_cl = run_inprocess(strat, grad_fn, params0, batch_fn,
                               schedule=sched, lr=0.03,
                               secondary_density=sd, secondary_spec=spec)
    np.testing.assert_array_equal(h_sim.losses, h_cl.losses)
    np.testing.assert_array_equal(h_sim.worker_ids, h_cl.worker_ids)
    np.testing.assert_array_equal(h_sim.staleness, h_cl.staleness)
    for a, b in zip(jax.tree.leaves(f_sim), jax.tree.leaves(f_cl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert h_sim.up_bytes == h_cl.up_bytes
    assert h_sim.down_bytes == h_cl.down_bytes


# ---------------------------------------------------------------------------
# TCP backend
# ---------------------------------------------------------------------------

def test_tcp_two_clients_converge():
    grad_fn, batch_fn, params0 = _problem()
    strat = make_strategy("dgs", density=0.2, momentum=0.7, quantize="int8")
    ct = TcpCoordinatorTransport()
    coord = Coordinator(transport=ct, params0=params0, n_slots=2,
                        secondary_density=0.2, recv_timeout=120.0)

    def client_main(cid):
        t = TcpClientTransport("127.0.0.1", ct.port, cid)
        ClusterClient(
            transport=t, strategy=strat, grad_fn=grad_fn, params0=params0,
            batch_fn=batch_fn, plan=ClientPlan(client_id=cid, n_rounds=8),
            lr=0.05).run()
        t.close()

    threads = [threading.Thread(target=client_main, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    final, hist = coord.serve()
    for t in threads:
        t.join(timeout=60)
    ct.close()
    assert len(hist.losses) == 16
    assert hist.losses[-4:].mean() < hist.losses[:4].mean()
    assert hist.up_bytes > 0 and hist.down_bytes > 0


# ---------------------------------------------------------------------------
# federated scenarios
# ---------------------------------------------------------------------------

def test_scenario_elastic_partial_faulty_is_deterministic():
    """Joins/leaves + 70% participation + drops: runs, converges, and the
    whole virtual-time execution replays bit-identically."""
    grad_fn, batch_fn, params0 = _problem()
    plans = hetero_plans(4, 10, hetero=0.8, seed=3, participation=0.7,
                         late_join=1, early_leave=1, bandwidth=1e5,
                         drop_prob=0.15)
    strat = make_strategy("dgs", density=0.25, momentum=0.7)
    runs = [run_inprocess(strat, grad_fn, params0, batch_fn, plans=plans,
                          lr=0.05, inject_faults=True,
                          secondary_density=0.25) for _ in range(2)]
    (f1, h1), (f2, h2) = runs
    n_max = 3 * 10 + 5  # 3 full-life clients + early leaver's half life
    assert 5 < len(h1.losses) < n_max
    assert h1.losses[-3:].mean() < h1.losses[:3].mean()
    np.testing.assert_array_equal(h1.losses, h2.losses)
    np.testing.assert_array_equal(h1.worker_ids, h2.worker_ids)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_grows_and_reuses_slots():
    """More clients than initial slots: v grows via ps.add_worker; a freed
    slot is zeroed and reused by the next joiner."""
    grad_fn, batch_fn, params0 = _problem()
    plans = [ClientPlan(client_id=0, n_rounds=4),
             ClientPlan(client_id=1, n_rounds=2),
             # joins after client 1 leaves: reuses its slot
             ClientPlan(client_id=2, n_rounds=4, join_time=10.0)]
    strat = make_strategy("dgs", density=0.5, momentum=0.5)
    final, hist = run_inprocess(strat, grad_fn, params0, batch_fn,
                                plans=plans, n_workers=1, lr=0.05)
    assert len(hist.losses) == 10
    # slot ids stay within the grown pool (1 initial + 1 grown)
    assert set(hist.worker_ids.tolist()) <= {0, 1}


def test_participation_draws_are_seeded():
    plan = ClientPlan(client_id=1, n_rounds=100, participation=0.5, seed=9)
    a = [participates(plan, r) for r in range(100)]
    b = [participates(plan, r) for r in range(100)]
    assert a == b
    assert 20 < sum(a) < 80


def test_dirichlet_shards_skew_with_alpha():
    w_skew = dirichlet_class_weights(16, 10, 0.1, seed=0)
    w_iid = dirichlet_class_weights(16, 10, 1000.0, seed=0)
    np.testing.assert_allclose(w_skew.sum(1), 1.0, atol=1e-9)
    assert w_skew.max(1).mean() > 0.6     # concentrated
    assert w_iid.max(1).mean() < 0.2      # near uniform


def test_reset_worker_zeroes_v_row():
    params0 = {"w": jnp.ones((4,))}
    state = ps.init(params0, 2)
    state, _ = ps.add_worker(state)
    assert state.v.shape[0] == 3
    msg = jnp.ones((4,), jnp.float32)   # dense arena update
    state = ps.receive(state, msg)
    state, _ = ps.send(state, 2)
    assert float(jnp.abs(state.v[2]).sum()) > 0
    state = ps.reset_worker(state, 2)
    assert float(jnp.abs(state.v[2]).sum()) == 0.0


# ---------------------------------------------------------------------------
# flight-recorder accounting: injected faults must show up in telemetry
# ---------------------------------------------------------------------------

def test_fault_policy_accounting_matches_seeded_expectations():
    """Every injected drop, observed retry, and virtual-time cost must be
    visible in the coordinator's telemetry counters — and the drop counts
    must equal a host-side replay of each FaultInjector's seeded rng."""
    grad_fn, batch_fn, params0 = _problem()
    n_rounds, drop_prob, bandwidth, delay = 8, 0.3, 1e5, 0.01
    plans = [ClientPlan(client_id=c, n_rounds=n_rounds,
                        compute_time=1.0 + 0.3 * c, bandwidth=bandwidth,
                        delay=delay, drop_prob=drop_prob, seed=11)
             for c in range(3)]
    strat = make_strategy("dgs", density=0.25, momentum=0.7)
    _, hist = run_inprocess(strat, grad_fn, params0, batch_fn, plans=plans,
                            lr=0.05, secondary_density=0.25,
                            inject_faults=True)

    counters = hist.metrics["counters"]
    clients = hist.metrics["clients"]
    assert len(hist.losses) == 3 * n_rounds   # every drop was recovered
    total_drops = 0
    for p in plans:
        cid = p.client_id
        acct = clients[cid]
        # the injector draws its rng ONCE per droppable (UP) send: the
        # n_rounds scheduled sends plus one resend per observed retry.
        # Replaying those draws must reproduce the injected drop count.
        rng = np.random.default_rng(p.fault_policy(realtime=False).seed)
        draws = rng.random(n_rounds + acct["retries"])
        assert acct["drops"] == int((draws < drop_prob).sum())
        # every drop forces a reply timeout, so retries >= drops; spurious
        # timeouts (slow first-compile) may add benign extra retransmits
        assert acct["retries"] >= acct["drops"]
        total_drops += acct["drops"]
        # per-client coordinator counters: all rounds served exactly once
        assert counters[f"client/{cid}/events"] == n_rounds
        up = counters[f"client/{cid}/up_bytes"]
        down = counters[f"client/{cid}/down_bytes"]
        assert up > 0 and down > 0
        # virtual time booked with the scheduler == the policy's formula
        # over exactly the frames the coordinator served
        expect_cost = n_rounds * delay + (up + down) / bandwidth
        np.testing.assert_allclose(counters[f"client/{cid}/virtual_cost"],
                                   expect_cost, rtol=1e-9)
    assert total_drops > 0, "policy injected nothing — test is vacuous"
    # duplicate UPs (retransmits that survived) were answered from the
    # reply cache, never re-applied
    assert counters.get("dup", 0) == counters.get("reply_cache_hits", 0)


# ---------------------------------------------------------------------------
# sharded parameter server (DESIGN.md §12)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("name,kw,sd,spec", [
    ("asgd", {}, None, CompressionSpec(engine="exact")),
    ("dgs", {"density": 0.2, "momentum": 0.7, "quantize": "int8"}, 0.1,
     CompressionSpec(engine="exact", quantize="bf16")),
    ("dgc_async", {"density": 0.2, "momentum": 0.7}, None,
     CompressionSpec(engine="exact")),
])
def test_sharded_inprocess_bit_parity(n_shards, name, kw, sd, spec):
    """S coordinator shards over disjoint arena ranges reproduce the
    single-server run bit-for-bit (losses, event order, final params),
    and the sharded wire bytes match the static per-shard accounting."""
    from repro.cluster import wire
    from repro.core.paramspace import ParamSpace, ShardSpec

    grad_fn, batch_fn, params0 = _problem()
    sched = async_sim.make_schedule(3, 24, seed=7, hetero=0.9)
    strat = make_strategy(name, **kw)
    f1, h1 = run_inprocess(strat, grad_fn, params0, batch_fn,
                           schedule=sched, lr=0.03,
                           secondary_density=sd, secondary_spec=spec)
    fS, hS = run_inprocess(strat, grad_fn, params0, batch_fn,
                           schedule=sched, lr=0.03,
                           secondary_density=sd, secondary_spec=spec,
                           n_shards=n_shards)
    np.testing.assert_array_equal(h1.losses, hS.losses)
    np.testing.assert_array_equal(h1.worker_ids, hS.worker_ids)
    np.testing.assert_array_equal(h1.staleness, hS.staleness)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(fS)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # sparse upward frames have a static size: the sharded run's measured
    # up bytes must equal the per-shard static accounting exactly
    space = ParamSpace.from_tree(params0)
    up_seg = strat.message_seg(space)
    if up_seg is not None:
        sspec = ShardSpec.for_space(space, n_shards)
        per_event = sum(wire.shard_frame_bytes_static(sspec, up_seg,
                                                      strat.quantize))
        assert hS.up_bytes == per_event * len(hS.losses)
        assert h1.up_bytes == (wire.frame_bytes_static(up_seg, space.total,
                                                       strat.quantize)
                               * len(h1.losses))
    # every shard served every event; the balance counters say so
    counters = hS.metrics["counters"]
    for s in range(n_shards):
        assert counters[f"shard/{s}/events"] == len(hS.losses)
        assert counters[f"shard/{s}/arena_elems"] == \
            ShardSpec.for_space(space, n_shards).sizes[s]


def _run_tcp_lockstep(n_shards, *, rounds=6, clients=3, sd=0.2):
    """One TCP cluster run serving a lockstep round-robin schedule."""
    from repro.cluster.transport import ScheduleDriven
    from repro.core.paramspace import ParamSpace, ShardSpec

    grad_fn, batch_fn, params0 = _problem()
    strat = make_strategy("dgs", density=0.2, momentum=0.7, quantize="int8")
    order = np.tile(np.arange(clients), rounds)
    shard_spec = (ShardSpec.for_space(ParamSpace.from_tree(params0),
                                      n_shards)
                  if n_shards > 1 else None)
    cts = [TcpCoordinatorTransport() for _ in range(n_shards)]
    coords = [Coordinator(transport=cts[s], params0=params0,
                          n_slots=clients, secondary_density=sd,
                          recv_timeout=120.0,
                          scheduler=ScheduleDriven(order),
                          shard_spec=shard_spec, shard_id=s)
              for s in range(n_shards)]

    def client_main(cid):
        ts = [TcpClientTransport("127.0.0.1", ct.port, cid) for ct in cts]
        ClusterClient(
            transport=ts if n_shards > 1 else ts[0],
            shard_spec=shard_spec, pin_slot=True, strategy=strat,
            grad_fn=grad_fn, params0=params0, batch_fn=batch_fn,
            plan=ClientPlan(client_id=cid, n_rounds=rounds), lr=0.05).run()
        for t in ts:
            t.close()

    client_threads = [threading.Thread(target=client_main, args=(i,),
                                       daemon=True) for i in range(clients)]
    for t in client_threads:
        t.start()
    results = [None] * n_shards
    coord_threads = [threading.Thread(
        target=lambda s=s: results.__setitem__(s, coords[s].serve()),
        daemon=True) for s in range(1, n_shards)]
    for t in coord_threads:
        t.start()
    results[0] = coords[0].serve()
    for t in client_threads + coord_threads:
        t.join(timeout=60)
    for ct in cts:
        ct.close()
    finals = [r[0] for r in results]
    if n_shards > 1:
        leaves = [leaf for f in finals for leaf in jax.tree.leaves(f)]
        final = jax.tree.unflatten(jax.tree.structure(params0), leaves)
    else:
        final = finals[0]
    return final, [r[1] for r in results]


def test_sharded_tcp_bit_parity():
    """A 2-shard TCP cluster reproduces the 1-shard TCP run bit-for-bit
    under the same lockstep schedule — real sockets, split frames."""
    f1, (h1,) = _run_tcp_lockstep(1)
    f2, hs = _run_tcp_lockstep(2)
    for h in hs:   # every shard logged the identical event stream
        np.testing.assert_array_equal(h1.losses, h.losses)
        np.testing.assert_array_equal(h1.worker_ids, h.worker_ids)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # each shard moved fewer bytes than the whole model's single frame
    assert all(0 < h.up_bytes < h1.up_bytes for h in hs)


# ---------------------------------------------------------------------------
# device-mesh shard servers (DESIGN.md §14)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shards", [2, 4])
@pytest.mark.parametrize("name,kw,sd,spec", [
    ("asgd", {}, None, CompressionSpec(engine="exact")),
    ("dgs", {"density": 0.2, "momentum": 0.7, "quantize": "int8"}, 0.1,
     CompressionSpec(engine="exact", quantize="bf16")),
    ("dgs", {"density": 0.2, "momentum": 0.7, "engine": "sampled",
             "quantize": "bf16"}, None, CompressionSpec(engine="exact")),
    ("dgs", {"density": 0.2, "momentum": 0.7, "engine": "blockwise",
             "quantize": "tern"}, 0.2, CompressionSpec(engine="exact")),
    ("dgc_async", {"density": 0.2, "momentum": 0.7}, None,
     CompressionSpec(engine="exact")),
])
def test_mesh_inprocess_bit_parity(mesh_shards, name, kw, sd, spec):
    """The mesh-sharded runtime (ONE coordinator, S in-graph shard servers
    over stacked arenas) reproduces both the single-server run AND the
    S-thread sharded runtime bit-for-bit — and, unlike the S-thread
    runtime, moves exactly the single-server wire bytes (one frame per
    event, split in-graph rather than on the wire)."""
    from repro.core.paramspace import ParamSpace, ShardSpec

    grad_fn, batch_fn, params0 = _problem()
    sched = async_sim.make_schedule(3, 24, seed=7, hetero=0.9)
    strat = make_strategy(name, **kw)
    f1, h1 = run_inprocess(strat, grad_fn, params0, batch_fn,
                           schedule=sched, lr=0.03,
                           secondary_density=sd, secondary_spec=spec)
    fM, hM = run_inprocess(strat, grad_fn, params0, batch_fn,
                           schedule=sched, lr=0.03,
                           secondary_density=sd, secondary_spec=spec,
                           mesh_shards=mesh_shards)
    fT, hT = run_inprocess(strat, grad_fn, params0, batch_fn,
                           schedule=sched, lr=0.03,
                           secondary_density=sd, secondary_spec=spec,
                           n_shards=mesh_shards)
    np.testing.assert_array_equal(h1.losses, hM.losses)
    np.testing.assert_array_equal(h1.worker_ids, hM.worker_ids)
    np.testing.assert_array_equal(h1.staleness, hM.staleness)
    np.testing.assert_array_equal(hT.losses, hM.losses)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(fM)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(fT), jax.tree.leaves(fM)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # bytes contract: the mesh runtime speaks the SINGLE-server wire
    # protocol — the index-range split happens in-graph, not on the wire
    assert (hM.up_bytes, hM.down_bytes) == (h1.up_bytes, h1.down_bytes)
    # fixed-capacity route slots never overflowed, and every shard saw
    # every event with its static arena range
    counters = hM.metrics["counters"]
    assert counters["route_overflow"] == 0
    sspec = ShardSpec.for_space(ParamSpace.from_tree(params0), mesh_shards)
    for s in range(mesh_shards):
        assert counters[f"shard/{s}/events"] == len(hM.losses)
        assert counters[f"shard/{s}/arena_elems"] == sspec.sizes[s]


def test_mesh_and_thread_sharding_are_exclusive():
    grad_fn, batch_fn, params0 = _problem()
    strat = make_strategy("dgs", density=0.2, momentum=0.7)
    with pytest.raises(ValueError, match="exactly one"):
        run_inprocess(strat, grad_fn, params0, batch_fn,
                      schedule=np.zeros(4, np.int64), lr=0.03,
                      n_shards=2, mesh_shards=2)


def test_mesh_serving_not_implemented():
    grad_fn, batch_fn, params0 = _problem()
    strat = make_strategy("dgs", density=0.2, momentum=0.7)
    with pytest.raises(NotImplementedError, match="mesh-sharded serving"):
        run_inprocess(strat, grad_fn, params0, batch_fn,
                      schedule=np.zeros(4, np.int64), lr=0.03,
                      mesh_shards=2, n_replicas=1)


def test_mesh_shards_spread_over_devices_bitwise():
    """With a ``shards`` mesh (here 4 forced host devices, the mesh rule
    patched to accept them), every shard's M row and v column sits on its
    own device before and after the server stages, the stage carries the
    all_to_all, and the run still equals the flat single server bit for
    bit.  Runs in a subprocess so the device count cannot leak."""
    import os
    import pathlib
    import subprocess
    import sys

    import repro

    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import async_sim, distributed, make_strategy
from repro.core import server as ps
from repro.core.engine import CompressionSpec
from repro.core.sparsify import SparseLeaf
from repro.cluster.runner import run_inprocess
distributed.shard_mesh = lambda S: Mesh(np.asarray(jax.devices()[:S]),
                                        ("shards",))
key = jax.random.PRNGKey(0)
Wt = jax.random.normal(key, (6, 4))
def grad_fn(p, batch):
    x, y = batch
    loss = lambda q: jnp.mean((x @ q["w"] + q["b"] - y) ** 2)
    return jax.value_and_grad(loss)(p)
def batch_fn(e, k):
    x = jax.random.normal(jax.random.PRNGKey(int(e) * 131 + int(k) + 1),
                          (8, 6))
    return x, x @ Wt
params0 = {"w": jnp.zeros((6, 4)), "b": jnp.zeros((4,))}
strat = make_strategy("dgs", density=0.2, momentum=0.7, quantize="int8")
kw = dict(schedule=async_sim.make_schedule(3, 24, seed=7, hetero=0.9),
          lr=0.03, secondary_density=0.2,
          secondary_spec=CompressionSpec(engine="exact"))
f1, h1 = run_inprocess(strat, grad_fn, params0, batch_fn, **kw)
fM, hM = run_inprocess(strat, grad_fn, params0, batch_fn, mesh_shards=4,
                       **kw)
np.testing.assert_array_equal(h1.losses, hM.losses)
for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(fM)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert (h1.up_bytes, h1.down_bytes) == (hM.up_bytes, hM.down_bytes)
def spread(st):
    for arr, axis in ((st.M, 0), (st.v, 1)):
        shards = arr.addressable_shards
        assert len({s.device for s in shards}) == 4, shards
        assert all(s.data.shape[axis] == 1 for s in shards)
st = ps.init_mesh_shards(params0, 3, 4)
spread(st)
stage = async_sim.make_mesh_batched_server_step(
    0.2, CompressionSpec(engine="exact"))
msgs = SparseLeaf(values=jnp.ones((2, 5)),
                  indices=jnp.asarray([[0, 3, 9, 17, 27]] * 2, jnp.int32),
                  size=28)
ids = jnp.asarray([0, 1], jnp.int32)
assert "all-to-all" in stage.lower(st, msgs, ids).compile().as_text()
st, _, _ = stage(st, msgs, ids)
spread(st)
print("MESH_SPREAD_OK")
"""
    root = pathlib.Path(next(iter(repro.__path__))).parent
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_SPREAD_OK" in out.stdout
