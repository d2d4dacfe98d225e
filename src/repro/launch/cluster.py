"""Launch a real multi-process federated cluster over TCP.

    PYTHONPATH=src python -m repro.launch.cluster --clients 4 --rounds 20
    PYTHONPATH=src python -m repro.launch.cluster --clients 4 --shards 2
    PYTHONPATH=src python -m repro.launch.cluster --smoke [--shards 2]

The main process runs the coordinator; each client is a separate OS process
(``--role client`` re-invocations of this module) connecting over a real
socket, so every gradient crosses the packed wire codec and the printed
up/down numbers are *measured* bytes, not a formula.  All processes rebuild
the identical problem (MLP on the gaussian-blobs task, optionally Dirichlet
non-IID sharded) from the shared ``--seed``; nothing but wire frames moves
between them.

``--shards S`` range-partitions the parameter arena across S coordinator
shards (DESIGN.md §12), each listening on its own port; clients connect to
every shard (``--ports p0,p1,...``), split each upward frame by index
range, and merge the per-shard downward diffs.  Sharded runs serve clients
in a LOCKSTEP round-robin schedule so every shard sees the identical event
order — which makes an S-shard run reproduce the 1-shard run's losses and
final parameters bit-for-bit (disjoint-range scatter-adds commute).

``--mesh-shards S`` runs the same range partition as ONE coordinator
hosting all S shard arenas in-graph (DESIGN.md §14): the stacked mesh
server stages route every message through the alltoallv exchange, clients
connect to a single ordinary port, and both losses/params AND measured
wire bytes reproduce the unsharded run bit-for-bit.  Uses one JAX device
per shard when available (``XLA_FLAGS=--xla_force_host_platform_device_``
``count=S`` on CPU); otherwise the bit-identical single-device fallback.
Mutually exclusive with ``--shards``.

One process per chip: the coordinator may hold the accelerator, so every
child this launcher spawns (clients here, trainers and replicas in
``launch/serve.py``) starts with ``JAX_PLATFORMS=cpu``.  The children stand
for the federated devices on the far side of the wire; a child that
reached for the chip the coordinator holds would fail or hang.

``--smoke`` is the CI guard for the multiprocess path: 2 clients, a few
int8-quantized rounds, asserts the loss dropped, and exits nonzero on any
hang (every stage is timeout-bounded).  With ``--shards S`` (or
``--mesh-shards S``) the smoke run first serves a 1-shard lockstep
reference, then the sharded run, and asserts their losses and final
parameters are bit-identical (for mesh runs, the measured bytes too).
"""
from __future__ import annotations

import argparse
import atexit
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import make_strategy
from repro.core.engine import CompressionSpec
from repro.data.synthetic import ClassificationTask

log = telemetry.get_logger("cluster")

# every child this launcher spawns, so nothing is orphaned when the
# launcher dies mid-run (e.g. `timeout` sending SIGTERM to a hung smoke —
# the finally-block cleanup never runs on an unhandled signal)
_CHILDREN: list[subprocess.Popen] = []


def spawn(cmd) -> subprocess.Popen:
    """``Popen`` tracked for reaping by :func:`reap_children`.  The child
    runs JAX on the CPU: the spawning process may hold the chip."""
    proc = subprocess.Popen(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    _CHILDREN.append(proc)
    return proc


def reap_children(timeout: float = 5.0):
    """Terminate -> wait -> kill every live tracked child."""
    live = [p for p in _CHILDREN if p.poll() is None]
    for p in live:
        try:
            p.terminate()
        except OSError:
            pass
    deadline = time.monotonic() + timeout
    for p in live:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
    _CHILDREN.clear()


def install_reaper():
    """Reap children on normal exit AND on SIGTERM/SIGINT.

    ``timeout``(1) kills a hung smoke with SIGTERM; without a handler the
    client processes (blocked on their sockets) outlive the launcher.
    The handler re-exits with the conventional 128+signum code.
    """
    atexit.register(reap_children)

    def _on_signal(signum, frame):
        reap_children()
        sys.exit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_signal)
        except ValueError:
            pass   # not the main thread (embedded use): atexit still runs


def _problem(args):
    """Deterministic shared problem — identical in every process."""
    task = ClassificationTask(n_features=args.features,
                              n_classes=args.classes,
                              batch_size=args.batch_size,
                              noise=0.6, seed=args.seed)
    if args.alpha > 0:
        from repro.cluster.scenarios import NonIIDClassification
        data = NonIIDClassification(task=task, alpha=args.alpha,
                                    shard_seed=args.seed,
                                    n_clients=args.clients)
    else:
        data = task

    k1, k2 = jax.random.split(jax.random.PRNGKey(args.seed))
    h = args.hidden
    params0 = {
        "w1": jax.random.normal(k1, (args.features, h)) * 0.2,
        "b1": jnp.zeros((h,)),
        "w2": jax.random.normal(k2, (h, args.classes)) * 0.2,
        "b2": jnp.zeros((args.classes,)),
    }

    def apply(p, x):
        return jax.nn.relu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def grad_fn(p, batch):
        x, y = batch

        def loss(p):
            lp = jax.nn.log_softmax(apply(p, x))
            return -jnp.mean(lp[jnp.arange(x.shape[0]), y])

        return jax.value_and_grad(loss)(p)

    def batch_fn(e, k):
        return data.batch(int(e), int(k) % args.clients)

    def accuracy(p):
        x, y = task.eval_set(512)
        return float(jnp.mean(jnp.argmax(apply(p, x), -1) == y))

    return params0, grad_fn, batch_fn, accuracy


def _strategy(args):
    kw = {}
    if args.strategy != "asgd":
        kw["density"] = args.density
    if args.strategy in ("dgs", "dgc_async"):
        kw["momentum"] = args.momentum
    if args.strategy != "asgd":
        kw["quantize"] = args.quantize
    return make_strategy(args.strategy, **kw)


def run_client(args):
    from repro.cluster.client import ClusterClient
    from repro.cluster.scenarios import ClientPlan
    from repro.cluster.transport import TcpClientTransport
    from repro.core.paramspace import ParamSpace, ShardSpec

    params0, grad_fn, batch_fn, _ = _problem(args)
    ports = ([int(x) for x in args.ports.split(",")] if args.ports
             else [args.port])
    transports = [TcpClientTransport(args.host, pt, args.client_id,
                                     connect_timeout=args.timeout)
                  for pt in ports]
    # every process derives the same ShardSpec from the same params0, so
    # client-side splitting and coordinator-side ownership always agree
    shard_spec = (ShardSpec.for_space(ParamSpace.from_tree(params0),
                                      len(ports))
                  if len(ports) > 1 else None)
    client = ClusterClient(
        transport=transports if len(transports) > 1 else transports[0],
        shard_spec=shard_spec,
        pin_slot=args.pin_slot,
        strategy=_strategy(args),
        grad_fn=grad_fn,
        params0=params0,
        batch_fn=batch_fn,
        plan=ClientPlan(client_id=args.client_id, n_rounds=args.rounds,
                        participation=args.participation, seed=args.seed),
        lr=args.lr,
        reply_timeout=args.timeout,
        max_retries=3,
    )
    client.run()
    for t in transports:
        t.close()
    return 0


def _serve_cluster(args, params0, *, spawn_clients: bool, n_shards: int,
                   recorder, lockstep: bool | None = None,
                   mesh_shards: int = 0):
    """One coordinator-side run (1 or S shards); returns (final, hist, dt).

    ``lockstep`` serves clients in an explicit round-robin schedule
    (client 0..C-1, repeated ``rounds`` times) instead of arrival order —
    the determinism sharded runs need so every shard sees the identical
    event order (and the 1-shard reference a ``--smoke --shards`` run is
    compared against sees it too).  Defaults to ``n_shards > 1`` or
    ``mesh_shards > 0``.  ``mesh_shards = S`` keeps ONE transport/port and
    hands the S-way range partition to the coordinator's in-graph mesh
    stages (clients are oblivious).
    """
    from repro.cluster.coordinator import Coordinator
    from repro.cluster.transport import (ScheduleDriven,
                                         TcpCoordinatorTransport)
    from repro.core.paramspace import ParamSpace, ShardSpec

    if lockstep is None:
        lockstep = n_shards > 1 or mesh_shards > 0
    transports = [TcpCoordinatorTransport(args.host,
                                          args.port if s == 0 else 0)
                  for s in range(n_shards)]
    ports = [t.port for t in transports]
    log.info(f"[coordinator] listening on {transports[0].host}:"
             f"{','.join(str(p) for p in ports)} ({args.clients} clients x "
             f"{args.rounds} rounds, {n_shards} shard(s))")
    procs = []
    if spawn_clients:
        for c in range(args.clients):
            cmd = [sys.executable, "-m", "repro.launch.cluster",
                   "--role", "client", "--client-id", str(c),
                   "--ports", ",".join(str(p) for p in ports)] \
                + _shared_flags(args)
            if lockstep:
                cmd.append("--pin-slot")
            procs.append(spawn(cmd))

    shard_spec = (ShardSpec.for_space(ParamSpace.from_tree(params0),
                                      n_shards)
                  if n_shards > 1 else None)
    spec = CompressionSpec(engine="exact", quantize=args.secondary_quantize)
    order = np.tile(np.arange(args.clients), args.rounds)
    coords = [Coordinator(
        transport=transports[s],
        params0=params0,
        n_slots=args.clients,
        secondary_density=args.secondary_density,
        secondary_spec=spec,
        scheduler=ScheduleDriven(order) if lockstep else None,
        recv_timeout=args.timeout,
        recorder=recorder,
        shard_spec=shard_spec,
        shard_id=s,
        mesh_shards=mesh_shards,
    ) for s in range(n_shards)]

    results: list = [None] * n_shards
    errors: list = []

    def _serve(s):
        try:
            results[s] = coords[s].serve()
        except Exception as exc:
            errors.append(exc)

    shard_threads = [threading.Thread(target=_serve, args=(s,), daemon=True)
                     for s in range(1, n_shards)]
    t0 = time.perf_counter()
    try:
        with recorder.span("cluster/serve"):
            for t in shard_threads:
                t.start()
            final, hist = coords[0].serve()
            for t in shard_threads:
                t.join(timeout=args.timeout)
        if errors:
            raise errors[0]
        dt = time.perf_counter() - t0
    finally:
        # on any serve() failure, still reap the children + free the ports
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
            finally:
                if p in _CHILDREN:
                    _CHILDREN.remove(p)
        for t in transports:
            t.close()

    if n_shards > 1:
        # stitch shard results: shard 0's History is the event log (every
        # shard served the identical lockstep order), bytes sum across
        # shards, shard/{i}/* counters merge, per-shard leaves concatenate
        results[0] = (final, hist)
        leaves = [leaf for f, _ in results for leaf in jax.tree.leaves(f)]
        final = jax.tree.unflatten(jax.tree.structure(params0), leaves)
        counters = dict(hist.metrics["counters"])
        for _, h in results[1:]:
            counters.update({k: v for k, v in h.metrics["counters"].items()
                             if k.startswith("shard/")})
        hist = hist._replace(
            up_bytes=sum(h.up_bytes for _, h in results),
            down_bytes=sum(h.down_bytes for _, h in results),
            metrics={**hist.metrics, "counters": counters})
    return final, hist, dt


def run_coordinator(args, *, spawn_clients: bool):
    params0, grad_fn, _, accuracy = _problem(args)
    recorder = (telemetry.Recorder(args.trace_dir)
                if args.trace_dir else telemetry.NULL)
    if recorder.enabled:
        telemetry.set_recorder(recorder)

    ref_hist = ref_final = None
    if args.smoke and (args.shards > 1 or args.mesh_shards > 0):
        # the bit-parity reference: same problem, same lockstep order,
        # ONE unsharded server — the sharded run below must reproduce it
        ref_final, ref_hist, _ = _serve_cluster(
            args, params0, spawn_clients=spawn_clients, n_shards=1,
            recorder=telemetry.NULL, lockstep=True)

    final, hist, dt = _serve_cluster(
        args, params0, spawn_clients=spawn_clients, n_shards=args.shards,
        recorder=recorder, mesh_shards=args.mesh_shards)

    n = max(1, len(hist.losses))
    log.info(f"[coordinator] {len(hist.losses)} events in {dt:.1f}s | "
             f"loss {hist.losses[:3].mean():.4f} -> "
             f"{hist.losses[-3:].mean():.4f} | acc {accuracy(final):.3f}")
    log.info(f"[coordinator] measured wire bytes: up={hist.up_bytes} "
             f"({hist.up_bytes / n:.0f}/event) down={hist.down_bytes} "
             f"({hist.down_bytes / n:.0f}/event)")
    if recorder.enabled:
        telemetry.set_recorder(None)
        paths = recorder.close()
        log.info(f"[coordinator] telemetry: {' '.join(paths)}")
    if args.smoke:
        assert len(hist.losses) == args.clients * args.rounds, \
            "smoke: missing events"
        assert hist.losses[-3:].mean() < hist.losses[:3].mean(), \
            "smoke: loss did not decrease"
        assert hist.up_bytes > 0 and hist.down_bytes > 0
        if ref_hist is not None:
            assert np.array_equal(hist.losses, ref_hist.losses), \
                "smoke: sharded losses diverged from 1-shard reference"
            for a, b in zip(jax.tree.leaves(final),
                            jax.tree.leaves(ref_final)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    "smoke: sharded params diverged from 1-shard reference"
            if args.mesh_shards > 0:
                # the mesh contract is stronger: one coordinator, one wire
                # frame per event — measured bytes match the reference too
                assert (hist.up_bytes, hist.down_bytes) == \
                    (ref_hist.up_bytes, ref_hist.down_bytes), \
                    "smoke: mesh-sharded bytes diverged from reference"
                label = f"{args.mesh_shards}-mesh-shard"
            else:
                label = f"{args.shards}-shard"
            log.info(f"[coordinator] smoke OK: {label} run "
                     f"bit-identical to 1-shard reference")
        else:
            log.info("[coordinator] smoke OK")
    return 0


def _shared_flags(args) -> list[str]:
    return ["--clients", str(args.clients), "--rounds", str(args.rounds),
            "--strategy", args.strategy, "--density", str(args.density),
            "--momentum", str(args.momentum), "--quantize", args.quantize,
            "--lr", str(args.lr), "--seed", str(args.seed),
            "--features", str(args.features), "--classes", str(args.classes),
            "--hidden", str(args.hidden), "--batch-size",
            str(args.batch_size), "--alpha", str(args.alpha),
            "--participation", str(args.participation),
            "--host", args.host, "--timeout", str(args.timeout)]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--role", choices=("auto", "coordinator", "client"),
                   default="auto")
    p.add_argument("--smoke", action="store_true",
                   help="tiny timeout-guarded multi-process CI run; with "
                        "--shards S it first runs a 1-shard reference and "
                        "asserts the sharded run is bit-identical")
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--rounds", type=int, default=20)
    p.add_argument("--client-id", type=int, default=0)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--shards", type=int, default=1,
                   help="coordinator shards: range-partition the parameter "
                        "arena across S servers, one port each (lockstep "
                        "round-robin serving; bit-identical to --shards 1)")
    p.add_argument("--mesh-shards", type=int, default=0,
                   help="in-graph device-mesh shards: ONE coordinator runs "
                        "all S shard arenas inside a single shard_mapped "
                        "server stage (DESIGN.md §14); one port, clients "
                        "unchanged, bytes AND losses bit-identical to the "
                        "unsharded run (exclusive with --shards)")
    p.add_argument("--ports", default=None,
                   help="client role: comma-separated coordinator shard "
                        "ports, shard order (overrides --port)")
    p.add_argument("--pin-slot", action="store_true",
                   help="client role: claim worker slot == client id "
                        "(lockstep runs need every shard to agree)")
    p.add_argument("--strategy", default="dgs")
    p.add_argument("--density", type=float, default=0.05)
    p.add_argument("--momentum", type=float, default=0.7)
    p.add_argument("--quantize", default="none",
                   choices=("none", "bf16", "int8", "tern"))
    p.add_argument("--secondary-density", type=float, default=None)
    p.add_argument("--secondary-quantize", default="none",
                   choices=("none", "bf16", "int8", "tern"))
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--alpha", type=float, default=0.0,
                   help="Dirichlet non-IID concentration (0 = IID)")
    p.add_argument("--participation", type=float, default=1.0)
    p.add_argument("--features", type=int, default=32)
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--timeout", type=float, default=60.0)
    p.add_argument("--trace-dir", default=None,
                   help="write trace.json + events.jsonl (flight recorder) "
                        "under this directory — coordinator role only")
    p.add_argument("--log-level", default=None,
                   help="silence/route launcher output: debug | info | "
                        "warning | error (default: REPRO_LOG env or info)")
    p.add_argument("--log-file", default=None,
                   help="mirror launcher output (timestamped) to a file")
    args = p.parse_args(argv)
    if args.mesh_shards and args.shards > 1:
        p.error("--shards and --mesh-shards are two different sharding "
                "runtimes (S coordinator processes vs one in-graph mesh "
                "stage) — pass exactly one of them")
    if args.log_level:
        telemetry.set_level(args.log_level)
    if args.log_file:
        telemetry.set_log_file(args.log_file)
    install_reaper()

    if args.smoke:
        args.clients, args.rounds = 2, 6
        args.strategy, args.density, args.quantize = "dgs", 0.1, "int8"
        args.secondary_density = 0.2
        args.lr = 0.1

    if args.role == "client":
        return run_client(args)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    return run_coordinator(args, spawn_clients=args.role == "auto")


if __name__ == "__main__":
    sys.exit(main())
