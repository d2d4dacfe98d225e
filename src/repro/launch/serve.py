"""Serving launcher: live inference fleet fed by sparse model-diffs.

Three roles:

* ``--role fleet`` (default) — the serve subsystem end-to-end over real
  TCP (DESIGN.md §13): this process runs the training coordinator with
  the subscriber leg enabled; training clients AND inference replicas are
  separate OS processes.  Replicas SUBscribe, apply one coalesced
  re-sparsified ARENA diff per decode boundary (bounded staleness), and
  SYNC to the bit-exact final model at quiesce.  The coordinator also
  appends sparse delta-checkpoints of the live arena
  (checkpoint/delta.py).

      PYTHONPATH=src python -m repro.launch.serve --smoke \
          --ckpt-dir /tmp/ckpt --trace-dir /tmp/trace

  ``--smoke`` (the CI serve gate) asserts every replica's final params
  are bit-identical to the server model and that restoring the
  delta-checkpoint chain reproduces the live arena bit for bit.

* ``--role replica`` — one inference replica process: connects over TCP,
  decodes between diff pulls, writes its final arena to ``--out``.

* ``--role decode`` — the standalone mesh decode demo (prefill + decode
  loop on a host mesh; no cluster).

The fleet coordinator is the one process that may hold the chip: trainer
and replica children are spawned through ``launch/cluster.spawn`` with
``JAX_PLATFORMS=cpu``, standing for the devices on the far side of the
wire.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

from repro import telemetry

log = telemetry.get_logger("serve")


# ---------------------------------------------------------------------------
# --role replica: one TCP inference replica process
# ---------------------------------------------------------------------------

def run_replica(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cluster import wire
    from repro.cluster.replica import InferenceReplica
    from repro.cluster.transport import TcpClientTransport
    from repro.launch.cluster import _problem

    params0, _, _, _ = _problem(args)
    addr = wire.SUBSCRIBER_BASE + args.replica_id
    transport = TcpClientTransport(args.host, args.port, addr,
                                   connect_timeout=args.timeout)

    # the decode workload: batched classification forward on a fixed
    # eval set — enough to exercise decode-while-training; the arena
    # swap underneath it is what we're actually demonstrating
    from repro.data.synthetic import ClassificationTask
    task = ClassificationTask(n_features=args.features,
                              n_classes=args.classes,
                              batch_size=args.batch_size,
                              noise=0.6, seed=args.seed)
    x_eval, y_eval = task.eval_set(256)
    accs = []

    @jax.jit
    def logits_fn(p, x):
        return jax.nn.relu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def decode_fn(params, step):
        acc = float(jnp.mean(
            jnp.argmax(logits_fn(params, x_eval), -1) == y_eval))
        accs.append(acc)

    replica = InferenceReplica(
        transport, params0, replica_id=args.replica_id,
        max_staleness=args.max_staleness, decode_fn=decode_fn,
        recv_timeout=args.timeout)
    result = replica.run()
    transport.close()
    if args.out:
        np.save(args.out, result.arena)
    s = result.stats
    log.info(f"[replica {args.replica_id}] version={result.version} "
             f"decodes={s['decodes']} diffs={s['diffs']} "
             f"pulls={s['pulls']} bytes_in={s['bytes_in']} "
             f"stale_waits={s['stale_waits']} "
             f"acc {accs[0] if accs else 0:.3f} -> "
             f"{accs[-1] if accs else 0:.3f}")
    return 0


# ---------------------------------------------------------------------------
# --role fleet: coordinator + training clients + replica fleet over TCP
# ---------------------------------------------------------------------------

def run_fleet(args) -> int:
    import numpy as np

    from repro.cluster.coordinator import Coordinator
    from repro.cluster.transport import TcpCoordinatorTransport
    from repro.core.engine import CompressionSpec
    from repro.core.paramspace import ParamSpace
    from repro.launch import cluster as cluster_launch
    from repro.launch.cluster import _problem, _shared_flags

    params0, _, _, accuracy = _problem(args)
    recorder = (telemetry.Recorder(args.trace_dir)
                if args.trace_dir else telemetry.NULL)
    if recorder.enabled:
        telemetry.set_recorder(recorder)

    transport = TcpCoordinatorTransport(args.host, args.port)
    out_dir = pathlib.Path(args.out_dir)
    os.makedirs(out_dir, exist_ok=True)
    log.info(f"[fleet] coordinator on {transport.host}:{transport.port} "
             f"({args.clients} trainer(s) x {args.rounds} rounds, "
             f"{args.replicas} replica(s))")

    for c in range(args.clients):
        cluster_launch.spawn(
            [sys.executable, "-m", "repro.launch.cluster",
             "--role", "client", "--client-id", str(c),
             "--port", str(transport.port)] + _shared_flags(args))
    replica_outs = [out_dir / f"replica_{i}.npy"
                    for i in range(args.replicas)]
    for i in range(args.replicas):
        cluster_launch.spawn(
            [sys.executable, "-m", "repro.launch.serve",
             "--role", "replica", "--replica-id", str(i),
             "--port", str(transport.port),
             "--out", str(replica_outs[i]),
             "--max-staleness", str(args.max_staleness)]
            + _shared_flags(args))

    coord = Coordinator(
        transport=transport,
        params0=params0,
        n_slots=args.clients,
        secondary_density=args.secondary_density,
        secondary_spec=CompressionSpec(engine="exact",
                                       quantize=args.secondary_quantize),
        recv_timeout=args.timeout,
        recorder=recorder,
        push_density=args.push_density,
        min_subscribers=args.replicas,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )
    t0 = time.perf_counter()
    try:
        with recorder.span("fleet/serve"):
            final, hist = coord.serve()
        dt = time.perf_counter() - t0
    finally:
        cluster_launch.reap_children()
        transport.close()

    n = max(1, len(hist.losses))
    cnt = hist.metrics["counters"]
    log.info(f"[fleet] {len(hist.losses)} events in {dt:.1f}s | "
             f"loss {hist.losses[:3].mean():.4f} -> "
             f"{hist.losses[-3:].mean():.4f} | acc {accuracy(final):.3f}")
    for i in range(args.replicas):
        log.info(f"[fleet] replica {i}: pushes="
                 f"{cnt.get(f'sub/{i}/pushes', 0):.0f} "
                 f"push_bytes={cnt.get(f'sub/{i}/push_bytes', 0):.0f} "
                 f"lag_max={cnt.get(f'sub/{i}/lag_max', 0):.0f} "
                 f"version={cnt.get(f'sub/{i}/version', 0):.0f}")
    if args.ckpt_dir:
        log.info(f"[fleet] delta-checkpoint: "
                 f"{cnt.get('ckpt_deltas', 0):.0f} deltas, "
                 f"{cnt.get('ckpt_bytes', 0):.0f} bytes -> {args.ckpt_dir}")
    if recorder.enabled:
        telemetry.set_recorder(None)
        paths = recorder.close()
        log.info(f"[fleet] telemetry: {' '.join(paths)}")

    if args.smoke:
        space = ParamSpace.from_tree(params0)
        final_arena = np.asarray(space.pack(final))
        assert len(hist.losses) == args.clients * args.rounds, \
            "smoke: missing events"
        for i, path in enumerate(replica_outs):
            arena = np.load(path)
            assert np.array_equal(arena, final_arena), \
                f"smoke: replica {i} final != server model (bitwise)"
        if args.ckpt_dir:
            from repro.checkpoint import load_delta_checkpoint
            arena, version, _ = load_delta_checkpoint(args.ckpt_dir)
            assert np.array_equal(arena, final_arena), \
                "smoke: delta-checkpoint restore != live arena (bitwise)"
            assert version == len(hist.losses)
        log.info(f"[fleet] smoke OK: {args.replicas} replicas bit-identical"
                 f" to server"
                 + (", checkpoint restore bit-identical"
                    if args.ckpt_dir else ""))
    return 0


# ---------------------------------------------------------------------------
# --role decode: the standalone mesh decode demo
# ---------------------------------------------------------------------------

def run_decode(args) -> int:
    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.launch import mesh as mesh_lib
    from repro.models import decode_step, init_params, prefill

    cfg = get_arch(args.arch).reduced()
    n_dev = jax.device_count()
    mesh = mesh_lib.make_mesh((1, n_dev), ("data", "model"))
    print(f"[serve] arch={cfg.name} mesh={dict(mesh.shape)}")
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    max_len = args.prompt_len + args.gen
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    fe = None
    if cfg.frontend_tokens:
        fe = jax.random.normal(key, (args.batch, cfg.frontend_tokens,
                                     cfg.d_model), cfg.cdtype)

    @jax.jit
    def do_prefill(params, prompt):
        return prefill(params, prompt, cfg, frontend_embeds=fe,
                       max_len=max_len)

    @jax.jit
    def do_decode(params, caches, token, pos):
        return decode_step(params, caches, token, pos, cfg)

    with mesh:
        logits, caches, _ = do_prefill(params, prompt)
        tokens = [jnp.argmax(logits[:, -1], axis=-1)]
        for t in range(args.gen - 1):
            pos = jnp.int32(args.prompt_len + t)
            logits, caches = do_decode(params, caches, tokens[-1][:, None],
                                       pos)
            if args.temperature > 0:
                k2 = jax.random.fold_in(key, t)
                nxt = jax.random.categorical(
                    k2, logits[:, 0] / args.temperature, axis=-1)
            else:
                nxt = jnp.argmax(logits[:, 0], axis=-1)
            tokens.append(nxt)
    out = jnp.stack(tokens, axis=1)
    print("[serve] generated token ids:")
    for b in range(args.batch):
        print("  seq", b, out[b].tolist())
    print("[serve] done")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--role", choices=("fleet", "replica", "decode"),
                   default="fleet")
    p.add_argument("--smoke", action="store_true",
                   help="CI serve gate: tiny fleet run + bit-identity "
                        "asserts (replicas vs server, checkpoint restore "
                        "vs live arena)")
    # fleet / replica: cluster problem flags (shared with launch.cluster)
    p.add_argument("--clients", type=int, default=1)
    p.add_argument("--rounds", type=int, default=16)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--replica-id", type=int, default=0)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--strategy", default="dgs")
    p.add_argument("--density", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.7)
    p.add_argument("--quantize", default="none",
                   choices=("none", "bf16", "int8", "tern"))
    p.add_argument("--secondary-density", type=float, default=0.2)
    p.add_argument("--secondary-quantize", default="none",
                   choices=("none", "bf16", "int8", "tern"))
    p.add_argument("--push-density", type=float, default=0.25,
                   help="per-tensor top-k density of each replica push "
                        "(<= 0: ship the exact nonzero residual)")
    p.add_argument("--max-staleness", type=int, default=4,
                   help="decode boundaries an unanswered PULL may span "
                        "before the replica blocks for the diff")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--alpha", type=float, default=0.0)
    p.add_argument("--participation", type=float, default=1.0)
    p.add_argument("--features", type=int, default=32)
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--timeout", type=float, default=60.0)
    p.add_argument("--out", default=None,
                   help="replica role: write the final arena here (.npy)")
    p.add_argument("--out-dir", default=".serve_fleet",
                   help="fleet role: replica final-arena output directory")
    p.add_argument("--ckpt-dir", default=None,
                   help="append sparse delta-checkpoints of the live arena "
                        "under this directory (checkpoint/delta.py)")
    p.add_argument("--ckpt-every", type=int, default=4)
    p.add_argument("--trace-dir", default=None,
                   help="write trace.json + events.jsonl (flight recorder)")
    p.add_argument("--log-level", default=None)
    p.add_argument("--log-file", default=None)
    # decode role
    p.add_argument("--arch", default="chatglm3-6b")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--devices", type=int, default=4)
    p.add_argument("--temperature", type=float, default=0.0)
    args = p.parse_args(argv)
    if args.log_level:
        telemetry.set_level(args.log_level)
    if args.log_file:
        telemetry.set_log_file(args.log_file)
    if args.push_density is not None and args.push_density <= 0:
        args.push_density = None

    if args.smoke:
        args.clients, args.rounds, args.replicas = 1, 12, 2
        args.strategy, args.density = "dgs", 0.1
        args.secondary_density = 0.2

    if args.role == "replica":
        return run_replica(args)
    if args.role == "decode":
        return run_decode(args)
    from repro.compile_cache import enable_compile_cache
    from repro.launch.cluster import install_reaper
    enable_compile_cache()
    install_reaper()
    return run_fleet(args)


if __name__ == "__main__":
    sys.exit(main())
