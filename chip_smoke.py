#!/usr/bin/env python3
"""Bring-up smoke test: DGS training runs on a TPU through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the paths that exist only across chips

One chip, in order, all in this one process (a chip belongs to one
process, so nothing here spawns a JAX child):

* step 0 — ``jax.devices()[0].platform`` must be ``"tpu"``; anything else
  (``JAX_PLATFORMS=cpu``, no chip) exits non-zero and prints no result.
* Phase A — ``AsyncTrainer.run_batched`` at the paper's 32 workers over the
  10.5M-parameter MLP of ``benchmarks/bench_scalability.run_big``: DGS,
  density 0.001, SAMomentum 0.7, int8 wire, secondary density 0.001, a
  seeded heterogeneous schedule, ``max_batch=16``.  Once with the ``auto``
  selection engine and once with ``blockwise`` (the Pallas top-k, fused
  SAMomentum and scatter kernels); the server receive, commit and apply
  run the Pallas scatter either way.  Losses must be finite and fall, and
  the wire bytes must equal the static frame sizes.
* Phase B — ``launch/train.py``'s mesh step for mamba2-780m at full width
  (48 layers, d_model 1536, vocab 50,280) on a 1x1 mesh: batch 4 x seq
  1024, ``allgather``, density 0.01, rematerialized, 5 steps.
* Kernel checks — each Pallas kernel the phases ran, against its XLA / jnp
  reference at the phases' shapes.

``--chips 4`` runs only (a) the mamba2-780m step on the launcher's
(data=2, model=2) mesh in ``allgather`` and ``shardedps`` against
``dense``, and (b) the ``mesh_shards=4`` server with its all_to_all on the
mesh against the flat single server.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase or check is printed and the next one still runs; the
process then exits non-zero without that line.
The stage programs of each phase are compiled side by side in threads
before the phases run, and JAX's persistent compile cache is on
(``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

N_WORKERS = 32
MLP_HIDDEN = (2048, 2304, 2048)       # run_big's 10.5M-parameter MLP
MLP_FEATURES, MLP_CLASSES = 512, 10
DENSITY = 0.001
# blockwise candidates per 1024-element block: the oversampled production
# mode (as in benchmarks/bench_compression.py).  The exact mode (r = k,
# capped at the block) sorts every block in full — O(n * 1024) sweeps per
# tensor — and did not finish 256 events in 20 minutes on one v5e.
BLOCK_R = 32
EVENTS = 256          # Phase A schedule length
# the run fails, naming the phase it was in, after this many seconds:
# inside the 1200 s a chip run is given
DEADLINE_S = 1100.0


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


FAILED: list[str] = []
RUNNING = ["start"]


def watchdog(seconds: float) -> None:
    """Exit non-zero, naming the phase, once ``seconds`` have passed: a
    phase that would overrun the run's time limit fails with a reason
    instead of being killed from outside."""
    def fire():
        print(f"chip_smoke: deadline of {seconds:.0f} s passed during "
              f"{RUNNING[0]}", file=sys.stderr, flush=True)
        os._exit(3)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()


def attempt(name: str, fn, *args):
    """Run one phase.  A failure is printed and counted, and the next
    phase still runs; the process then exits non-zero at the end."""
    RUNNING[0] = name
    try:
        return fn(*args)
    except Exception as e:  # noqa: BLE001
        FAILED.append(name)
        emit(phase=name, ok=False, error=f"{type(e).__name__}: {e}"[:4000])
        traceback.print_exc()
        return None


def compile_all(jobs, threads: int):
    """``[(name, thunk)]`` -> ``{name: result or the exception}``,
    compiled side by side."""
    def run(fn):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            return e

    with ThreadPoolExecutor(threads) as pool:
        futs = {name: pool.submit(run, fn) for name, fn in jobs}
        return {name: f.result() for name, f in futs.items()}


def compiled_or_raise(compiled, name):
    out = compiled[name]
    if isinstance(out, Exception):
        raise out
    return out


# ---------------------------------------------------------------- Phase A

def mlp_problem():
    import jax
    import jax.numpy as jnp

    from benchmarks.common import mlp_apply, mlp_init
    from repro.data.synthetic import ClassificationTask

    params0 = mlp_init(jax.random.PRNGKey(0), MLP_FEATURES, MLP_CLASSES,
                       hidden=MLP_HIDDEN)
    task = ClassificationTask(n_features=MLP_FEATURES,
                              n_classes=MLP_CLASSES, batch_size=8, seed=0,
                              noise=1.0)

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            lp = jax.nn.log_softmax(mlp_apply(p, x))
            return -jnp.mean(lp[jnp.arange(x.shape[0]), y])

        return jax.value_and_grad(loss)(params)

    return params0, grad_fn, lambda e, k: task.batch(e, worker=k)


def phase_a_trainer(engine: str, grad_fn):
    from repro.core import async_sim, make_strategy
    from repro.core.engine import CompressionSpec

    strat = make_strategy("dgs", density=DENSITY, momentum=0.7,
                          quantize="int8", engine=engine,
                          block_r=BLOCK_R if engine == "blockwise" else None)
    # lr 0.01: run_big's 0.05 diverges at this width under 32-way
    # staleness (a loss spike past 200 within 256 events on the CPU)
    return async_sim.AsyncTrainer(
        strat, grad_fn, N_WORKERS, lr=0.01, secondary_density=DENSITY,
        secondary_spec=CompressionSpec(engine="auto"))


def run_phase_a(tr, engine, params0, batch_fn, sched, compile_s):
    import numpy as np

    from repro.cluster import wire
    from repro.core.paramspace import ParamSpace

    t0 = time.perf_counter()
    _, sstate, hist = tr.run_batched(params0, sched, batch_fn, max_batch=16)
    sstate.M.block_until_ready()
    run_s = time.perf_counter() - t0
    space = ParamSpace.from_tree(params0)
    n = len(sched)
    losses = hist.losses
    q = max(1, n // 4)
    first, last = float(np.mean(losses[:q])), float(np.mean(losses[-q:]))
    up = n * wire.frame_bytes_static(tr.strategy.message_seg(space),
                                     space.total, "int8")
    down = n * wire.frame_bytes_static(space.ks(DENSITY), space.total,
                                       "none")
    emit(phase=f"A/{engine}", seconds=round(compile_s + run_s, 3),
         compile_s=round(compile_s, 3), run_s=round(run_s, 3),
         workers=N_WORKERS, params=space.total, events=n,
         loss_first_quarter=first, loss_last_quarter=last,
         losses_head=losses[:4].tolist(), losses_tail=losses[-4:].tolist(),
         up_bytes=hist.up_bytes, down_bytes=hist.down_bytes,
         peak_bytes_in_use=peak_bytes())
    check(np.all(np.isfinite(losses)), f"A/{engine}: non-finite loss")
    check(last < first, f"A/{engine}: loss did not fall ({first} -> {last})")
    check((hist.up_bytes, hist.down_bytes) == (up, down),
          f"A/{engine}: bytes {hist.up_bytes, hist.down_bytes} != static "
          f"frame sizes {up, down}")


# ---------------------------------------------------------------- Phase B

def train_args(mode: str, steps: int = 5):
    from repro.launch import train

    return train.parse_args([
        "--arch", "mamba2-780m", "--no-smoke", "--steps", str(steps),
        "--batch", "4", "--seq", "1024", "--mode", mode,
        "--density", "0.01"])


def compile_train(built):
    _, mesh, bundle = built
    with mesh:
        return bundle.jit().lower(*bundle.arg_specs).compile()


def run_train(name, args, built, step, compile_s):
    import numpy as np

    from repro.launch import train

    cfg, mesh, _ = built
    t0 = time.perf_counter()
    losses = train.train(args, built=built, step=step)
    run_s = time.perf_counter() - t0
    emit(phase=name, seconds=round(compile_s + run_s, 3),
         compile_s=round(compile_s, 3), run_s=round(run_s, 3),
         arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
         vocab=cfg.vocab_size, mesh=dict(mesh.shape), mode=args.mode,
         losses=losses, peak_bytes_in_use=peak_bytes())
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    return losses


# ---------------------------------------------------------- kernel checks

def kernel_checks(space, plan_r):
    """``(name, make_args, kernel, reference, verify)`` at Phase A's
    shapes: the 10.5M arena with its k, 16 commit lanes, the largest
    tensor for the selection kernels.  Inputs are made only when a check
    runs, so they hold no device memory during the phases."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref, wire_pack

    n = space.total
    ks = space.ks(DENSITY)
    k = sum(ks)
    big = max(space.sizes)                     # the largest tensor
    k_big = ks[space.sizes.index(big)]
    m = 0.7
    rng = np.random.default_rng(0)
    uniq = rng.choice(n, k, replace=False).astype(np.int32)
    lanes = np.stack([rng.choice(n, k, replace=False)
                      for _ in range(16)]).astype(np.int32)
    key = jax.random.PRNGKey(0)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    def first_diff(u, w, same):
        """The first few mismatching positions of one output, with the
        kernel's and the reference's values there."""
        bad = np.argwhere(~same)[:5]
        return {"shape": list(u.shape), "n_differ": int((~same).sum()),
                "at": bad.tolist(),
                "kernel": [u[tuple(i)].item() for i in bad],
                "reference": [w[tuple(i)].item() for i in bad]}

    def bits_equal(a, b, args):
        for j, (u, w) in enumerate(zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b))):
            u, w = np.asarray(u), np.asarray(w)
            if u.shape != w.shape:
                return False, {"output": j, "shapes": [u.shape, w.shape]}
            same = (u == w) | (np.isnan(u) & np.isnan(w)
                               if u.dtype.kind == "f" else False)
            if not same.all():
                return False, {"output": j, **first_diff(u, w, same)}
        return True, None

    def topk_equal(a, b, args):   # the kernel pads to whole block groups
        return bits_equal([u[:b[0].shape[0]] for u in a], b, args)

    def samo_args():
        x = normal(4, (big,))
        return x, jnp.sort(jnp.abs(x))[-k_big]

    def samo_close(a, b, args):
        # elements at the threshold may flip with the FMA order: compare
        # the rest, as the kernel tests do
        x, thr = (np.asarray(t) for t in args)
        inner = np.abs(np.abs(x) - thr) > 1e-6 * thr
        for j, (u, w) in enumerate(zip(a, b)):
            u, w = np.asarray(u), np.asarray(w)
            same = ~inner | np.isclose(u, w, rtol=1e-6, atol=1e-7)
            if not same.all():
                return False, {"output": j, **first_diff(u, w, same)}
        return True, None

    def scatter(d, i, v):
        return ops.scatter_apply(d, i, v, interpret=False)

    def xla_scatter(d, i, v):
        return d.at[i].add(v)

    checks = [
        ("scatter_apply/unique",
         lambda: (normal(0, (n,)), jnp.asarray(uniq), normal(1, (k,))),
         scatter, xla_scatter, bits_equal),
        # k entries packed into 4 blocks: most spill past SCATTER_CAP
        ("scatter_apply/spill",
         lambda: (normal(0, (n,)), jnp.arange(k, dtype=jnp.int32) * 3,
                  normal(1, (k,))),
         scatter, xla_scatter, bits_equal),
        ("scatter_apply_rows/16",
         lambda: (normal(2, (16, n)), jnp.asarray(lanes),
                  normal(3, (16, k))),
         lambda d, i, v: ops.scatter_apply_rows(d, i, v, interpret=False),
         lambda d, i, v: d.at[jnp.arange(16)[:, None], i].add(v),
         bits_equal),
        (f"block_topk/r{plan_r}", lambda: (normal(4, (big,)),),
         lambda x: ops.block_topk_candidates(x, r=plan_r, interpret=False),
         lambda x: ref.block_topk_ref(x, block=1024, r=plan_r), topk_equal),
        ("samomentum_fused", samo_args,
         lambda x, t: ops.samomentum_fused(x, x, t, momentum=m, lr=1.0 - m,
                                           interpret=False),
         lambda x, t: ref.samomentum_ref(x, x, t, momentum=m,
                                         lr=1.0 - m)[:2],
         samo_close),
    ]
    # only tern has a kernel (the packer); bf16 and int8 are XLA ops
    seg = tuple(int(s) for s in ks)
    checks.append((
        "quantize_pack/tern", lambda: (normal(5, (k,)),),
        lambda v: wire_pack.quantize_pack(v, mode="tern", seg=seg,
                                          pallas=True),
        lambda v: wire_pack.quantize_pack(v, mode="tern", seg=seg,
                                          pallas=False),
        bits_equal))
    return checks


def compile_check(make_args, kernel, reference):
    import jax

    shapes = jax.eval_shape(make_args)
    return (jax.jit(kernel).lower(*shapes).compile(),
            jax.jit(reference).lower(*shapes).compile())


def best_time(fn, args, reps: int = 3) -> float:
    """Best of ``reps`` host-clock seconds for one call that is already
    compiled and warm, waiting for its outputs."""
    import jax

    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def one_kernel_check(name, make_args, verify, compiled):
    kern, refc = compiled_or_raise(compiled, name)
    check("tpu_custom_call" in kern.as_text(),
          f"{name}: no Pallas kernel in the compiled program")
    t0 = time.perf_counter()
    args = make_args()
    ok, diff = verify(kern(*args), refc(*args), args)
    # the kernel's and its XLA reference's time at this shape, so a chip
    # run shows which of the two paths is faster
    emit(check=name, ok=bool(ok),
         seconds=round(time.perf_counter() - t0, 3),
         kernel_s=best_time(kern, args), reference_s=best_time(refc, args),
         first_diff=diff)
    return bool(ok)


def run_kernel_checks(checks, compiled):
    """Every check runs and prints its line; a failure is raised only
    after the last one, naming all that failed."""
    failed = []
    for name, make_args, _, _, verify in checks:
        try:
            if not one_kernel_check(name, make_args, verify, compiled):
                failed.append(name)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            emit(check=name, ok=False,
                 error=f"{type(e).__name__}: {e}"[:4000])
    check(not failed, f"kernel checks failed: {', '.join(failed)}")


# ------------------------------------------------------------- four chips

def four_chip_mesh_step(threads):
    """(a): mamba2-780m on (data=2, model=2), allgather / shardedps vs
    dense on the same batches."""
    from repro.launch import train

    modes = ("dense", "allgather", "shardedps")
    args = {m: train_args(m) for m in modes}
    built = {m: train.build(args[m]) for m in modes}
    t0 = time.perf_counter()
    steps = compile_all([(m, lambda m=m: compile_train(built[m]))
                         for m in modes], threads)
    compile_s = time.perf_counter() - t0
    losses = {m: run_train(f"4chip/a/{m}", args[m], built[m],
                           compiled_or_raise(steps, m), compile_s)
              for m in modes}
    for m in ("allgather", "shardedps"):
        check(dict(built[m][1].shape) == {"data": 2, "model": 2},
              f"mesh {dict(built[m][1].shape)}")
        # same params and batch at step 0: the losses differ only by the
        # reduction order, i.e. within one bf16 rounding step
        check(math.isclose(losses[m][0], losses["dense"][0],
                           rel_tol=2.0 ** -7),
              f"{m} step-0 loss {losses[m][0]} vs dense "
              f"{losses['dense'][0]}")
    emit(phase="4chip/a", step0={m: losses[m][0] for m in modes},
         ok=True)


def four_chip_mesh_shards():
    """(b): the mesh_shards=4 server, all_to_all on the mesh, against the
    flat single server on the same schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import mlp_apply, mlp_init
    from repro.cluster.runner import run_inprocess
    from repro.core import async_sim, make_strategy
    from repro.core import server as ps
    from repro.core.engine import CompressionSpec
    from repro.core.paramspace import ParamSpace
    from repro.core.sparsify import SparseLeaf
    from repro.data.synthetic import ClassificationTask

    S, W = 4, 8
    # small on purpose: what is checked is the collective, the placement
    # and bit-parity; each in-process client thread compiles its own step
    params0 = mlp_init(jax.random.PRNGKey(0), 64, 10, hidden=(128, 128))
    task = ClassificationTask(n_features=64, n_classes=10, batch_size=8,
                              seed=0, noise=1.0)

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            lp = jax.nn.log_softmax(mlp_apply(p, x))
            return -jnp.mean(lp[jnp.arange(x.shape[0]), y])

        return jax.value_and_grad(loss)(params)

    strat = make_strategy("dgs", density=0.01, momentum=0.7,
                          quantize="int8")
    sched = async_sim.make_schedule(W, 64, seed=7, hetero=0.8)
    kw = dict(schedule=sched, lr=0.05, secondary_density=0.01,
              secondary_spec=CompressionSpec(engine="exact"))
    bf = lambda e, k: task.batch(e, worker=k)  # noqa: E731
    t0 = time.perf_counter()
    f1, h1 = run_inprocess(strat, grad_fn, params0, bf, **kw)
    fM, hM = run_inprocess(strat, grad_fn, params0, bf, mesh_shards=S, **kw)
    run_s = time.perf_counter() - t0
    check(np.array_equal(h1.losses, hM.losses), "mesh losses differ")
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(fM))),
          "mesh final params differ")
    check((h1.up_bytes, h1.down_bytes) == (hM.up_bytes, hM.down_bytes),
          "mesh wire bytes differ")
    check(hM.metrics["counters"]["route_overflow"] == 0, "route overflow")

    # one mesh server stage on its own: the collective is in the compiled
    # program, and every shard's arena row stays on its own device
    space = ParamSpace.from_tree(params0)
    st = ps.init_mesh_shards(params0, W, S)
    k = sum(space.ks(0.01))
    idx = jnp.asarray(np.random.default_rng(0).choice(
        space.total, (2, k)).astype(np.int32))
    msgs = SparseLeaf(values=jnp.ones((2, k)), indices=idx,
                      size=space.total)
    stage = async_sim.make_mesh_batched_server_step(
        0.01, CompressionSpec(engine="exact"))
    ids = jnp.asarray([0, 1], jnp.int32)
    hlo = stage.lower(st, msgs, ids).compile().as_text()
    st, _, _ = stage(st, msgs, ids)
    placed = {name: sorted((s.device.id, s.data.shape)
                           for s in arr.addressable_shards)
              for name, arr in (("M", st.M), ("v", st.v))}
    emit(phase="4chip/b", seconds=round(run_s, 3), events=len(sched),
         losses_head=h1.losses[:3].tolist(), up_bytes=hM.up_bytes,
         down_bytes=hM.down_bytes, all_to_all="all-to-all" in hlo,
         placement={n: [[d, list(sh)] for d, sh in p]
                    for n, p in placed.items()}, ok=True)
    check("all-to-all" in hlo, "no all-to-all in the mesh server stage")
    for name, p in placed.items():
        check(len({d for d, _ in p}) == S,
              f"{name} is not spread over {S} devices: {p}")
        check(all(sh[1 if name == "v" else 0] == 1 for _, sh in p),
              f"{name} shards hold more than one arena row: {p}")


# ------------------------------------------------------------------- main

def one_chip(threads):
    from repro.core.engine import BlockwiseEngine
    from repro.core.paramspace import ParamSpace
    from repro.core import async_sim
    from repro.launch import train

    params0, grad_fn, batch_fn = mlp_problem()
    space = ParamSpace.from_tree(params0)
    sched = async_sim.make_schedule(N_WORKERS, EVENTS, seed=7,
                                    hetero=0.8)
    trainers = {e: phase_a_trainer(e, grad_fn)
                for e in ("auto", "blockwise")}
    b_args = train_args("allgather")
    b_built = train.build(b_args)
    big = max(space.sizes)
    plan_r = BlockwiseEngine(block_r=BLOCK_R)._plan(
        big, space.ks(DENSITY)[space.sizes.index(big)])
    checks = kernel_checks(space, plan_r)

    jobs = [("B", lambda: compile_train(b_built))]
    for e, tr in trainers.items():
        for i, (fn, a) in enumerate(tr.batched_programs(
                params0, sched, batch_fn, max_batch=16)):
            jobs.append((f"A/{e}/{i}",
                         lambda fn=fn, a=a: fn.lower(*a).compile()))
    jobs += [(c[0], lambda c=c: compile_check(*c[1:4])) for c in checks]
    RUNNING[0] = "compile"
    t0 = time.perf_counter()
    compiled = compile_all(jobs, threads)
    compile_s = time.perf_counter() - t0
    refused = {name: f"{type(out).__name__}: {out}"[:2000]
               for name, out in compiled.items()
               if isinstance(out, Exception)}
    emit(phase="compile", seconds=round(compile_s, 3), programs=len(jobs),
         threads=threads, refused=refused)

    for e, tr in trainers.items():
        attempt(f"A/{e}", run_phase_a, tr, e, params0, batch_fn, sched,
                compile_s)
    del trainers
    attempt("B", lambda: run_train(
        "B", b_args, b_built, compiled_or_raise(compiled, "B"), compile_s))
    del b_built
    attempt("kernel checks", run_kernel_checks, checks, compiled)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    watchdog(DEADLINE_S)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {devices[0].platform} "
              f"devices ({devices[0].device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    threads = max(1, min(12, (os.cpu_count() or 2) - 1))
    emit(phase="device", platform=devices[0].platform,
         kind=devices[0].device_kind, count=len(devices), cache=cache)
    if args.chips == 4:
        attempt("4chip/a", four_chip_mesh_step, threads)
        attempt("4chip/b", four_chip_mesh_shards)
    else:
        attempt("setup", one_chip, threads)
    if FAILED:
        print(f"chip_smoke: failed: {', '.join(FAILED)}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
