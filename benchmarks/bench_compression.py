"""Compression ratio and kernel microbenchmarks.

* message bytes vs density (the dual-way compression ratio table)
* us/call for the Pallas kernels (interpret mode — correctness-path timing,
  NOT TPU performance) vs their jnp references.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_strategy
from repro.core.sparsify import dense_bytes, message_bytes
from repro.kernels import ops, ref, resolve_interpret

from .common import csv_row, mlp_init


def _time(fn, *args, n=5):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def run(quick: bool = False):
    rows = []
    params = mlp_init(jax.random.PRNGKey(0), 256, 10, hidden=(512, 512))
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1), p.shape), params)
    dense = dense_bytes(params)
    for density in (0.1, 0.01, 0.001):
        s = make_strategy("dgs", density=density)
        st = s.init(params)
        _, msg = s.step(st, grads, lr=0.1)
        b = message_bytes(msg)
        rows.append(csv_row(
            f"compression/density_{density}", 0.0,
            f"msg_bytes={b};dense_bytes={dense};ratio={dense/b:.0f}x"))
    # kernel microbench (Pallas interpret mode off the TPU)
    kind = "interp" if resolve_interpret(None) else "pallas"
    n = 1 << 16 if quick else 1 << 20
    u = jax.random.normal(jax.random.PRNGKey(0), (n,))
    g = jax.random.normal(jax.random.PRNGKey(1), (n,))
    thr = jnp.float32(1.0)
    t_kern = _time(lambda: ops.samomentum_fused(u, g, thr, momentum=0.7,
                                                lr=0.1))
    ref_jit = jax.jit(lambda u, g: ref.samomentum_ref(u, g, thr,
                                                      momentum=0.7, lr=0.1))
    t_ref = _time(lambda: ref_jit(u, g))
    rows.append(csv_row(f"kernel/samomentum_{kind}", t_kern,
                        f"ref_us={t_ref:.1f};n={n}"))
    k = max(1, n // 100)
    t_hier = _time(lambda: ops.hierarchical_topk(u, k=k, r=32))
    topk_jit = jax.jit(lambda x: jax.lax.top_k(jnp.abs(x), k))
    t_topk = _time(lambda: topk_jit(u))
    rows.append(csv_row(f"kernel/block_topk_{kind}", t_hier,
                        f"lax_topk_us={t_topk:.1f};k={k}"))
    rows.extend(run_engines(quick=quick))
    rows.extend(run_quantization(quick=quick))
    return rows


def run_engines(quick: bool = False):
    """Engine-vs-engine SAMomentum step timing through core/engine.py.

    One full accumulate -> select -> rescale step per engine on the same
    tensor (interpret-mode Pallas for blockwise on CPU — correctness-path
    timing, NOT TPU performance; blockwise runs oversampled r=32 as in
    production).
    """
    from repro.core.engine import CompressionSpec, samomentum_step

    rows = []
    n = 1 << 14 if quick else 1 << 18
    k = max(1, n // 100)
    u = jax.random.normal(jax.random.PRNGKey(2), (n,))
    g = jax.random.normal(jax.random.PRNGKey(3), (n,))
    # sample_size must be << n or the sampled row degenerates into an
    # exact full-tensor threshold (quick n is below the 65536 default)
    for spec in (CompressionSpec(engine="exact"),
                 CompressionSpec(engine="sampled", sample_size=max(64, n // 16)),
                 CompressionSpec(engine="blockwise", block_r=32)):
        step = jax.jit(lambda u, g, _s=spec: samomentum_step(
            u, g, momentum=0.7, lr=0.1, k=k, spec=_s))
        t = _time(lambda: step(u, g))
        rows.append(csv_row(f"engine/{spec.engine}", t, f"n={n};k={k}"))
    return rows


def run_quantization(quick: bool = False):
    """DGS + wire quantization (the paper's TernGrad future-work combo)."""
    import numpy as np

    from repro.core import async_sim, make_strategy

    from .common import make_classification_problem, run_strategy
    rows = []
    params0, grad_fn, batch_fn, accuracy = make_classification_problem(
        seed=0, noise=0.8)
    n_events = 200 if quick else 1000
    for q in ("none", "bf16", "int8", "tern"):
        strat = make_strategy("dgs", density=0.05, momentum=0.7, quantize=q)
        tr = async_sim.AsyncTrainer(strat, grad_fn, 4, lr=0.08)
        sched = async_sim.make_schedule(4, n_events, seed=5, hetero=0.6)
        final, _, hist = tr.run(params0, sched,
                                lambda e, k: batch_fn(e, int(k)))
        rows.append(csv_row(
            f"quantize/dgs_{q}", 0.0,
            f"acc={accuracy(final):.4f};up_bytes={hist.up_bytes}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run(quick=True)))
