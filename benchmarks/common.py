"""Shared benchmark plumbing: the paper's model/task stand-ins and the
async-cluster runner wiring.

The paper trains ResNet-18/CIFAR-10 and a 5-layer LSTM/AN4 on a 32-GPU PS
cluster.  At CPU/benchmark scale we substitute: a conv-ish MLP on a
gaussian-blobs classification task (same optimization phenomenology:
momentum matters, staleness hurts) and a 2-layer LSTM on a delayed-copy
task.  Strategy implementations are the real ones from repro.core.
"""
from __future__ import annotations

import json
import pathlib
import platform
import socket
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import async_sim, make_strategy
from repro.data.synthetic import ClassificationTask, SequenceCopyTask

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------- perf artifacts

# bench name -> list of measurement records; benches append via
# record_perf and run.py / --smoke entries flush to BENCH_<name>.json
_PERF: dict[str, list[dict]] = {}

# BENCH_*.json schema: 2 adds schema_version + env provenance (hostname,
# platform, python/jax versions, backend) and optional per-row histograms
SCHEMA_VERSION = 2


def bench_environment() -> dict:
    """Where the numbers came from — enough to judge row comparability."""
    return {
        "hostname": socket.gethostname(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
    }


def git_commit() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=REPO_ROOT, text=True, stderr=subprocess.DEVNULL).strip()
    except Exception:
        return "unknown"


def record_perf(bench: str, name: str, *, config: dict,
                events_per_sec: float, nbytes: int,
                wall_clock_s: float, hists: dict | None = None,
                extras: dict | None = None) -> None:
    """Book one measurement for the ``BENCH_<bench>.json`` artifact.

    ``config`` is the measurement's full parameterization (model size,
    workers, events, strategy...) so a row is reproducible from the
    artifact alone.  ``hists`` attaches flight-recorder histograms
    (e.g. ``telemetry.metrics.summarize_log2`` of per-event staleness);
    ``extras`` merges arbitrary scalar context into the row.
    """
    row = {
        "name": name,
        "config": config,
        "events_per_sec": round(float(events_per_sec), 3),
        "bytes": int(nbytes),
        "wall_clock_s": round(float(wall_clock_s), 6),
    }
    if extras:
        row.update(extras)
    if hists:
        row["hists"] = hists
    _PERF.setdefault(bench, []).append(row)


def write_bench_artifacts(root: pathlib.Path | None = None) -> list[str]:
    """Flush every recorded bench to ``BENCH_<name>.json`` at the repo
    root (schema v2: commit + environment + measurement rows); returns
    the paths written."""
    root = pathlib.Path(root) if root is not None else REPO_ROOT
    commit = git_commit()
    env = bench_environment()
    written = []
    for bench, rows in sorted(_PERF.items()):
        path = root / f"BENCH_{bench}.json"
        path.write_text(json.dumps(
            {"schema_version": SCHEMA_VERSION, "commit": commit,
             "environment": env, "bench": bench, "rows": rows}, indent=2)
            + "\n")
        written.append(str(path))
    return written


# --------------------------------------------------------------- MLP model

def mlp_init(key, n_features, n_classes, hidden=(64, 64)):
    params = {}
    dims = [n_features, *hidden, n_classes]
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(k, (a, b)) * (2.0 / a) ** 0.5
        params[f"b{i}"] = jnp.zeros((b,))
    return params


def mlp_apply(params, x):
    n = len([k for k in params if k.startswith("w")])
    h = x
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def make_classification_problem(seed=0, n_features=64, n_classes=10,
                                batch_size=32, noise=0.6):
    task = ClassificationTask(n_features=n_features, n_classes=n_classes,
                              batch_size=batch_size, seed=seed, noise=noise)

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            logits = mlp_apply(p, x)
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(lp[jnp.arange(x.shape[0]), y])

        return jax.value_and_grad(loss)(params)

    def batch_fn(e, k):
        return task.batch(e, worker=k)

    def accuracy(params):
        x, y = task.eval_set(1024)
        return float(jnp.mean(jnp.argmax(mlp_apply(params, x), -1) == y))

    params0 = mlp_init(jax.random.PRNGKey(seed), n_features, n_classes)
    return params0, grad_fn, batch_fn, accuracy


# -------------------------------------------------------------- LSTM model

def lstm_init(key, vocab, hidden, n_layers=2):
    params = {"embed": jax.random.normal(key, (vocab, hidden)) * 0.1}
    for l in range(n_layers):
        key, k1, k2 = jax.random.split(key, 3)
        params[f"l{l}_wx"] = jax.random.normal(
            k1, (hidden, 4 * hidden)) * (1.0 / hidden) ** 0.5
        params[f"l{l}_wh"] = jax.random.normal(
            k2, (hidden, 4 * hidden)) * (1.0 / hidden) ** 0.5
        params[f"l{l}_b"] = jnp.zeros((4 * hidden,))
    key, k = jax.random.split(key)
    params["head"] = jax.random.normal(k, (hidden, vocab)) * 0.1
    return params


def lstm_apply(params, tokens):
    n_layers = len([k for k in params if k.endswith("_wx")])
    h = params["embed"][tokens]                      # (B, S, H)
    B, S, H = h.shape
    for l in range(n_layers):
        wx, wh, b = (params[f"l{l}_wx"], params[f"l{l}_wh"],
                     params[f"l{l}_b"])

        def cell(carry, x_t):
            hp, cp = carry
            z = x_t @ wx + hp @ wh + b
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * cp + jax.nn.sigmoid(i) * jnp.tanh(g)
            hn = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (hn, c), hn

        init = (jnp.zeros((B, H)), jnp.zeros((B, H)))
        _, hs = jax.lax.scan(cell, init, jnp.moveaxis(h, 1, 0))
        h = jnp.moveaxis(hs, 0, 1)
    return h @ params["head"]


def make_copy_problem(seed=0, vocab=32, hidden=64, copy_len=6, delay=6,
                      batch_size=16):
    task = SequenceCopyTask(vocab_size=vocab, copy_len=copy_len, delay=delay,
                            batch_size=batch_size, seed=seed)

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            logits = lstm_apply(p, x)
            lp = jax.nn.log_softmax(logits)
            mask = y >= 0
            tgt = jnp.where(mask, y, 0)
            nll = -jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
            return jnp.sum(nll * mask) / jnp.sum(mask)

        return jax.value_and_grad(loss)(params)

    def batch_fn(e, k):
        return task.batch(e, worker=k)

    def error_rate(params):
        """Symbol error rate on the copy positions (the WER stand-in)."""
        x, y = task.batch(999983)
        pred = jnp.argmax(lstm_apply(params, x), -1)
        mask = y >= 0
        wrong = jnp.sum((pred != y) & mask)
        return float(wrong / jnp.sum(mask))

    params0 = lstm_init(jax.random.PRNGKey(seed), vocab, hidden)
    return params0, grad_fn, batch_fn, error_rate


# ---------------------------------------------------------------- running

def run_strategy(name, params0, grad_fn, batch_fn, *, n_workers, n_events,
                 lr, density=0.01, momentum=0.7, seed=0, hetero=0.8,
                 lr_fn=None, secondary_density=None, quantize="none"):
    """Run one strategy on the async cluster; returns (final, hist, dt)."""
    if name == "msgd":
        batches = [batch_fn(e, 0) for e in range(n_events)]
        t0 = time.perf_counter()
        final, losses = async_sim.run_msgd(params0, grad_fn, batches, lr=lr,
                                           momentum=momentum, lr_fn=lr_fn)
        dt = time.perf_counter() - t0
        hist = async_sim.History(losses=losses,
                                 worker_ids=np.zeros(n_events, np.int32),
                                 staleness=np.zeros(n_events, np.int64),
                                 up_bytes=0, down_bytes=0, evals=[])
        return final, hist, dt
    kw = {}
    if name != "asgd":
        kw["density"] = density
        kw["quantize"] = quantize
    if name in ("dgc_async", "dgs"):
        kw["momentum"] = momentum
    strat = make_strategy(name, **kw)
    tr = async_sim.AsyncTrainer(strat, grad_fn, n_workers, lr=lr,
                                secondary_density=secondary_density)
    sched = async_sim.make_schedule(n_workers, n_events, seed=seed,
                                    hetero=hetero)
    t0 = time.perf_counter()
    final, _, hist = tr.run(params0, sched, batch_fn, lr_fn=lr_fn)
    dt = time.perf_counter() - t0
    return final, hist, dt


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
