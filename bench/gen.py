"""Seeded inputs and weights, kept with the benchmark.

Copies of the generators the program's own scripts use (the MLP of
``benchmarks/common.py``, the blob classification task of
``data/synthetic.py``, the worker schedule of
``core/async_sim.make_schedule``), so that no change to the program can
change what the benchmark feeds it.  Everything on the device is made in
one jitted call per array group; nothing here is called inside a window.
"""
from __future__ import annotations

import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole ``seed`` below 2**64."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def np_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed, salt])


# ------------------------------------------------------------------ MLP

def mlp_dims(cfg: dict) -> list[int]:
    return [cfg["features"], *cfg["hidden"], cfg["classes"]]


def mlp_init(key, dims):
    """He-normal weights, zero biases: ``{"w<i>": (a, b), "b<i>": (b,)}``."""
    params = {}
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


def mlp_loss(params, batch):
    x, y = batch
    lp = jax.nn.log_softmax(mlp_apply(params, x).astype(jnp.float32))
    return -jnp.mean(lp[jnp.arange(x.shape[0]), y])


def mlp_params(seed: int, cfg: dict):
    """The cell's initial float32 weights, made on the device in one
    call."""
    dims = mlp_dims(cfg)
    return jax.jit(lambda k: mlp_init(k, dims))(
        jax.random.fold_in(root_key(seed), 1))


@partial(jax.jit, static_argnames=("shape", "chunk"))
def _blob_chunk(key, first, noise, *, shape, chunk):
    f, c, b = shape
    centers = jax.random.normal(jax.random.fold_in(key, 2), (c, f))

    def one(e):
        ky, kx = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, 3), e))
        y = jax.random.randint(ky, (b,), 0, c)
        x = centers[y] + noise * jax.random.normal(kx, (b, f))
        return x, y

    xs, ys = jax.vmap(one)(first + jnp.arange(chunk))
    return list(zip(list(xs), list(ys)))


def blob_events(seed: int, cfg: dict, n: int, chunk: int = 64) -> list:
    """Per-event ``(x, y)`` batches of events ``0 .. n-1`` of the blob task,
    as a list of device arrays: ``y`` uniform over the classes, ``x`` the
    class centre plus gaussian noise.  Event ``e``'s rows depend only on
    ``(seed, e)``.  Made ``chunk`` events per call of one compiled
    program, whatever ``n`` and the seed are."""
    shape = (cfg["features"], cfg["classes"], cfg["batch_per_worker"])
    key = root_key(seed)
    noise = jnp.float32(cfg["noise"])
    out = []
    for first in range(0, n, chunk):
        out.extend(_blob_chunk(key, jnp.int32(first), noise, shape=shape,
                               chunk=chunk))
    return out[:n]


# ------------------------------------------------------------- schedule

def make_schedule(n_workers: int, n_events: int, *, seed: int,
                  hetero: float) -> np.ndarray:
    """Event order from simulated worker speeds: exponential service
    times with per-worker rates drawn lognormal(0, ``hetero``); the next
    event is the worker that completes first (ties to the lowest id).
    A prefix of a longer schedule is the shorter one."""
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.exp(rng.normal(0.0, hetero, n_workers))
    t_next = rng.exponential(scale)
    heap = [(float(t_next[k]), k) for k in range(n_workers)]
    heapq.heapify(heap)
    order = np.empty(n_events, dtype=np.int32)
    for e in range(n_events):
        t, k = heapq.heappop(heap)
        order[e] = k
        heapq.heappush(heap, (t + rng.exponential(scale[k]), k))
    return order


def batch_sizes(schedule, max_batch: int) -> list[int]:
    """Sizes of the batches the event loop forms: maximal runs of
    pairwise-distinct workers, at most ``max_batch``, cut to a power of
    two."""
    sizes, i, n = [], 0, len(schedule)
    while i < n:
        seen, j = set(), i
        while j < min(n, i + max_batch) and schedule[j] not in seen:
            seen.add(schedule[j])
            j += 1
        size = 1 << ((j - i).bit_length() - 1)
        sizes.append(size)
        i += size
    return sizes
