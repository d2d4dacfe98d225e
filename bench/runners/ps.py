"""Asynchronous parameter-server cells: ``AsyncTrainer.run_batched``.

One cell is one fleet (the configuration) under one exchange (the mix).
Set-up builds one trainer, compiles or loads its stage programs for every
batch size the event loop can form, sizes the window from warm runs, and
drives the window's own call over one batch of each size the window's
schedule forms (the compared run).  The window is one ``run_batched``
call over that schedule, timed to ``block_until_ready`` of the final
server state.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.reference import ps as ref_ps

# schedule prefix that the set-up's runs draw from
PROBE_EVENTS = 4096


def make_strategy(mix: dict):
    from repro.core import make_strategy as make

    if mix["strategy"] == "asgd":
        return make("asgd")
    return make(mix["strategy"], density=mix["density"],
                momentum=mix["momentum"], quantize=mix["quantize"],
                engine=mix["engine"])


def schedule(cfg: dict, mix: dict, seed: int, n: int) -> np.ndarray:
    """The fleet's event order: one schedule per mix, drawn from its
    ``schedule_seed``, with the workers relabelled by a permutation drawn
    from ``seed``.  Every seed then has the same batch sizes, staleness
    and arrivals, on other workers' data."""
    base = gen.make_schedule(cfg["n_workers"], n, seed=mix["schedule_seed"],
                             hetero=mix["hetero"])
    perm = gen.np_rng(seed, 7).permutation(cfg["n_workers"])
    return perm[base].astype(np.int32)


def arena(tree) -> np.ndarray:
    """A parameter dict as one float64 vector, tensors in name order."""
    return np.concatenate([np.asarray(tree[n], np.float64).reshape(-1)
                           for n in sorted(tree)])


def compared_schedule(sched, max_batch: int) -> np.ndarray:
    """The first batch of each size the event loop forms over ``sched``,
    largest first.  The loop groups it into exactly those batches: each
    holds distinct workers, and the smaller ones after it add up to less
    than its size, so a run of distinct workers from its start is cut
    back to it."""
    first, i = {}, 0
    for b in gen.batch_sizes(sched, max_batch):
        first.setdefault(b, np.asarray(sched[i:i + b]))
        i += b
    sizes = sorted(first, reverse=True)
    events = np.concatenate([first[b] for b in sizes]).astype(np.int32)
    if gen.batch_sizes(events, max_batch) != sizes:
        raise RuntimeError(f"compared run regroups: {sizes}")
    return events


def warm_schedule(max_batch: int, n_workers: int) -> np.ndarray:
    """One batch of each power-of-two size the event loop can form."""
    sizes, b = [], 1 << (min(max_batch, n_workers).bit_length() - 1)
    while b >= 1:
        sizes.append(b)
        b //= 2
    return np.concatenate([np.arange(b) for b in sizes]).astype(np.int32)


class Cell:
    unit = "events"

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.core import async_sim
        from repro.core.engine import CompressionSpec

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.max_batch = mix["max_batch"]
        secondary = mix.get("secondary_density")
        spec = CompressionSpec(engine=mix.get("secondary_engine", "auto"))
        self.grad_fn = jax.value_and_grad(gen.mlp_loss)
        self.tr = async_sim.AsyncTrainer(
            make_strategy(mix), self.grad_fn, cfg["n_workers"], lr=cfg["lr"],
            secondary_density=secondary, secondary_spec=spec)
        self.params0 = None
        self.data: list = []

    # ------------------------------------------------------------ set-up

    def _batch(self, e, k):
        return self.data[e]

    def _run(self, sched):
        final, sstate, hist = self.tr.run_batched(
            self.params0, sched, self._batch, max_batch=self.max_batch)
        jax.block_until_ready(sstate.M)
        return final, sstate, hist

    def _compile(self, warm):
        """Compile (or load from the cache) every stage program of every
        batch size, side by side."""
        progs = self.tr.batched_programs(self.params0, warm, self._batch,
                                         max_batch=self.max_batch)
        threads = max(1, min(12, (os.cpu_count() or 2) - 1))
        with ThreadPoolExecutor(threads) as pool:
            for f in [pool.submit(lambda fn=fn, a=a: fn.lower(*a).compile())
                      for fn, a in progs]:
                f.result()

    def setup(self, seconds: float) -> None:
        steps = {"prepare": self.prepare,
                 "size_window": lambda: self.size_window(seconds),
                 "first_steps": self.first_steps}
        self.phases = {}
        for name, step in steps.items():
            t0 = time.perf_counter()
            step()
            self.phases[name] = time.perf_counter() - t0

    def prepare(self) -> None:
        """The seed's weights and batches; every stage program compiled or
        loaded, and run once at each batch size."""
        self.params0 = gen.mlp_params(self.seed, self.cfg)
        self.probe = schedule(self.cfg, self.mix, self.seed, PROBE_EVENTS)
        self.data = gen.blob_events(self.seed, self.cfg, PROBE_EVENTS)
        warm = warm_schedule(self.max_batch, self.cfg["n_workers"])
        self._compile(warm)
        self._run(warm)              # the eager batch stacking of each size

    def size_window(self, seconds: float) -> None:
        """Events for ``seconds``, from warm runs of growing prefixes of
        the window's schedule."""
        n, t = 16, 0.0
        while t < 1.0 and 2 * n <= PROBE_EVENTS:
            n *= 2
            self._warm_join(self.probe[:n])
            t0 = time.perf_counter()
            self._run(self.probe[:n])
            t = time.perf_counter() - t0
        self.window_events(max(self.max_batch, int(n / t * seconds)))

    def window_events(self, n: int) -> None:
        """The window's schedule of ``n`` events and their batches."""
        self.n_events = n
        if n > len(self.data):
            self.data = gen.blob_events(self.seed, self.cfg, n)
        self.sched = schedule(self.cfg, self.mix, self.seed, n)
        self._warm_join(self.sched)

    def first_steps(self) -> None:
        """The compared run, through the window's own call and feed: the
        window's first batch of each size it forms, largest first, so
        that every batch program the window runs is compared.  Keeps the
        server's ``M`` after the first batch, whose events all start from
        the seed's weights, the parameters' change after all of them, the
        losses and the wire bytes."""
        events = compared_schedule(self.sched, self.max_batch)
        n1 = gen.batch_sizes(events, self.max_batch)[0]
        _, s1, _ = self._run(events[:n1])
        m1 = np.asarray(s1.M)
        del s1
        final, s, h = self._run(events)
        del s
        self.first = {"events": events, "n1": n1, "M1": m1,
                      "change": arena(final) - arena(self.params0),
                      "losses": np.asarray(h.losses),
                      "bytes": int(h.up_bytes + h.down_bytes)}
        self._ref = None

    def _warm_join(self, sched) -> None:
        """The event loop joins its per-batch losses and counts into one
        array when a run ends: one concatenate of as many operands as the
        run has batches.  Compile it before the run that is timed."""
        sizes = gen.batch_sizes(sched, self.max_batch)
        for dt in (jnp.float32, jnp.int32):
            jnp.concatenate([jnp.zeros((b,), dt) for b in sizes]
                            ).block_until_ready()

    # ------------------------------------------------------------ window

    def window(self) -> dict:
        t0 = time.perf_counter()
        final, sstate, hist = self.tr.run_batched(
            self.params0, self.sched, self._batch, max_batch=self.max_batch)
        jax.block_until_ready(sstate.M)
        t = time.perf_counter() - t0
        self.hist = hist
        return {"work": self.n_events, "seconds": t}

    def counters(self) -> dict:
        from repro.core import async_sim

        batches = async_sim.batch_schedule(self.sched,
                                           max_batch=self.max_batch)
        return {"batches": len(batches),
                "wire_bytes": int(self.hist.up_bytes + self.hist.down_bytes),
                "failed": int(np.sum(~np.isfinite(self.hist.losses)))}

    def flops_per_unit(self) -> float:
        from bench import flops

        return flops.mlp_train_flops(gen.mlp_dims(self.cfg),
                                     self.cfg["batch_per_worker"])

    def commit_apply_bytes(self) -> float:
        from bench import flops

        return flops.commit_apply_bytes(
            flops.mlp_tensor_sizes(gen.mlp_dims(self.cfg)),
            self.mix.get("secondary_density"), self.n_events)

    def free(self) -> None:
        """Drop every array the program holds; its compiled stages stay."""
        self.data, self.params0, self.hist = [], None, None

    # ------------------------------------------------------- correctness

    def readings(self, detail: bool = False) -> dict:
        """The compared run's numbers against the plain reference, which
        runs in float32 at the highest matmul precision."""
        return ref_ps.compare(self.first, self._reference(), detail)

    def control(self, dtype: str | None = None, fault: str | None = None,
                detail: bool = False) -> dict:
        """The same numbers with the reference put in the program's place:
        computed in ``dtype``, or with a planted ``fault``."""
        prog = ref_ps.run(self.cfg, self.mix, self.seed,
                          self.first["events"], self.first["n1"],
                          act_dtype=dtype or "float32", fault=fault)
        return ref_ps.compare(prog, self._reference(), detail)

    def _reference(self) -> dict:
        if getattr(self, "_ref", None) is None:
            self._ref = ref_ps.run(self.cfg, self.mix, self.seed,
                                   self.first["events"], self.first["n1"])
        return self._ref
