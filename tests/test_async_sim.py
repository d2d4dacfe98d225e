import numpy as np
import pytest

from repro.core import async_sim


def test_schedule_deterministic():
    a = async_sim.make_schedule(8, 100, seed=5, hetero=0.5)
    b = async_sim.make_schedule(8, 100, seed=5, hetero=0.5)
    np.testing.assert_array_equal(a, b)
    assert set(np.unique(a)) <= set(range(8))


def test_schedule_fair_when_homogeneous():
    s = async_sim.make_schedule(4, 4000, seed=0, hetero=0.0)
    counts = np.bincount(s, minlength=4)
    assert counts.min() > 0.8 * counts.max()


def test_schedule_stragglers_when_heterogeneous():
    s = async_sim.make_schedule(4, 4000, seed=0, hetero=1.5)
    counts = np.bincount(s, minlength=4)
    assert counts.max() > 2 * counts.min()  # fast workers dominate


def test_staleness_grows_with_workers():
    import jax, jax.numpy as jnp
    from repro.core import make_strategy

    def grad_fn(p, b):
        return jnp.sum(p["w"] ** 2), jax.tree.map(lambda x: 2 * x, p)

    def batch_fn(e, k):
        return None

    params0 = {"w": jnp.ones((4,))}
    stats = []
    for n in (2, 8):
        tr = async_sim.AsyncTrainer(make_strategy("asgd"), grad_fn, n,
                                    lr=0.01)
        sched = async_sim.make_schedule(n, 120, seed=1, hetero=0.3)
        _, _, hist = tr.run(params0, sched, batch_fn)
        stats.append(hist.staleness[n * 2:].mean())
    assert stats[1] > stats[0]


# ---------------------------------------------------------------------------
# batched event loop: scheduling properties + bit-for-bit parity
# ---------------------------------------------------------------------------

def test_batch_schedule_partition_properties():
    for seed in range(5):
        sched = async_sim.make_schedule(7, 200, seed=seed, hetero=0.8)
        batches = async_sim.batch_schedule(sched)
        # exact partition: concatenating the batches recovers the schedule
        np.testing.assert_array_equal(np.concatenate(batches), sched)
        for b in batches:
            assert len(set(int(x) for x in b)) == len(b)  # distinct workers
            assert len(b) & (len(b) - 1) == 0             # power of two


def test_batch_schedule_max_batch_and_cut_every():
    sched = async_sim.make_schedule(9, 300, seed=2, hetero=0.3)
    for max_batch, cut_every in [(4, None), (None, 16), (8, 24)]:
        batches = async_sim.batch_schedule(sched, max_batch=max_batch,
                                           cut_every=cut_every)
        np.testing.assert_array_equal(np.concatenate(batches), sched)
        i = 0
        for b in batches:
            if max_batch is not None:
                assert len(b) <= max_batch
            if cut_every is not None:
                # a batch never straddles an eval boundary
                assert i // cut_every == (i + len(b) - 1) // cut_every
            i += len(b)


def _parity_problem():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    params0 = {
        "w1": jnp.asarray(rng.normal(size=(12, 16)).astype(np.float32)),
        "b1": jnp.zeros(16),
        "w2": jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32)),
    }
    X = jnp.asarray(rng.normal(size=(64, 12)).astype(np.float32))
    Y = jnp.asarray(rng.integers(0, 4, 64))

    def grad_fn(p, batch):
        x, y = batch

        def loss(q):
            h = jnp.tanh(x @ q["w1"] + q["b1"]) @ q["w2"]
            lp = jax.nn.log_softmax(h)
            return -jnp.mean(lp[jnp.arange(x.shape[0]), y])

        return jax.value_and_grad(loss)(p)

    def batch_fn(e, k):
        i = (e * 7 + k * 3) % 56
        return (X[i:i + 8], Y[i:i + 8])

    return params0, grad_fn, batch_fn


def _assert_runs_equal(tr, params0, sched, batch_fn, **kw):
    import jax

    f1, s1, h1 = tr.run(params0, sched, batch_fn, **kw)
    f2, s2, h2 = tr.run_batched(params0, sched, batch_fn, **kw)
    np.testing.assert_array_equal(h1.losses, h2.losses)
    assert h1.up_bytes == h2.up_bytes
    assert h1.down_bytes == h2.down_bytes
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(s1.M), np.asarray(s2.M))
    np.testing.assert_array_equal(np.asarray(s1.v), np.asarray(s2.v))
    return h1, h2


_PARITY_CONFIGS = [
    # (strategy, kwargs, secondary_density, down_quantize, engine)
    ("dgs", dict(density=0.1), 0.1, "int8", "exact"),
    ("dgs", dict(density=0.2), 0.15, "bf16", "sampled"),
    ("dgs", dict(density=0.1), 0.1, "tern", "blockwise"),
    ("dgc_async", dict(density=0.1), 0.1, "none", "exact"),
    ("asgd", dict(), None, "none", "exact"),
    ("gd_async", dict(density=0.1), 0.1, "int8", "exact"),
]


@pytest.mark.parametrize("name,kw,sec,dq,eng", _PARITY_CONFIGS)
def test_batched_matches_serial_bitwise(name, kw, sec, dq, eng):
    """The tentpole contract: run_batched == run, bit for bit — losses,
    byte accounting, final params, and server state — across strategies,
    compression engines, and wire quantize modes."""
    from repro.core import engine as engine_lib
    from repro.core import make_strategy

    specs = {
        "exact": engine_lib.CompressionSpec(engine="exact", quantize=dq),
        "sampled": engine_lib.CompressionSpec(engine="sampled", quantize=dq),
        "blockwise": engine_lib.CompressionSpec(engine="blockwise",
                                                quantize=dq, block_r=4),
    }
    params0, grad_fn, batch_fn = _parity_problem()
    sched = async_sim.make_schedule(5, 40, seed=3, hetero=0.8)
    tr = async_sim.AsyncTrainer(make_strategy(name, **kw), grad_fn, 5,
                                lr=0.05, secondary_density=sec,
                                secondary_spec=specs[eng])
    _assert_runs_equal(tr, params0, sched, batch_fn)


def test_batched_matches_serial_with_lr_fn_and_eval():
    from repro.core import make_strategy
    from repro.core.paramspace import ParamSpace

    params0, grad_fn, batch_fn = _parity_problem()
    sched = async_sim.make_schedule(5, 40, seed=1, hetero=0.5)
    tr = async_sim.AsyncTrainer(make_strategy("dgs", density=0.1), grad_fn,
                                5, lr=0.05, secondary_density=0.1)
    space = ParamSpace.from_tree(params0)

    def eval_fn(model):
        return float(np.asarray(space.pack(model)).sum())

    h1, h2 = _assert_runs_equal(tr, params0, sched, batch_fn,
                                lr_fn=lambda e: 0.05 / (1 + 0.01 * e),
                                eval_fn=eval_fn, eval_every=8)
    assert [e for e, _ in h1.evals] == [e for e, _ in h2.evals]
    assert [v for _, v in h1.evals] == [v for _, v in h2.evals]


def test_batched_max_batch_one_matches_serial():
    from repro.core import make_strategy

    params0, grad_fn, batch_fn = _parity_problem()
    sched = async_sim.make_schedule(4, 24, seed=6, hetero=0.5)
    tr = async_sim.AsyncTrainer(make_strategy("dgc_async", density=0.1),
                                grad_fn, 4, lr=0.05, secondary_density=0.1)
    f1, s1, h1 = tr.run(params0, sched, batch_fn)
    f2, s2, h2 = tr.run_batched(params0, sched, batch_fn, max_batch=1)
    np.testing.assert_array_equal(h1.losses, h2.losses)
    import jax
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# flight-recorder telemetry: metrics must not perturb the data plane
# ---------------------------------------------------------------------------

_METRICS_CONFIGS = [
    # (strategy, kwargs, secondary_density, the telemetry switched on);
    # the metrics cases keep the ids they had before the recorder cases
    pytest.param("dgs", dict(density=0.1, quantize="int8"), 0.1, "metrics",
                 id="dgs-kw0-0.1"),
    pytest.param("dgc_async", dict(density=0.1), 0.1, "metrics",
                 id="dgc_async-kw1-0.1"),
    pytest.param("asgd", dict(), None, "metrics", id="asgd-kw2-None"),
    pytest.param("dgs", dict(density=0.1, quantize="int8", engine="sampled"),
                 0.1, "recorder", id="dgs-sampled-recorder"),
    pytest.param("asgd", dict(), None, "recorder", id="asgd-recorder"),
]


@pytest.mark.parametrize("name,kw,sec,on", _METRICS_CONFIGS)
def test_metrics_do_not_change_bits(name, kw, sec, on):
    """DESIGN.md §11's contract: metrics ON is bit-identical to metrics
    OFF — losses, final params, byte totals — in every runner (serial,
    batched, scan), and all three runners agree on the drained
    MetricsState itself.  A Recorder on the batched loop changes no bit
    either; it gets the loop's spans, and a second run of a schedule
    builds no program."""
    import jax
    import jax.numpy as jnp

    from repro import telemetry
    from repro.core import make_strategy
    from repro.core.scan_runner import run_async_scan
    from repro.telemetry import metrics as metrics_lib

    params0, grad_fn, batch_fn = _parity_problem()
    n_workers, n_events = 5, 40
    sched = async_sim.make_schedule(n_workers, n_events, seed=3, hetero=0.8)
    tr = async_sim.AsyncTrainer(make_strategy(name, **kw), grad_fn,
                                n_workers, lr=0.05, secondary_density=sec)

    if on == "recorder":
        rec = telemetry.Recorder()
        f_off, _, h_off = tr.run_batched(params0, sched, batch_fn,
                                         max_batch=4)
        f_on, _, h_on = tr.run_batched(params0, sched, batch_fn,
                                       max_batch=4, recorder=rec)
        np.testing.assert_array_equal(h_off.losses, h_on.losses)
        assert (h_off.up_bytes, h_off.down_bytes) == (h_on.up_bytes,
                                                      h_on.down_bytes)
        for a, b in zip(jax.tree.leaves(f_off), jax.tree.leaves(f_on)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        n_batches = len(async_sim.batch_schedule(sched, max_batch=4))
        spans = [ev for ev in rec._trace if ev["ph"] == "X"]
        names = [ev["name"] for ev in spans]
        assert names[0] == "batched/init" and names[-1] == "batched/finish"
        for stage in ("batch_build", "client", "server", "commit", "apply"):
            per = [ev["args"] for ev in spans
                   if ev["name"] == f"batched/{stage}"]
            assert [a["batch"] for a in per] == list(range(n_batches))
            assert sum(a["size"] for a in per) == n_events
        assert not [k for k in rec.counters if k.startswith("programs/")]
        return

    f_off, _, h_off = tr.run(params0, sched, batch_fn)
    f_on, _, h_on = tr.run(params0, sched, batch_fn, metrics=True)
    np.testing.assert_array_equal(h_off.losses, h_on.losses)
    assert (h_off.up_bytes, h_off.down_bytes) == (h_on.up_bytes,
                                                  h_on.down_bytes)
    for a, b in zip(jax.tree.leaves(f_off), jax.tree.leaves(f_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert h_off.metrics is None

    f_b, _, h_b = tr.run_batched(params0, sched, batch_fn, metrics=True)
    np.testing.assert_array_equal(h_off.losses, h_b.losses)
    for a, b in zip(jax.tree.leaves(f_off), jax.tree.leaves(f_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    strat = make_strategy(name, **kw)
    batches = [batch_fn(e, int(sched[e])) for e in range(n_events)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    f_sc_off, h_sc_off = run_async_scan(
        strat, grad_fn, params0, sched, stacked, n_workers=n_workers,
        lr=0.05, secondary_density=sec)
    f_sc, h_sc = run_async_scan(
        strat, grad_fn, params0, sched, stacked, n_workers=n_workers,
        lr=0.05, secondary_density=sec, metrics=True)
    np.testing.assert_array_equal(np.asarray(h_sc_off.losses),
                                  np.asarray(h_sc.losses))
    np.testing.assert_array_equal(h_off.losses, np.asarray(h_sc.losses))
    assert (h_sc_off.up_bytes, h_sc_off.down_bytes) == (h_sc.up_bytes,
                                                        h_sc.down_bytes)
    for a, b in zip(jax.tree.leaves(f_sc_off), jax.tree.leaves(f_sc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the drained state: correct content, and runner-independent.  The
    # magnitude histogram's bucket is a float reduction (|G|^2), so it is
    # checked for mass only; every integer-exact histogram must agree
    # across runners bucket-for-bucket.
    md = h_on.metrics
    assert md["n_events"] == n_events
    assert md["per_worker"] == np.bincount(
        sched, minlength=n_workers).tolist()
    assert sum(md["staleness_hist"]["counts"]) == n_events
    assert sum(md["update_mag_hist"]["counts"]) == n_events
    assert md["staleness_hist"] == metrics_lib.summarize_log2(
        h_on.staleness)
    for other in (h_b.metrics, h_sc.metrics):
        a, b = dict(md), dict(other)
        a.pop("update_mag_hist"), b.pop("update_mag_hist")
        assert a == b
        assert sum(other["update_mag_hist"]["counts"]) == n_events


@pytest.mark.parametrize("name,kw,eng", [
    ("dgs", dict(density=0.1, quantize="int8"), "exact"),
    ("dgs", dict(density=0.1, quantize="int8"), "sampled"),
    ("asgd", dict(), "exact"),
])
def test_batched_stage_programs_carry_layer_scopes(name, kw, eng):
    """Each batched stage program's compiled HLO names its layers in the
    ops' ``op_name`` metadata, which a device trace keeps: selection
    under the client strategy and the server's secondary select, wire
    quantization in the quantizers."""
    import re

    from repro.core import make_strategy
    from repro.core.engine import CompressionSpec
    from repro.telemetry.trace import LAYER

    params0, grad_fn, batch_fn = _parity_problem()
    sched = async_sim.make_schedule(5, 40, seed=3, hetero=0.9)
    sparse = name != "asgd"
    spec = CompressionSpec(engine=eng, quantize="int8")
    tr = async_sim.AsyncTrainer(
        make_strategy(name, engine=eng, **kw) if sparse
        else make_strategy(name), grad_fn, 5, lr=0.05,
        secondary_density=0.1 if sparse else None, secondary_spec=spec)
    want = {
        "run": {LAYER.grad, LAYER.strategy} | ({LAYER.select} if sparse
                                                else set()),
        "<lambda>": {LAYER.quantize},
        "server_batch": {LAYER.receive} | ({LAYER.select} if sparse
                                           else set()),
        "commit": {LAYER.commit},
        "apply_rows": {LAYER.apply},
    }
    seen = set()
    for fn, args in tr.batched_programs(params0, sched, batch_fn,
                                        max_batch=4):
        text = fn.lower(*args).compile().as_text()
        paths = " ".join(re.findall(r'op_name="([^"]*)"', text))
        got = set(re.findall(r"layer\.\w+", paths))
        assert got == want[fn.__name__], (fn.__name__, got)
        seen.add(fn.__name__)
    assert seen == set(want) - (set() if sparse else {"<lambda>"})


@pytest.mark.parametrize("name,kw,sec", [
    ("dgs", dict(density=0.1, quantize="int8"), 0.1),
    ("asgd", dict(), None),
])
def test_batched_programs_prebuild_every_stage(name, kw, sec):
    """Once ``batched_programs`` are compiled, the run compiles no stage
    program of its own, and its result is bit-equal to a run that
    compiled lazily."""
    import logging

    import jax
    from repro.core import make_strategy
    from repro.core.engine import CompressionSpec

    params0, grad_fn, batch_fn = _parity_problem()
    sched = async_sim.make_schedule(5, 40, seed=3, hetero=0.9)

    def trainer():
        return async_sim.AsyncTrainer(
            make_strategy(name, **kw), grad_fn, 5, lr=0.05,
            secondary_density=sec,
            secondary_spec=CompressionSpec(engine="exact", quantize="bf16"))

    tr = trainer()
    n_sizes = len({len(b) for b in async_sim.batch_schedule(
        sched, max_batch=4)})
    progs = tr.batched_programs(params0, sched, batch_fn, max_batch=4)
    assert len(progs) >= 4 * n_sizes
    for fn, args in progs:
        fn.lower(*args).compile()

    stage_names = ("jit(run)", "jit(server_batch)", "jit(commit)",
                   "jit(apply_rows)", "jit(<lambda>)")
    seen = []

    class Catch(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "Finished XLA compilation" in msg:
                seen.append(msg)

    handler = Catch()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            f1, _, h1 = tr.run_batched(params0, sched, batch_fn,
                                       max_batch=4)
    finally:
        logger.removeHandler(handler)
    assert not [m for m in seen if any(s in m for s in stage_names)], seen
    f2, _, h2 = trainer().run_batched(params0, sched, batch_fn, max_batch=4)
    np.testing.assert_array_equal(h1.losses, h2.losses)
    assert (h1.up_bytes, h1.down_bytes) == (h2.up_bytes, h2.down_bytes)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _apply_batch(sparse):
    """A stacked worker buffer, distinct batch rows and a shipped batch:
    a SparseLeaf of distinct per-row indices, or a dense (B, total)."""
    import jax.numpy as jnp

    from repro.core.sparsify import SparseLeaf

    rng = np.random.default_rng(5)
    w, n, k = 6, 2000, 30
    wp = rng.normal(size=(w, n)).astype(np.float32)
    wp[:, ::5] = -0.0
    ids = np.asarray([4, 1, 2], np.int32)
    if sparse:
        idx = np.stack([rng.choice(n, k, replace=False)
                        for _ in ids]).astype(np.int32)
        G = SparseLeaf(values=jnp.asarray(
            rng.normal(size=(len(ids), k)).astype(np.float32)),
            indices=jnp.asarray(idx), size=n)
    else:
        G = jnp.asarray(rng.normal(size=(len(ids), n)).astype(np.float32))
    return jnp.asarray(wp), jnp.asarray(ids), G


def test_batched_sparse_apply_scatters_into_donated_rows():
    """The sparse ``apply_rows`` program is one flat scatter per batch
    row straight into its donated ``wp`` (no vmapped copy of the rows),
    and equals the serial ``apply_update`` of each row, bit for bit."""
    import re

    import jax
    from repro.core import server as ps
    from repro.core.sparsify import SparseLeaf

    wp, ids, G = _apply_batch(sparse=True)
    apply_rows = async_sim.make_batched_apply()
    text = apply_rows.lower(wp, ids, G).as_text()
    assert "tf.aliasing_output = 0" in text
    scatters = re.findall(r"\}\) : \((tensor<[^,]*), tensor<[^,]*xi32>",
                          text)
    assert scatters == [f"tensor<{wp.shape[1]}xf32>"] * len(ids), scatters
    assert "gather" not in text

    expect = np.asarray(wp).copy()
    for b, r in enumerate(np.asarray(ids)):
        expect[r] = np.asarray(ps.apply_update(
            wp[r], SparseLeaf(G.values[b], G.indices[b], G.size)))
    out = np.asarray(apply_rows(jax.numpy.copy(wp), ids, G))
    np.testing.assert_array_equal(out.view(np.uint32),
                                  expect.view(np.uint32))


def test_batched_dense_apply_lowering_unchanged():
    """A dense batch (ASGD) keeps the vmapped gather-apply-set program:
    its lowering is text-equal to the expression written out here."""
    import jax
    from repro.core import server as ps

    wp, ids, G = _apply_batch(sparse=False)

    def apply_rows(wp, ids, G):
        return wp.at[ids].set(jax.vmap(ps.apply_update)(wp[ids], G))

    want = jax.jit(apply_rows, donate_argnums=(0,)).lower(wp, ids, G)
    got = async_sim.make_batched_apply().lower(wp, ids, G)
    assert got.as_text() == want.as_text()
