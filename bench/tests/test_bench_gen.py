"""The benchmark's copies of the program's generators give what the
originals give, and its inputs depend only on the seed."""
import numpy as np
import pytest

from bench import gen


@pytest.mark.parametrize("hetero", [0.0, 0.8])
def test_schedule_copy_matches_the_program(hetero):
    from repro.core import async_sim

    ours = gen.make_schedule(32, 500, seed=7, hetero=hetero)
    theirs = async_sim.make_schedule(32, 500, seed=7, hetero=hetero)
    assert np.array_equal(ours, theirs)
    # a prefix of a longer schedule is the shorter one
    assert np.array_equal(gen.make_schedule(32, 100, seed=7, hetero=hetero),
                          ours[:100])
    sizes = [len(b) for b in async_sim.batch_schedule(ours, max_batch=16)]
    assert gen.batch_sizes(ours, 16) == sizes


def test_inputs_come_from_the_seed_alone():
    cfg = {"features": 16, "classes": 10, "batch_per_worker": 8,
           "noise": 1.0, "hidden": [32]}
    big = 2**31 + 11
    a = gen.blob_events(big, cfg, 70)
    b = gen.blob_events(big, cfg, 3)
    assert len(a) == 70 and a[0][0].shape == (8, 16)
    assert all(np.array_equal(a[i][0], b[i][0]) for i in range(3))
    c = gen.blob_events(big + 1, cfg, 3)
    assert not np.array_equal(a[0][0], c[0][0])
    # rows of different events differ
    assert not np.array_equal(a[0][0], a[1][0])
    p, q = gen.mlp_params(big, cfg), gen.mlp_params(big, cfg)
    assert all(np.array_equal(p[k], q[k]) for k in p)
    with pytest.raises(ValueError):
        gen.root_key(-1)


@pytest.mark.parametrize("hetero,max_batch", [(0.8, 16), (0.0, 16), (0.8, 4)])
def test_compared_run_holds_each_batch_size_of_the_window(hetero, max_batch):
    from repro.core import async_sim

    from bench import harness

    ps = harness.load_module(harness.BENCH / "runners" / "ps.py")
    window = gen.make_schedule(32, 1000, seed=7, hetero=hetero)
    events = ps.compared_schedule(window, max_batch)
    grouped = [len(b) for b in async_sim.batch_schedule(
        events, max_batch=max_batch)]
    assert grouped == sorted(set(gen.batch_sizes(window, max_batch)),
                             reverse=True)
    # each batch is the window's first of its size
    i, first = 0, {}
    for b in gen.batch_sizes(window, max_batch):
        first.setdefault(b, list(window[i:i + b]))
        i += b
    j = 0
    for b in grouped:
        assert list(events[j:j + b]) == first[b]
        j += b


def test_every_seed_has_the_same_batches():
    from bench import harness

    ps = harness.load_module(harness.BENCH / "runners" / "ps.py")
    cfg = {"n_workers": 32}
    mix = {"schedule_seed": 7, "hetero": 0.8}
    a = ps.schedule(cfg, mix, 2**31 + 11, 500)
    b = ps.schedule(cfg, mix, 17, 500)
    assert not np.array_equal(a, b)
    assert gen.batch_sizes(a, 16) == gen.batch_sizes(b, 16)
