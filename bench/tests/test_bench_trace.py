"""The trace reduction, on a hand-made trace and on a recorded one."""
import gzip
import json
import pathlib

import pytest

from bench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def planes(ops, modules, host):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
    ]


def test_op_label_drops_layouts():
    name = ("%sort.7 = (f32[6448,73728]{1,0:T(8,128)}, s32[6448,73728]"
            "{1,0:T(8,128)}) sort(f32[6448,73728]{1,0:T(8,128)} %abs.48)")
    assert tr.op_label(name) == "%sort.7 = (f32[6448,73728], s32[6448,73728]) sort"
    assert tr.SORT_RE.search(tr.op_label(name))


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_hand_made():
    s = 1_000_000   # 1 ms in ns
    host = [[tr.WINDOW, 0, 10 * s, ""],
            ["PjitFunction(run)", 1 * s, 3 * s, ""],
            ["batch_build", 6 * s, 9 * s, ""]]
    ops = [["%sort.1 = f32[8]{0} sort(f32[8]{0} %p)", 1 * s, 2 * s, ""],
           ["%while.2 = (f32[8]{0}) while(%t)", 2 * s, 5 * s, ""],
           ["%fusion.3 = f32[8]{0} fusion(%q)", 2 * s + s // 2,   # nested
            3 * s + s // 2, ""],
           ["scatter.4", 9 * s, 12 * s, ""]]          # clipped at 10
    modules = [["jit_run(7)", 1 * s, 5 * s, ""],
               ["jit_apply_rows(9)", 9 * s, 12 * s, ""]]
    red = tr.reduce(planes(ops, modules, host))
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)       # [1,5] and [9,10]
    assert red["sort_s"] == pytest.approx(0.001)
    assert red["modules"] == pytest.approx({"jit_run": 0.004,
                                            "jit_apply_rows": 0.001})
    assert tr.module_seconds(red, {"jit_run"}) == pytest.approx(0.004)
    assert tr.module_seconds(red, {"jit_commit"}) is None
    gaps = red["breakdown"]["idle_gaps"]
    # [5,9] is the longest gap, the host building a batch for most of it
    assert gaps[0] == ["batch_build", pytest.approx(0.004)]
    assert gaps[1][1] == pytest.approx(0.001)           # [0,1]
    top = dict(red["breakdown"]["device_ops"])
    # the loop's own time is what its nested op leaves
    assert top == pytest.approx({"%while.2 = (f32[8]) while": 0.002,
                                 "%sort.1 = f32[8] sort": 0.001,
                                 "%fusion.3 = f32[8] fusion": 0.001,
                                 "scatter.4": 0.001})


def test_reduce_needs_a_window_and_device_ops():
    with pytest.raises(ValueError):
        tr.reduce(planes([], [], [["other", 0, 5, ""]]))
    with pytest.raises(ValueError):
        tr.reduce(planes([], [], [[tr.WINDOW, 0, 5, ""]]))


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json.gz")))
def test_reduce_recorded_chip_trace(name):
    """The first 50 ms of a traced window of a cell on one TPU v5e
    (``python3 -m bench.trace_reduce``), with the numbers the reduction
    gave on it when it was recorded."""
    with gzip.open(DATA / name, "rt") as f:
        rec = json.load(f)
    red = tr.reduce(rec["planes"])
    want = rec["expect"]
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # self times add up to the busy time where ops do not overlap
    assert sum(s for _, s in red["breakdown"]["device_ops"]) <= red["busy_s"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["sort_s"] == pytest.approx(want["sort_s"], rel=1e-9)
    for m, s in want["modules"].items():
        assert red["modules"][m] == pytest.approx(s, rel=1e-9)
