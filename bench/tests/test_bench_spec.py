"""BENCHMARK.json and the files it names, checked as data on the CPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.spec()


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == set(names)


ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries_have_exactly_their_keys(bench, group):
    needed, optional = ENTRY_KEYS[group]
    for entry in bench[group]:
        assert needed <= set(entry) <= needed | optional, entry["name"]
        for key in ("why", "layer", "source"):
            text = entry.get(key)
            if isinstance(text, str):
                assert 1 <= len(text) <= 200
                assert "\n" not in text and "\t" not in text


def test_every_file_is_found_by_name(bench):
    for w in bench["workloads"]:
        cell, cfg, mix, limits, runner = harness.cell_files(bench, w["name"])
        assert runner.exists() and limits["limits"]
        mod = harness.load_module(runner)
        assert hasattr(mod, "Cell")
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(KeyError):
        harness.find_cell(bench, "no-such.cell")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_per_layer_cells_report_what_they_move(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        cells = m.get("workloads") or [w["name"] for w in bench["workloads"]]
        for cell in cells:
            reported = {x["name"] for x in harness.metrics_for(
                bench, cell, trace=False)}
            assert m["moves"] in reported, (m["name"], cell)
    for w in bench["workloads"]:
        assert harness.metrics_for(bench, w["name"], trace=True)
        assert len(harness.metrics_for(bench, w["name"], trace=False)) >= 2


def test_readers_leave_out_what_they_cannot_read(bench):
    rec = {"work": 10, "window_s": 2.0, "setup_s": 1.0, "unit": "events",
           "counters": {}, "flops_per_unit": 1.0, "trace": None,
           "peaks": None, "chips": 1}
    for m in bench["per_layer"]:
        mod = harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py")
        assert mod.read(rec) is None, m["name"]


def test_judge_holds_each_number_to_its_limit():
    limits = {"limits": {"a": 0.1, "b": 0}}
    ok, checks = harness.judge({"a": 0.05, "b": 0.0}, limits)
    assert ok and checks["a"] == {"value": 0.05, "limit": 0.1}
    assert not harness.judge({"a": 0.2, "b": 0.0}, limits)[0]
    assert not harness.judge({"a": 0.0, "b": 1.0}, limits)[0]
    with pytest.raises(KeyError):
        harness.judge({"a": 0.0}, limits)
    # a number the limits do not name is not compared
    assert harness.judge({"a": 0.0, "b": 0.0, "c": 9.0}, limits)[0]


def test_cpu_run_exits_nonzero_with_no_result(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = bench["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())
    assert "no TPU" in out.stderr


def test_run_without_the_system_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no system to measure."""
    import shutil

    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         harness.spec()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()


def test_benchmark_json_is_small_and_plain(bench):
    raw = (harness.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert json.loads(raw) == bench
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_reduced_keys_are_in_the_configuration_file(bench):
    for c in bench["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["departures"], key
            assert not key.endswith(("_dim", "_rank", "_size"))
