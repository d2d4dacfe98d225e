"""The benchmark harness: one cell, one process, one JSON line.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the sizes as run, with ``runner``
  naming ``bench/runners/<runner>.py``;
* ``bench/mixes/<traffic>.json`` — the exchange and schedule settings;
* ``bench/metrics/<metric>.py`` — ``read(rec) -> float | None``, one per
  metric, end-to-end and per-layer alike;
* ``bench/limits/<workload>.json`` — each compared number's limit;
* ``bench/peaks.json`` — the chip's peaks by ``device_kind``.

With ``BENCH_KEEP_TRACE=<file>`` a traced run also keeps its raw
``.xplane.pb`` there (``python3 -m bench.trace_reduce`` trims one into
the tests' recorded traces).

A runner module has ``Cell(cfg, mix, seed)`` with ``setup(seconds)``,
``window() -> {"work", "seconds"}``, ``counters()``, ``flops_per_unit()``,
``free()`` and ``readings()``; its ``unit`` names what ``work`` counts.
A ``phases`` dict of set-up's seconds by step, where the cell keeps one,
is printed to standard error.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The run cannot measure here: no accelerator, too few chips, or a
    chip the peaks table does not know."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    """The workload entry and its configuration entry."""
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            for cfg in bench["configs"]:
                if cfg["name"] == cell["config"]:
                    return cell, cfg
            raise KeyError(f"{workload}: no configuration {cell['config']!r}")
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_files(bench: dict, workload: str):
    """``(cell, cfg, mix, limits, runner module path)`` for one cell."""
    cell, cfg_entry = find_cell(bench, workload)
    cfg = load_json(ROOT / cfg_entry["file"])
    mix = load_json(BENCH / "mixes" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    runner = BENCH / "runners" / f"{cfg['runner']}.py"
    if not runner.exists():
        raise FileNotFoundError(runner)
    return cell, cfg, mix, limits, runner


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics, or with a trace
    its per-layer ones; a metric with ``workloads`` only in those."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def check_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``, a fixed path; every program is
    kept, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_memory(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def settle() -> None:
    """Let earlier work end before a window opens: collect what was
    dropped, then wait for one small program, which the device runs
    after everything queued before it."""
    import gc

    import jax
    import jax.numpy as jnp

    gc.collect()
    jax.block_until_ready(jnp.arange(2, dtype=jnp.int32) + 1)


def traced(fn):
    """Run ``fn`` under the profiler, inside the ``WINDOW`` span; returns
    ``(fn's result, reduced trace)``.  One small program runs under the
    profiler before the span opens, so that the profiler's first dispatch
    is not the window's.  The trace is written to a temporary directory
    and removed."""
    import jax

    from bench import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            settle()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                out = fn()
        finally:
            jax.profiler.stop_trace()
        path = next(pathlib.Path(tmp).rglob("*.xplane.pb"))
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            shutil.copy(path, keep)
        return out, trace_reduce.reduce(trace_reduce.load(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the limits name beside its limit; correct when each is
    at or under it.  A limit with no reading is an error."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits["limits"].items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, device: dict | None = None,
             cfg_override: dict | None = None) -> dict:
    """Set up, measure, check.  Returns the result line as a dict.
    Without ``device`` (the CPU tests) no peak memory or peaks are read;
    ``cfg_override`` replaces configuration keys (the tests' small
    sizes)."""
    bench = spec()
    cell, cfg, mix, limits, runner_path = cell_files(bench, workload)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    runner = load_module(runner_path)
    obj = runner.Cell(cfg, mix, seed)
    obj.setup(seconds)
    settle()
    setup_s = time.perf_counter() - t_start
    for phase, s in getattr(obj, "phases", {}).items():
        print(f"setup {phase} {s:.3f} s", file=sys.stderr)
    if trace:
        win, red = traced(obj.window)
    else:
        win, red = obj.window(), None
    mem = peak_memory(cell["chips"]) if device else None
    rec = {"work": win["work"], "window_s": win["seconds"],
           "setup_s": setup_s, "unit": runner.Cell.unit,
           "counters": obj.counters(), "flops_per_unit": obj.flops_per_unit(),
           "trace": red, "peaks": peaks_for(device["kind"]) if device else None,
           "chips": cell["chips"]}
    if hasattr(obj, "commit_apply_bytes"):
        rec["commit_apply_bytes"] = obj.commit_apply_bytes()
    obj.free()
    correct, checks = judge(obj.readings(), limits)
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device or {"platform": "none", "kind": "none", "count": 0})
    dev["memory_peak_bytes"] = mem
    if red is not None:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
    line = {"correct": correct, "attempted": win["work"],
            "failed": rec["counters"].get("failed", 0), "metrics": metrics,
            "device": dev}
    if red is not None:
        line["breakdown"] = red["breakdown"]
    line["checks"] = checks
    return line


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec()
        cell, _ = find_cell(bench, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise NoChip(f"no system under test at {ROOT / 'src'}")
        device = check_device(cell["chips"])
        peaks_for(device["kind"])
    except (NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=t_start, device=device)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
