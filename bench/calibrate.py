#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--faults state_unchanged,half_batch] \
        [--highest-seeds 7,8] [--out FILE]

In one process, on the chip: for each of ``--seeds`` the program's
compared run against the plain reference (the lower readings); for each
of ``--control-seeds`` the reference put in the program's place in the
precision below the configuration's, and with each planted fault (the
upper readings); for each of ``--highest-seeds`` the program run at the
reference's own precision (float32 at ``highest``), a witness that the
two compute the same thing where rounding does not part them.  The
window is sized once, on the first seed, as a run sizes it: every seed's
schedule has the same batches.  One JSON line per reading, with each
leaf's gaps and supports, to standard output and to ``--out``.  The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

LOWER_DTYPE = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def calibrate(workload, seeds, control_seeds, faults, emit,
              cfg_override=None, highest_seeds=(), seconds=None):
    import jax

    bench = harness.spec()
    cell, cfg, mix, limits, runner_path = harness.cell_files(bench, workload)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    seconds = bench["run_seconds"] if seconds is None else seconds
    runner = harness.load_module(runner_path)
    obj = runner.Cell(cfg, mix, (list(seeds) + list(control_seeds) +
                                 list(highest_seeds))[0])
    plan = ([("program", s) for s in seeds] +
            [("control", s) for s in control_seeds] +
            [("program_highest", s) for s in highest_seeds])
    for kind, seed in plan:
        obj.seed = seed
        t0 = time.perf_counter()
        with jax.default_matmul_precision(
                "highest" if kind == "program_highest" else None):
            obj.prepare()
            if getattr(obj, "n_events", None) is None:
                obj.size_window(seconds)
            else:
                obj.window_events(obj.n_events)
            obj.first_steps()
        obj.free()
        if kind != "control":
            t1 = time.perf_counter()
            row = obj.readings(detail=True)
            emit({"kind": kind, "seed": seed, **row,
                  "events": len(obj.first["events"]),
                  "seconds": time.perf_counter() - t0,
                  "reference_s": time.perf_counter() - t1})
            continue
        lower = LOWER_DTYPE[cfg["dtype"]]
        emit({"kind": f"control_{lower}", "seed": seed,
              **obj.control(dtype=lower, detail=True)})
        for fault in faults:
            emit({"kind": f"fault_{fault}", "seed": seed,
                  **obj.control(fault=fault, detail=True)})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="state_unchanged,half_batch")
    ap.add_argument("--highest-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell, _ = harness.find_cell(harness.spec(), args.workload)
    device = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    out = open(args.out, "a") if args.out else None

    def emit(row):
        row = {"workload": args.workload, "device": device["kind"], **row}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    calibrate(args.workload, ints(args.seeds), ints(args.control_seeds),
              [f for f in args.faults.split(",") if f], emit,
              highest_seeds=ints(args.highest_seeds))
    if out:
        out.close()
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
