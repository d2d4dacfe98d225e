"""A parameter-server cell cut to a size the CPU runs in seconds: four
workers, a 16-feature input, and one 2048 x 512 tensor, large enough for
the ``auto`` engine's sampled selection."""
import time

from bench import harness

TINY = {"n_workers": 4, "features": 16, "hidden": [2048, 512]}
CELLS = ["ps32-mlp10m.dgs-int8", "ps32-mlp10m.asgd-dense"]


def run(workload, seed=2147483659):
    return harness.run_cell(workload, seed, 0.3, False,
                            t_start=time.perf_counter(), cfg_override=TINY)
