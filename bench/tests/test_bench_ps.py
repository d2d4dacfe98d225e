"""The parameter-server cells end to end on the CPU at a tiny size: a
sound run is correct, and the control (the reference with float8 matmul
inputs put in the program's place) is not."""
import pytest

from bench import calibrate, harness
from bench.tests import ps_tiny


@pytest.mark.parametrize("workload", ps_tiny.CELLS)
def test_sound_run_is_correct(workload):
    line = ps_tiny.run(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "events_per_s"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", ps_tiny.CELLS)
def test_control_is_not_correct(workload):
    rows = []
    calibrate.calibrate(workload, [], [7], [], rows.append,
                        cfg_override=ps_tiny.TINY)
    (control,) = rows
    assert control["kind"] == "control_float8_e4m3fn"
    limits = harness.load_json(
        harness.BENCH / "limits" / f"{workload}.json")
    readings = {k: control[k] for k in limits["limits"]}
    assert not harness.judge(readings, limits)[0], readings
