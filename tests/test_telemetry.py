"""Unit tests for the flight-recorder layer itself (DESIGN.md §11):
in-graph metrics, the trace/JSONL recorder, the log facility, and the
report renderer — the runner-integration contracts live in
test_async_sim.py / test_cluster.py."""
import json
import pathlib
import re
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.telemetry import metrics as M

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# metrics: bucketing, update, drain
# ---------------------------------------------------------------------------

def test_log2_bin_buckets_split_at_powers_of_two():
    xs = jnp.asarray([0, 1, 2, 3, 6, 7, 14, 2 ** 30], jnp.int32)
    got = np.asarray(M.log2_bin(xs, M.N_BINS))
    # bucket b holds x in [2^b - 1, 2^(b+1) - 2]; huge values clip
    assert got.tolist() == [0, 1, 1, 2, 2, 3, 3, M.N_BINS - 1]


def test_update_batched_equals_sequential_scalars():
    ms_seq = M.init(4)
    wids = [0, 2, 2, 3]
    stals = [0, 3, 1, 7]
    nnzs = [5, 5, 9, 1]
    mags = [0.0, 2.5, 0.1, 40.0]
    for w, s, n, g in zip(wids, stals, nnzs, mags):
        ms_seq = M.update(ms_seq, jnp.int32(w), jnp.int32(s), jnp.int32(n),
                          jnp.int32(n), jnp.float32(g))
    ms_bat = M.update(M.init(4), jnp.asarray(wids, jnp.int32),
                      jnp.asarray(stals, jnp.int32),
                      jnp.asarray(nnzs, jnp.int32),
                      jnp.asarray(nnzs, jnp.int32),
                      jnp.asarray(mags, jnp.float32))
    assert M.drain(ms_seq) == M.drain(ms_bat)
    d = M.drain(ms_seq)
    assert d["n_events"] == 4
    assert d["per_worker"] == [1, 0, 2, 1]
    assert sum(d["staleness_hist"]["counts"]) == 4
    # the exact-zero magnitude landed in the reserved bin 0
    assert d["update_mag_hist"]["counts"][0] == 1


def test_route_overflow_accumulates_and_drains():
    """The overflow counter sums scalar and batched contributions across
    updates and drains as a plain int — zero when never fed."""
    ms = M.init(2)
    assert M.drain(ms)["route_overflow"] == 0
    ms = M.update(ms, jnp.int32(0), jnp.int32(0), jnp.int32(1),
                  jnp.int32(1), jnp.float32(1.0), overflow=jnp.int32(3))
    ms = M.update(ms, jnp.asarray([0, 1], jnp.int32),
                  jnp.asarray([0, 0], jnp.int32),
                  jnp.asarray([1, 1], jnp.int32),
                  jnp.asarray([1, 1], jnp.int32),
                  jnp.asarray([1.0, 1.0], jnp.float32),
                  overflow=jnp.asarray([2, 5], jnp.int32))
    assert M.drain(ms)["route_overflow"] == 10


def test_summarize_log2_is_the_host_twin():
    vals = [0, 1, 5, 100, 1000, 1000, 2 ** 20]
    ms = M.init(1)
    for v in vals:
        ms = M.update(ms, jnp.int32(0), jnp.int32(v), jnp.int32(0),
                      jnp.int32(0), jnp.float32(1.0))
    assert M.drain(ms)["staleness_hist"] == M.summarize_log2(vals)


def test_hist_dict_trims_trailing_zeros():
    h = M.hist_dict([0, 3, 0, 1, 0, 0])
    assert h["counts"] == [0, 3, 0, 1]
    assert len(h["bins"]) == 4


# ---------------------------------------------------------------------------
# trace: recorder artifacts
# ---------------------------------------------------------------------------

def test_recorder_writes_parseable_artifacts(tmp_path):
    with telemetry.Recorder(tmp_path) as rec:
        with rec.span("phase/a", detail=1):
            pass
        with rec.span("marker"):
            pass
        rec.event("progress", event=1, loss=0.5)
        rec.count("client/0/retries")
        rec.count("client/0/retries")
    trace = json.loads((tmp_path / "trace.json").read_text())
    names = [e["name"] for e in trace["traceEvents"]]
    assert "phase/a" in names and "marker" in names
    span = next(e for e in trace["traceEvents"] if e["name"] == "phase/a")
    assert span["ph"] == "X" and span["dur"] >= 0
    lines = [json.loads(line) for line in
             (tmp_path / "events.jsonl").read_text().splitlines()]
    kinds = [e["kind"] for e in lines]
    assert kinds == ["progress", "counters"]
    assert lines[-1]["counters"] == {"client/0/retries": 2}


def test_null_recorder_is_free_and_writes_nothing():
    rec = telemetry.NULL
    assert not rec.enabled
    with rec.span("x"):
        pass
    rec.event("y", z=1)
    rec.count("c")
    assert rec.flush() == []
    assert rec.counters == {}


def test_recorder_spans_land_in_the_profiler_trace(tmp_path):
    """Each span also enters a profiler annotation of its name and
    arguments, so it lands on the device trace's clock; NULL's do not."""
    import jax
    from jax.profiler import ProfileData

    rec = telemetry.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("batched/client", batch=3, size=8):
            with rec.span("batched/inner"):
                jnp.ones(4).block_until_ready()
        with telemetry.NULL.span("null/span"):
            pass
    finally:
        jax.profiler.stop_trace()
    prof = ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    host = {ev.name: (ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in prof.planes for line in plane.lines
            for ev in line.events}
    outer, inner = host["batched/client"], host["batched/inner"]
    assert outer[2] == {"batch": 3, "size": 8}
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert "null/span" not in host
    assert [ev["name"] for ev in rec._trace] == ["batched/inner",
                                                 "batched/client"]


def test_counting_programs_counts_a_fresh_jit_and_null_counts_nothing():
    import jax
    from jax._src import monitoring

    def square_plus_x(x):      # lax alone: jnp's operators are jits
        return jax.lax.add(jax.lax.mul(x, x), x)

    x, y, z = jnp.arange(3.0), jnp.arange(4.0), jnp.arange(5.0)
    listeners = list(monitoring._event_duration_secs_listeners)
    with telemetry.NULL.counting_programs():
        jax.jit(square_plus_x)(x).block_until_ready()
    assert telemetry.NULL.counters == {}
    assert list(monitoring._event_duration_secs_listeners) == listeners

    rec = telemetry.Recorder()
    with rec.counting_programs():
        jax.jit(square_plus_x)(y).block_until_ready()
    assert rec.counters == {"programs/traced/square_plus_x": 1,
                            "programs/compiled/jit(square_plus_x)": 1}
    jax.jit(square_plus_x)(z).block_until_ready()   # not counting
    assert sum(rec.counters.values()) == 2


# ---------------------------------------------------------------------------
# layers: device time by layer scope, idle by host span
# ---------------------------------------------------------------------------

def test_layer_of_takes_the_innermost_layer_past_transform_wrappers():
    from repro.telemetry.layers import layer_of

    assert layer_of("jit(run)/vmap(transpose(jvp(layer.grad)))/dot") \
        == "layer.grad"
    assert layer_of("jit(run)/vmap(layer.strategy)/layer.select/sort") \
        == "layer.select"
    assert layer_of("jit(server_batch)/layer.receive/while/body/"
                    "closed_call/sub") == "layer.receive"
    assert layer_of("jit(run)/jvp()/gather") == "unscoped"
    assert layer_of("") == "unscoped"
    assert layer_of("jit(f)/player.grad/add") == "unscoped"


def test_layers_reduce_hand_made():
    """Nested scopes and nested program spans: the innermost wins, and
    the ``unscoped`` and ``none`` buckets fill."""
    import pytest
    from repro.telemetry import layers

    s = 1_000_000   # 1 ms in ns
    host = [[layers.WINDOW, 0, 20 * s, ""],
            ["batched/init", 0, 2 * s, ""],
            ["batched/client", 3 * s, 9 * s, ""],
            ["batched/inner", 4 * s, 6 * s, ""],
            ["PjitFunction(run)", 4 * s, 5 * s, ""],   # not a program span
            ["batched/apply", 15 * s, 25 * s, ""]]      # clipped at 20
    ops = [["%while.1", 2 * s, 3 * s, "jit(f)/layer.receive/while"],
           # a loop holding a selection: the nested op is select's
           ["%while.2", 7 * s, 10 * s, "jit(g)/vmap(layer.strategy)/while"],
           ["%sort.3", 8 * s, 9 * s,
            "jit(g)/vmap(layer.strategy)/layer.select/sort"],
           ["%copy.4", 12 * s, 13 * s, "jit(h)/copy"],
           ["%add.5", 16 * s, 22 * s, "jit(k)/transpose(jvp(layer.grad))/add"]]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": ops}]}]
    red = layers.reduce(planes)
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.009)  # [2,3] [7,10] [12,13] [16,20]
    assert red["scopes"] == pytest.approx({
        "layer.receive": 0.001, "layer.strategy": 0.002,
        "layer.select": 0.001, "unscoped": 0.001, "layer.grad": 0.004})
    # idle: [0,2] init; [3,7] client, of which [4,6] inner; [10,12]
    # none; [13,15] none; [15,16] apply
    assert red["span_idle"] == pytest.approx({
        "batched/init": 0.002, "batched/client": 0.002,
        "batched/inner": 0.002, "batched/apply": 0.001, "none": 0.004})
    with pytest.raises(ValueError):
        layers.reduce(planes, window="no-such-window")


def _pb(*fields):
    """Protobuf wire bytes of ``(field, value)`` pairs: an int is a
    varint, bytes/str/a nested list a length-delimited field."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += varint(field << 3) + varint(value)
        else:
            if isinstance(value, str):
                value = value.encode()
            elif isinstance(value, list):
                value = _pb(*value)
            out += varint(field << 3 | 2) + varint(len(value)) + value
    return out


def test_layers_load_reads_op_names_from_program_hlo(tmp_path):
    """A trace laid out as the TPU profiler writes one: a program's
    optimized HLO on the ``/host:metadata`` plane, device ops naming
    their program and instruction.  A fusion the compiler made takes its
    op_name from the HLO (through its fused computation's caller), an op
    with no program from its ``tf_op`` stat; the window's spans name the
    idle gap."""
    import jax
    import pytest
    from repro.telemetry import layers

    def f(x):
        with jax.named_scope("layer.grad"):
            y = jnp.sin(x) * 2
        with jax.named_scope("layer.select"):
            return jax.lax.top_k(y, 3)[0]

    compiled = jax.jit(f).lower(jnp.ones(16)).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    hlo = _pb((1, module.as_serialized_hlo_module_proto()))
    fusions = [n for n in re.findall(r"^\s*%?(\S*fusion\S*) = ",
                                     compiled.as_text(), re.M)
               if "multiply" in n]        # the sin(x) * 2 fusion
    assert fusions

    def meta(i, name, display, stats):
        return (4, [(1, i), (2, [(1, i), (2, name), (4, display),
                                 *[(5, st) for st in stats]])])

    def stat_meta(i, name):
        return (5, [(1, i), (2, [(1, i), (2, name)])])

    def event(i, start_ns, end_ns):
        return (4, [(1, i), (2, start_ns * 1000),
                    (3, (end_ns - start_ns) * 1000)])

    host_meta = [(2, "/host:metadata"), stat_meta(1, "Hlo Proto"),
                 meta(1, "jit_f(77)", "", [[(1, 1), (6, hlo)]])]
    device = [(2, "/device:TPU:0"), stat_meta(1, "program_id"),
              stat_meta(2, "tf_op"),
              meta(1, f"%{fusions[0]} = f32[16] fusion(%x)", fusions[0],
                   [[(1, 1), (3, 77)]]),
              meta(2, "%copy.9 = f32[4] copy(%p)", "copy.9",
                   [[(1, 2), (5, "jit(g)/layer.apply/copy:")]]),
              meta(3, "%iota.1 = s32[2] iota()", "iota.1", []),
              (3, [(2, "XLA Ops"), (3, 0), event(1, 10, 30),
                   event(2, 30, 40), event(3, 60, 70)])]
    host = [(2, "/host:CPU"), meta(1, layers.WINDOW, "", []),
            meta(2, "batched/client", "", []),
            (3, [(2, "python3"), (3, 0), event(1, 0, 100),
                 event(2, 5, 50)])]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host_meta), (1, device), (1, host)))

    names = layers.op_names(path.read_bytes())["/device:TPU:0"]
    assert layers.layer_of(names[f"%{fusions[0]} = f32[16] fusion(%x)"]) \
        == "layer.grad"
    assert names["%copy.9 = f32[4] copy(%p)"] == "jit(g)/layer.apply/copy"
    assert "%iota.1 = s32[2] iota()" not in names
    red = layers.reduce(layers.load(path))
    assert sum(red["scopes"].values()) == pytest.approx(40e-9)
    assert red["scopes"]["layer.apply"] == pytest.approx(10e-9)
    assert red["scopes"]["unscoped"] == pytest.approx(10e-9)
    # idle [0,10], [40,60], [70,100]; the span holds [5,10] and [40,50]
    assert red["span_idle"] == pytest.approx(
        {"batched/client": 15e-9, "none": 45e-9})


def test_innermost_spans_cut_the_window():
    from repro.telemetry.layers import innermost, overlap

    pieces = innermost([("a/x", 2, 8), ("a/y", 3, 5), ("a/z", 5, 6)], 0, 10)
    assert pieces == [(0, 2, "none"), (2, 3, "a/x"), (3, 5, "a/y"),
                      (5, 6, "a/z"), (6, 8, "a/x"), (8, 10, "none")]
    got = overlap([(1, 4), (7, 9)], pieces)
    assert got == {k: v * 1e-9 for k, v in
                   {"none": 2, "a/x": 2, "a/y": 1}.items()}


# ---------------------------------------------------------------------------
# logs: bare-message stdout + recorder mirroring
# ---------------------------------------------------------------------------

def test_logger_prints_bare_messages_and_mirrors_to_recorder(capsys):
    log = telemetry.get_logger("test")
    rec = telemetry.Recorder()
    telemetry.set_recorder(rec)
    try:
        log.info("[test] hello %d", 7)
    finally:
        telemetry.set_recorder(None)
    assert capsys.readouterr().out == "[test] hello 7\n"
    mirrored = [json.loads(line) for line in rec._jsonl]
    assert mirrored and mirrored[0]["kind"] == "log"
    assert mirrored[0]["msg"] == "[test] hello 7"
    assert mirrored[0]["logger"] == "test"


def test_log_level_silences(capsys):
    log = telemetry.get_logger("test")
    telemetry.set_level("warning")
    try:
        log.info("[test] chatter")
        log.warning("[test] kept")
    finally:
        telemetry.set_level("info")
    assert capsys.readouterr().out == "[test] kept\n"


# ---------------------------------------------------------------------------
# report: render + --check gate
# ---------------------------------------------------------------------------

def _fake_run_dir(tmp_path):
    rec = telemetry.Recorder(tmp_path)
    with rec.span("coord/server_batch"):
        pass
    rec.event("progress", event=1, loss=1.0, up_bytes=100, down_bytes=80)
    rec.event("progress", event=2, loss=0.5, up_bytes=200, down_bytes=160)
    rec.event("run_summary", runner="cluster", n_events=2, up_bytes=200,
              down_bytes=160, loss_first=1.0, loss_last=0.5,
              staleness_hist=M.summarize_log2([0, 1]),
              up_bytes_hist=M.summarize_log2([100, 100]),
              down_bytes_hist=M.summarize_log2([80, 80]))
    rec.count("client/0/events", 2)
    rec.flush()
    return tmp_path


def _report(*args):
    return subprocess.run(
        [sys.executable, "scripts/report.py", *map(str, args)],
        cwd=REPO_ROOT, capture_output=True, text=True)


def test_report_renders_and_check_passes(tmp_path):
    run_dir = _fake_run_dir(tmp_path)
    proc = _report(run_dir, "--check")
    assert proc.returncode == 0, proc.stderr
    assert "Staleness distribution" in proc.stdout
    assert "Per-stage time breakdown" in proc.stdout
    assert "coord/server_batch" in proc.stdout
    assert "Per-client activity" in proc.stdout
    assert "report --check: OK" in proc.stdout


def test_report_check_fails_on_missing_or_corrupt(tmp_path):
    assert _report(tmp_path / "nope", "--check").returncode == 1
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "trace.json").write_text("{}")
    (bad / "events.jsonl").write_text("not json\n")
    assert _report(bad, "--check").returncode == 1
