"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into plain data: a list
of planes, each with its lines, each line a list of ``[name, start_ns,
end_ns, ""]`` events (on a TPU an op's name is its HLO text).  ``reduce``
works on that data alone, so a small recorded trace checks it without a
chip.

Within the window (the host span named ``WINDOW``): the device's busy
time is the union of the intervals in which an XLA op ran, per chip and
averaged over the chips; device time is summed per XLA program (module),
and per op as self time (less the ops nested in it, as a loop's body ops
are); sort time is the self time of ops whose opcode or shape names a
sort or top-k; the idle gaps between busy intervals are each named by the
host event that overlaps the gap most on the thread that opened the
window (what the host was doing while the device waited).
"""
from __future__ import annotations

import re

WINDOW = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SORT_RE = re.compile(r"sort|top-?k", re.IGNORECASE)


def load(path) -> list[dict]:
    """Planes of an ``.xplane.pb`` as plain lists."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [[ev.name, int(ev.start_ns),
                                    int(ev.end_ns), ""]
                                   for ev in line.events]}
                       for line in plane.lines]}
            for plane in prof.planes]


def op_label(name: str) -> str:
    """``%sort.7 = (f32[6448,73728]{1,0:T(8,128)}, ...) sort(...)`` ->
    ``%sort.7 = (f32[6448,73728], ...) sort``: the op, its output shape
    without layouts (cut at 60 characters) and its opcode."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:100]
    m = re.search(r"\s([a-z][\w\-]*)\(", rest)
    if not m:
        return head
    shape = rest[:m.start()]
    while True:
        stripped = re.sub(r"\{[^{}]*\}", "", shape)
        if stripped == shape:
            break
        shape = stripped
    shape = re.sub(r"/\*index=\d+\*/", "", shape)
    if len(shape) > 60:
        shape = shape[:57] + "..."
    return f"{head} = {shape} {m.group(1)}"


def self_times(events) -> list[tuple[str, float]]:
    """``[(name, seconds)]`` of each event less the time of the events
    nested in it (a while loop's body ops are listed inside the loop)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []          # stack: [index into out, end]
    for name, s, e in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= (min(e, stack[-1][1]) - s) * 1e-9
        out.append([name, (e - s) * 1e-9])
        stack.append((len(out) - 1, e))
    return [(n, t) for n, t in out]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", name)


def window_span(planes) -> tuple[int, int, dict]:
    for plane in planes:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, s, e, _ in line["events"]:
                if name == WINDOW:
                    return s, e, line
    raise ValueError(f"no {WINDOW!r} span in the trace")


def reduce(planes, top: int = 10) -> dict:
    t0, t1, host_line = window_span(planes)
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and _line(p, OPS_LINE)]
    if not devices:
        raise ValueError("no device ops in the trace")
    busy, modules, ops, sort_s = [], {}, {}, 0.0
    gaps_dev0 = []
    for i, dev in enumerate(devices):
        iv = [(max(s, t0), min(e, t1), name)
              for name, s, e, _ in _line(dev, OPS_LINE)
              if min(e, t1) > max(s, t0)]
        for name, t in self_times([(n, s, e) for s, e, n in iv]):
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + t / len(devices)
            if SORT_RE.search(label):
                sort_s += t / len(devices)
        iv = [(s, e) for s, e, _ in iv]
        for name, s, e, _ in _line(dev, MODULES_LINE):
            s, e = max(s, t0), min(e, t1)
            if e > s:
                m = module_name(name)
                modules[m] = modules.get(m, 0.0) + (e - s) * 1e-9 / len(devices)
        u = union(iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        if i == 0:
            edges = [t0] + [x for se in u for x in se] + [t1]
            gaps_dev0 = [(edges[j], edges[j + 1])
                         for j in range(0, len(edges), 2)
                         if edges[j + 1] > edges[j]]
    busy_s = sum(busy) / len(busy)
    host = [(n, s, e) for n, s, e, _ in host_line["events"] if n != WINDOW]
    gaps = sorted(gaps_dev0, key=lambda g: g[0] - g[1])[:top]
    named = [[_host_doing(host, s, e), (e - s) * 1e-9] for s, e in gaps]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_s,
        "n_devices": len(devices),
        "modules": modules,
        "sort_s": sort_s,
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named,
        },
    }


def _host_doing(host, s, e) -> str:
    best, name = 0, "host idle"
    for n, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def module_seconds(red: dict, names) -> float | None:
    """Device seconds of the programs whose module name is in ``names``;
    None when none of them ran in the window."""
    hit = [v for k, v in red["modules"].items() if k in names]
    return sum(hit) if hit else None


def trim(planes, keep_s: float) -> list[dict]:
    """The first ``keep_s`` seconds of the window: the window span cut to
    that length, the device's op and module events and the window
    thread's host events that start in it."""
    t0, t1, host_line = window_span(planes)
    t1 = min(t1, t0 + int(keep_s * 1e9))
    host = [[WINDOW, t0, t1, ""]] + [
        ev for ev in host_line["events"]
        if ev[0] != WINDOW and t0 <= ev[1] < t1]
    out = [{"name": "/host:CPU",
            "lines": [{"name": host_line["name"], "events": host}]}]
    for p in planes:
        if p["name"].startswith(DEVICE_PREFIX):
            out.append({"name": p["name"], "lines": [
                {"name": ln, "events": [ev for ev in _line(p, ln)
                                        if t0 <= ev[1] < t1]}
                for ln in (MODULES_LINE, OPS_LINE)]})
    return out


def main(argv=None) -> None:
    """``python3 -m bench.trace_reduce <trace.xplane.pb> <out.json.gz>
    <seconds>``: keep the first seconds of a recorded window, with what
    the reduction reads from them, for the tests."""
    import gzip
    import json
    import sys

    src, dst, keep = (argv or sys.argv[1:])[:3]
    planes = trim(load(src), float(keep))
    red = reduce(planes)
    with gzip.open(dst, "wt") as f:
        json.dump({"planes": planes, "expect": {
            k: red[k] for k in ("busy_s", "sort_s", "modules")}}, f)


if __name__ == "__main__":
    main()
