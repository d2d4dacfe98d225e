"""Device time by data-plane layer, and device idle by host span, from a
JAX profiler trace (``.xplane.pb``).

    PYTHONPATH=src python -m repro.telemetry.layers TRACE.xplane.pb \
        [--window bench_window] [--events N]

``load`` turns the trace into plain data: planes of lines of ``[name,
start_ns, end_ns, scope]`` events, where ``scope`` is a device op's
scope path (its HLO ``op_name``) and ``""`` elsewhere.  The trace keeps
op names in event metadata and programs' HLO, which
``jax.profiler.ProfileData`` does not expose, so ``op_names`` reads them
from the protobuf wire format; ops the compiler made without one (the
TPU's custom fusions) take their operands' or their loop's.  ``reduce``
works on the plain data alone, within the window (the host span named
``window``):

* ``scopes`` — device self seconds (less the ops nested in an op, as a
  loop's body ops are) by the innermost ``layer.*`` component of each
  op's scope path (:data:`repro.telemetry.trace.LAYER`), after JAX's
  transform wrappers such as ``vmap(...)`` or ``transpose(jvp(...))``
  are stripped; ``unscoped`` for ops under no layer;
* ``span_idle`` — device idle seconds by the innermost program span (a
  ``/``-named :class:`~repro.telemetry.trace.Recorder` span, which enters
  a profiler annotation of its name) open on the window's thread at the
  time; ``none`` where no span is open.

Seconds are averaged over the trace's devices.  The bench harness keeps
a traced window's trace with ``BENCH_KEEP_TRACE=<file>``.  JAX's
persistent compilation cache keys programs without their metadata, so
an executable cached from a build without these scopes shows none:
trace with ``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1`` (or no
cache) to see them.
"""
from __future__ import annotations

import re

WINDOW = "bench_window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
OP_NAME_STAT = "tf_op"
LAYER_RE = re.compile(r"^layer\.\w+$")
WRAPPER_RE = re.compile(r"^[\w.-]+\((.*)\)$")
SPAN_RE = re.compile(r"^\w+(/\w+)+$")


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """``(field number, value)`` of each field of one protobuf message in
    ``buf[pos:end]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        else:
            pos += {1: 8, 5: 4}[kind]
            continue
        yield key >> 3, value


def _packed(buf, value) -> list[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, pos = [], value[0]
    while pos < value[1]:
        x, pos = _varint(buf, pos)
        out.append(x)
    return out


def _hlo_op_names(buf, span) -> dict[str, str]:
    """``{instruction name: op_name}`` of one serialized ``HloProto``
    (``xla/service/hlo.proto``: module 1; its computations 3; a
    computation's instructions 2 and id 5; an instruction's name 1,
    metadata 7 (op_name 2), id 35, operand_ids 36,
    called_computation_ids 38).  An instruction the compiler made
    without an op_name takes its first operand's, else its caller's."""
    text = _text(buf)
    module = next(v for f, v in _fields(buf, *span) if f == 1)
    comps = []                   # [(id, [instruction dicts])]
    for f, v in _fields(buf, *module):
        if f != 3:
            continue
        cid, instrs = None, []
        for g, w in _fields(buf, *v):
            if g == 5:
                cid = w
            elif g == 2:
                ins = {"op_name": "", "operands": [], "calls": []}
                for h, x in _fields(buf, *w):
                    if h == 1:
                        ins["name"] = text(x)
                    elif h == 35:
                        ins["id"] = x
                    elif h == 7:
                        ins["op_name"] = next(
                            (text(y) for k, y in _fields(buf, *x) if k == 2),
                            "")
                    elif h == 36:
                        ins["operands"] += _packed(buf, x)
                    elif h == 38:
                        ins["calls"] += _packed(buf, x)
                instrs.append(ins)
        comps.append((cid, instrs))
    by_id = {ins["id"]: ins for _, instrs in comps for ins in instrs}
    caller = {c: ins for _, instrs in comps for ins in instrs
              for c in ins["calls"]}
    for _, instrs in comps:      # operands come before their users
        for ins in instrs:
            if not ins["op_name"]:
                ins["op_name"] = next(
                    (by_id[o]["op_name"] for o in ins["operands"]
                     if o in by_id and by_id[o]["op_name"]), "")
    for cid, instrs in reversed(comps):   # callers come after callees
        up = caller.get(cid)
        for ins in instrs:
            if not ins["op_name"] and up is not None:
                ins["op_name"] = up["op_name"]
    return {ins["name"]: ins["op_name"] for _, instrs in comps
            for ins in instrs}


def _text(buf):
    return lambda span: bytes(buf[span[0]:span[1]]).decode("utf-8",
                                                          "replace")


def op_names(data: bytes) -> dict[str, dict[str, str]]:
    """``{device plane: {event name: op_name}}`` from a serialized
    ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``: an ``XPlane``'s
    name is field 2, its ``event_metadata`` map 4, its ``stat_metadata``
    map 5; an ``XEventMetadata``'s name 2, display name 4 and stats 5;
    an ``XStat``'s metadata id 1, uint64 3, string 5, bytes 6).

    An op's op_name comes from its program's optimized HLO, which the
    ``/host:metadata`` plane keeps per program (``jit_<name>(<program
    id>)``, stat ``Hlo Proto``), found by the op's ``program_id`` stat
    and display name; else from its ``tf_op`` stat (``<op_name>:``)."""
    buf = memoryview(data)
    text = _text(buf)

    def entry(span):             # a map entry: key 1, value 2
        return dict(_fields(buf, *span)).get(2)

    planes = []
    for field, plane in _fields(buf, 0, len(buf)):
        if field == 1:
            parts = list(_fields(buf, *plane))
            name = next((text(v) for f, v in parts if f == 2), "")
            stats = {}
            for f, v in parts:
                if f == 5:
                    meta = dict(_fields(buf, *entry(v)))
                    stats[text(meta[2])] = meta.get(1)
            planes.append((name, parts, stats))
    hlo = {}                     # program id -> {instruction: op_name}
    for name, parts, stats in planes:
        if name != "/host:metadata" or "Hlo Proto" not in stats:
            continue
        for f, v in parts:
            if f != 4:
                continue
            meta = list(_fields(buf, *entry(v)))
            prog = next((text(w) for g, w in meta if g == 2), "")
            pid = re.search(r"\((\d+)\)$", prog)
            for g, w in meta:
                if g == 5 and pid:
                    stat = dict(_fields(buf, *w))
                    if stat.get(1) == stats["Hlo Proto"] and 6 in stat:
                        hlo[int(pid.group(1))] = _hlo_op_names(buf, stat[6])
    out = {}
    for name, parts, stats in planes:
        if not name.startswith(DEVICE_PREFIX):
            continue
        names = {}
        for f, v in parts:
            if f != 4:
                continue
            ev_name, display, st = "", "", {}
            for g, w in _fields(buf, *entry(v)):
                if g == 2:
                    ev_name = text(w)
                elif g == 4:
                    display = text(w)
                elif g == 5:
                    stat = dict(_fields(buf, *w))
                    st[stat.get(1)] = stat
            prog = st.get(stats.get("program_id"), {}).get(3)
            op_name = hlo.get(prog, {}).get(display, "")
            tf_op = st.get(stats.get(OP_NAME_STAT), {}).get(5)
            if not op_name and tf_op:
                op_name = text(tf_op).rpartition(":")[0]
            if op_name and ev_name:
                names.setdefault(ev_name, op_name)
        out[name] = names
    return out


def load(path) -> list[dict]:
    """Planes of an ``.xplane.pb`` as plain lists."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    scopes = op_names(data)
    prof = ProfileData.from_serialized_xspace(data)
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [[ev.name, int(ev.start_ns),
                                    int(ev.end_ns),
                                    scopes.get(plane.name, {}).get(
                                        ev.name, "")]
                                   for ev in line.events]}
                       for line in plane.lines]}
            for plane in prof.planes]


def layer_of(path: str) -> str:
    """The innermost ``layer.*`` component of a scope path, or
    ``unscoped``: ``jit(run)/vmap(transpose(jvp(layer.grad)))/dot`` ->
    ``layer.grad``."""
    for part in reversed(path.split("/")):
        m = WRAPPER_RE.match(part)
        while m:
            part = m.group(1)
            m = WRAPPER_RE.match(part)
        if LAYER_RE.match(part):
            return part
    return "unscoped"


def self_times(events) -> list[tuple[str, float]]:
    """``[(key, seconds)]`` of each ``(key, start, end)`` event less the
    time of the events nested in it."""
    out, stack = [], []          # stack: [index into out, end]
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= (min(e, stack[-1][1]) - s) * 1e-9
        out.append([key, (e - s) * 1e-9])
        stack.append((len(out) - 1, e))
    return [(k, t) for k, t in out]


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def innermost(spans, t0: int, t1: int) -> list[tuple[int, int, str]]:
    """``[t0, t1]`` cut into ``(start, end, name)`` pieces, each named by
    the innermost of the nested ``(name, start, end)`` spans open over
    it, or ``none``."""
    out, stack, t = [], [], t0   # stack: [(end, name)]

    def emit(upto):
        nonlocal t
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack else "none"))
            t = upto

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(t1)
    return out


def overlap(gaps, pieces) -> dict[str, float]:
    """Seconds of the sorted, disjoint ``gaps`` under each name of the
    sorted, disjoint ``pieces``."""
    out: dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, name = pieces[k]
            ov = min(ge, pe) - max(gs, ps)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov * 1e-9
            k += 1
    return out


def _window(planes, window: str):
    for plane in planes:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, s, e, _ in line["events"]:
                if name == window:
                    return s, e, line["events"]
    raise ValueError(f"no {window!r} span in the trace")


def reduce(planes, window: str = WINDOW) -> dict:
    t0, t1, host = _window(planes, window)
    devices = [[ev for line in p["lines"] if line["name"] == OPS_LINE
                for ev in line["events"]]
               for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    devices = [ops for ops in devices if ops]
    if not devices:
        raise ValueError("no device ops in the trace")
    spans = [(n, max(s, t0), min(e, t1)) for n, s, e, _ in host
             if SPAN_RE.match(n) and min(e, t1) > max(s, t0)]
    pieces = innermost(spans, t0, t1)
    share = 1.0 / len(devices)
    scopes: dict[str, float] = {}
    span_idle = dict.fromkeys([n for n, _, _ in spans] + ["none"], 0.0)
    busy = 0.0
    for ops in devices:
        iv = [(layer_of(path), max(s, t0), min(e, t1))
              for _, s, e, path in ops if min(e, t1) > max(s, t0)]
        for layer, t in self_times(iv):
            scopes[layer] = scopes.get(layer, 0.0) + t * share
        u = union([(s, e) for _, s, e in iv])
        busy += sum(e - s for s, e in u) * 1e-9 * share
        edges = [t0] + [x for se in u for x in se] + [t1]
        gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                if edges[j + 1] > edges[j]]
        for name, t in overlap(gaps, pieces).items():
            span_idle[name] += t * share
    return {"window_s": (t1 - t0) * 1e-9, "busy_s": busy,
            "scopes": scopes, "span_idle": span_idle}


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--window", default=WINDOW)
    ap.add_argument("--events", type=int, default=0,
                    help="events in the window: adds ms per event")
    args = ap.parse_args(argv)
    red = reduce(load(args.trace), args.window)
    busy, n = red["busy_s"], args.events
    print(f"window {red['window_s']:.6f} s, device busy {busy:.6f} s")
    print("device time by layer: seconds, share of busy"
          + (", ms per event" if n else ""))
    for k, t in sorted(red["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"  {k:16} {t:.6f} {100 * t / busy:.3f}%"
              + (f" {1e3 * t / n:.6f}" if n else ""))
    print("device idle by innermost span: seconds, share of window")
    for k, t in sorted(red["span_idle"].items(), key=lambda kv: -kv[1]):
        if t:
            print(f"  {k:24} {t:.6f} {100 * t / red['window_s']:.4f}%")


if __name__ == "__main__":
    main()
