"""Host-side flight recorder: Chrome-trace/Perfetto spans + JSONL events,
and the names that put the data plane's layers on the device trace.

One :class:`Recorder` per run.  It buffers three things in memory and
writes them out on :meth:`close`:

* **spans** — ``with rec.span("coord/server_batch"): ...`` records a
  complete ("ph": "X") Chrome trace event with microsecond timestamps;
  the whole buffer serializes to ``trace.json`` in the Chrome
  trace-event format, loadable by Perfetto / chrome://tracing.  Spans
  measure HOST wall-clock between enter and exit — for jitted stages
  that is dispatch time (JAX dispatch is async); the recorder never
  inserts device syncs to "fix" that.  Each span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name and arguments, so
  under the JAX profiler it lands in the ``.xplane.pb`` on the same
  clock as the device's ops (a no-op costing about a microsecond when
  no profiler runs).
* **events** — ``rec.event("run_summary", n_events=..., ...)`` appends one
  structured record to ``events.jsonl`` (one JSON object per line, each
  stamped with seconds-since-recorder-start ``t`` and a ``kind``).
* **counters** — ``rec.count("client/3/drops")`` bumps a named counter;
  the full counter map is flushed as a final ``{"kind": "counters"}``
  JSONL record so reports can build per-client tables.  Inside
  ``with rec.counting_programs():`` every program JAX traces or compiles
  is counted as ``programs/traced/<fun_name>`` and
  ``programs/compiled/<fun_name>``, from one ``jax.monitoring``
  listener registered on first use.

All methods are thread-safe (the cluster runtime records from coordinator
and client threads) and cheap enough to leave in hot host loops; the
module-level :data:`NULL` recorder turns every call into a no-op (and
registers no listener) so runners can thread one object through
unconditionally.

:data:`LAYER` names the ``jax.named_scope`` each layer of the parameter
server's data plane runs under (client gradient, strategy, selection,
wire quantization, server receive, commit, apply).  The names reach the
compiled ops' ``op_name`` metadata, so a device trace splits a stage
program's time by layer; scopes change metadata only, never the
compiled arithmetic.
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import threading
import time
from typing import NamedTuple

import jax

TRACE_FILE = "trace.json"
EVENTS_FILE = "events.jsonl"


class _Layers(NamedTuple):
    grad: str = "layer.grad"            # the model's loss and gradient
    strategy: str = "layer.strategy"    # velocity, rescale, residuals
    select: str = "layer.select"        # top-k selection, every engine
    quantize: str = "layer.quantize"    # wire value quantization
    receive: str = "layer.receive"      # server M update and M - v_k
    commit: str = "layer.commit"        # v_k bookkeeping of a shipped G
    apply: str = "layer.apply"          # worker theta <- theta + G


# Each name differs from every primitive and function name, so it
# matches as a whole component of an op's scope path.
LAYER = _Layers()

# jax.monitoring duration events counted by Recorder.counting_programs
_PROGRAM_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "programs/traced/",
    "/jax/core/compile/backend_compile_duration": "programs/compiled/",
}
_counting = None            # the recorder program builds are counted into
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    rec, prefix = _counting, _PROGRAM_EVENTS.get(event)
    if rec is not None and prefix is not None:
        rec.count(prefix + str(kwargs.get("fun_name", "?")))


class _Span:
    """Reusable span context; appends one complete event on exit."""

    __slots__ = ("rec", "name", "cat", "args", "t0", "ann")

    def __init__(self, rec, name, cat, args):
        self.rec, self.name, self.cat, self.args = rec, name, cat, args

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name,
                                                **(self.args or {}))
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.rec._complete(self.name, self.cat, self.t0, t1, self.args)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Recorder:
    """Buffering trace + JSONL recorder for one run."""

    enabled = True

    def __init__(self, run_dir: str | os.PathLike | None = None):
        self.run_dir = pathlib.Path(run_dir) if run_dir is not None else None
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._trace: list[dict] = []
        self._jsonl: list[str] = []
        self.counters: dict[str, float] = {}
        self._lock = threading.Lock()

    # -- spans -------------------------------------------------------------

    def span(self, name: str, cat: str = "run", **args) -> _Span:
        return _Span(self, name, cat, args or None)

    def _complete(self, name, cat, t0, t1, args):
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": round((t0 - self._t0) * 1e6, 3),
              "dur": round((t1 - t0) * 1e6, 3),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self._trace.append(ev)

    # -- structured events -------------------------------------------------

    def event(self, kind: str, **fields) -> None:
        rec = {"t": round(time.perf_counter() - self._t0, 6), "kind": kind}
        rec.update(fields)
        line = json.dumps(rec, default=str)
        with self._lock:
            self._jsonl.append(line)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    @contextlib.contextmanager
    def counting_programs(self):
        """Count into this recorder every program JAX traces or compiles
        inside the block, by function name; the previously counting
        recorder, if any, resumes after it."""
        global _counting, _listening
        with _listen_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                _listening = True
        prev, _counting = _counting, self
        try:
            yield self
        finally:
            _counting = prev

    # -- lifecycle ---------------------------------------------------------

    def flush(self) -> list[str]:
        """Write ``trace.json`` + ``events.jsonl`` under ``run_dir`` (no-op
        without one); returns the paths written."""
        if self.run_dir is None:
            return []
        with self._lock:
            if self.counters:
                rec = {"t": round(time.perf_counter() - self._t0, 6),
                       "kind": "counters", "counters": dict(self.counters)}
                self._jsonl.append(json.dumps(rec))
            trace = list(self._trace)
            lines = list(self._jsonl)
        self.run_dir.mkdir(parents=True, exist_ok=True)
        tpath = self.run_dir / TRACE_FILE
        tpath.write_text(json.dumps(
            {"traceEvents": trace, "displayTimeUnit": "ms"}))
        epath = self.run_dir / EVENTS_FILE
        epath.write_text("".join(line + "\n" for line in lines))
        return [str(tpath), str(epath)]

    def close(self) -> list[str]:
        return self.flush()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class NullRecorder(Recorder):
    """Every method a no-op; the default recorder threaded through hot
    loops so call sites need no ``if`` guards."""

    enabled = False

    def __init__(self):
        self.run_dir = None
        self.counters = {}

    def span(self, name, cat="run", **args):
        return _NULL_SPAN

    def event(self, kind, **fields):
        pass

    def count(self, name, n=1):
        pass

    def counting_programs(self):
        return _NULL_SPAN

    def flush(self):
        return []


NULL = NullRecorder()
