"""Flight-recorder telemetry for every data-plane view (DESIGN.md §11).

Four pieces, importable as one package:

* :mod:`repro.telemetry.metrics` — in-graph :class:`MetricsState`: a
  fixed-shape pytree of counters + log2-bucketed histograms (staleness,
  up/down nnz, update magnitude, per-worker events) updated on device with
  zero host syncs and drained only at eval boundaries.
* :mod:`repro.telemetry.trace` — host-side :class:`Recorder`: Chrome
  trace-event / Perfetto spans (``trace.json``, and profiler annotations
  on the device trace's clock) plus a structured JSONL event log
  (``events.jsonl``) and a count of the programs built; :data:`NULL` is
  the free no-op default.  ``LAYER`` names the data plane's
  ``jax.named_scope`` per layer.
* :mod:`repro.telemetry.layers` — a profiler trace reduced to device
  time by layer scope and device idle by Recorder span.
* :mod:`repro.telemetry.logs` — the leveled ``log`` facility replacing
  bare prints in the launchers (bare-message stdout by default, one flag
  to silence or route).

The contract every runner honors: telemetry OFF is the untouched pre-
telemetry code path (identical compiled artifacts), telemetry ON changes
no data-plane bit (tests/test_async_sim.py::test_metrics_do_not_change_bits).
"""
from . import metrics
from .metrics import MetricsState
from .logs import get_logger, set_level, set_log_file, set_recorder
from .trace import LAYER, NULL, NullRecorder, Recorder

__all__ = [
    "metrics", "MetricsState",
    "Recorder", "NullRecorder", "NULL", "LAYER",
    "get_logger", "set_level", "set_log_file", "set_recorder",
]
