"""Fabric telemetry plane (DESIGN.md §10, docs/OPERATIONS.md §7).

Two halves, both process-global and dependency-free:

* :mod:`repro.telemetry.trace` — wire-propagated distributed tracing:
  a 16-byte trace id + span id + flags carried in the v5 request
  header, head-sampled at the root, recorded into a bounded ring
  buffer served by the ``dbg.trace`` RPC.
* :mod:`repro.telemetry.metrics` — the unified metrics registry
  (counters / gauges / log-bucket histograms) that the fabric's
  components report through, exported by the ``fab.metrics`` RPC and
  rendered live by ``tools/fabtop.py``.

:mod:`repro.telemetry.phases` names the phases of a loop: each phase is
a profiler annotation, on the device trace's clock, and a pair of
registry counters.
"""
from . import metrics, phases, trace
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      counter, gauge, histogram, snapshot)
from .phases import Phases
from .trace import (FLAG_SAMPLED, NULL_SPAN, Span, TraceContext,
                    ZERO_TRACE_ID, build_tree, configure, current,
                    format_tree, start_span, start_trace, use)

__all__ = [
    "metrics", "phases", "trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Phases", "REGISTRY",
    "counter", "gauge", "histogram", "snapshot",
    "FLAG_SAMPLED", "NULL_SPAN", "Span", "TraceContext", "ZERO_TRACE_ID",
    "build_tree", "configure", "current", "format_tree", "start_span",
    "start_trace", "use",
]
