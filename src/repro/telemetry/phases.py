"""Named phases of a loop, on the profiler's clock and in the registry.

``Phases(ns_counters, call_counters)`` times the phases of one owner's
loop (one ``ServeEngine`` step loop, say); the owner makes the registry
counters, one of each per phase name, as
``metrics.counter("<family>.phase_ns", phase=name)`` and
``metrics.counter("<family>.phase_calls", phase=name)``.
``with phases("serve.decode"): ...``

* opens a ``jax.profiler.TraceAnnotation`` of the phase's name: while a
  profiler session runs, that lands in the trace's host plane on the
  same clock as the device planes, so a device's idle gaps can be laid
  against the phase the host was in (nothing is recorded otherwise);
* adds the interval's ``perf_counter_ns`` and one call to the owner's
  ``ns`` / ``calls`` dicts and to the phase's two registry counters.

Always on, like every registry counter.  JAX is imported on the first
phase, so the telemetry package stays importable without it.
"""
from __future__ import annotations

import time
from typing import Dict

from . import metrics as _metrics

_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class Phases:
    """Per-owner phase totals (read from any thread: a phase's entry is
    written only by the owner's loop thread, each dict holds every name
    from construction on)."""

    def __init__(self, ns_counters: Dict[str, _metrics.Counter],
                 call_counters: Dict[str, _metrics.Counter]):
        self._counters = {n: (ns_counters[n], call_counters[n])
                          for n in ns_counters}
        self.ns: Dict[str, int] = dict.fromkeys(self._counters, 0)
        self.calls: Dict[str, int] = dict.fromkeys(self._counters, 0)

    def __call__(self, name: str) -> "_Phase":
        if name not in self._counters:
            raise KeyError(f"unknown phase {name!r}")
        return _Phase(self, name)

    def _add(self, name: str, ns: int) -> None:
        self.ns[name] += ns
        self.calls[name] += 1
        c_ns, c_calls = self._counters[name]
        c_ns.inc(ns)
        c_calls.inc()


class _Phase:
    __slots__ = ("_owner", "_name", "_ann", "_t0")

    def __init__(self, owner: Phases, name: str):
        self._owner = owner
        self._name = name

    def __enter__(self) -> None:
        self._ann = _trace_annotation()(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        self._owner._add(self._name, ns)
