"""Idle gaps on the device by the engine's step phase the host was in.

The engine names the phases of its step loop (``repro.serve.engine``'s
``PHASES``): each is a ``serve.*`` profiler annotation in the trace's
host plane, on the device planes' clock.  Each idle gap between
operations on a chip (``bench/trace_reduce.py``'s gaps) goes to the
inner phase that overlaps it most, else to ``serve.step``, else to
``outside_step``; so the labels sum to the same idle time as
``trace_reduce.reduce``'s ``gaps``.

The same trace shows how far the clocks agree: ``decode_in_phase``
counts the ``jit_decode_step`` executions that lie wholly inside a
``serve.decode`` annotation, of all of them, and gives the least and the
median margin (µs) between each execution's start and end and those of
the annotation around its midpoint; a negative margin is an execution
that seems to start before its own dispatch, or to end after the host
saw its result.  Where minus the least start margin is below the least
end margin, one constant shift of the device planes in that range puts
every such execution inside its phase: the planes then differ by a
constant offset, not by drift.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

from bench import trace_reduce as tr

PREFIX = "serve."
STEP = "serve.step"
OUTSIDE = "outside_step"
DECODE_PHASE = "serve.decode"
DECODE_MODULE = "jit_decode_step"


def phase_events(planes) -> Dict[str, List[List[float]]]:
    """The host's ``serve.*`` annotations: per name, merged intervals."""
    by_name: Dict[str, list] = defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    by_name[e.name].append(
                        (float(e.start_ns),
                         float(e.start_ns + e.duration_ns)))
    return {k: tr.union(v) for k, v in by_name.items()}


def label(s: float, e: float, phases) -> str:
    """The inner phase that overlaps the gap ``[s, e)`` most, else the
    step, else outside it."""
    ov = {k: tr._overlap(v, s, e) for k, v in phases.items()}
    inner = {k: v for k, v in ov.items() if v > 0 and k != STEP}
    if inner:
        return max(inner, key=inner.get)
    return STEP if ov.get(STEP, 0.0) > 0 else OUTSIDE


def _around(merged: List[List[float]], t: float):
    """The interval of ``merged`` that holds ``t``, else None."""
    i = bisect.bisect_right(merged, [t, float("inf")]) - 1
    return merged[i] if i >= 0 and merged[i][1] >= t else None


def _margins(starts: List[float], ends: List[float]) -> Dict:
    def least_and_median(v):
        v = sorted(v)
        return [v[0] * 1e-3, v[len(v) // 2] * 1e-3] if v else None
    return {"start_margin_us": least_and_median(starts),
            "end_margin_us": least_and_median(ends)}


def reduce_planes(planes) -> Dict:
    """Over all chips: idle seconds by phase, and the decode steps
    inside ``serve.decode``."""
    planes = list(planes)
    phases = phase_events(planes)
    decode = phases.get(DECODE_PHASE, [])
    gaps: Dict[str, float] = defaultdict(float)
    inside = total = 0
    starts: List[float] = []
    ends: List[float] = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = tr._events(plane, tr.OPS_LINE)
        if not ops:
            continue
        busy = tr.union((s, s + d) for _, s, d in ops)
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            gaps[label(e0, s1, phases)] += (s1 - e0) * 1e-9
        for name, s, d in tr._events(plane, tr.MODULES_LINE):
            mod = name.split("(")[0]
            if mod == DECODE_MODULE or mod.startswith(DECODE_MODULE + "."):
                total += 1
                ph = _around(decode, s + d / 2)
                if ph is None:
                    continue
                starts.append(s - ph[0])
                ends.append(ph[1] - s - d)
                inside += starts[-1] >= 0 and ends[-1] >= 0
    return {"phase_gaps": dict(gaps),
            "decode_in_phase": dict(inside=inside, total=total,
                                    **_margins(starts, ends))}


def reduce(path: Path) -> Dict:
    """``reduce_planes`` of the trace at ``path`` (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)
