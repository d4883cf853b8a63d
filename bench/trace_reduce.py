"""From a profiler trace (``.xplane.pb``) to device numbers.

Each chip is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` line
holds one event per operation run on the device, its ``XLA Modules``
line one event per executable run (``jit_<function>...``).  Busy time is
the union of the operation intervals; an idle gap is time between them.
What the host was doing in each gap comes from the host's annotations:
JAX's own ``PjitFunction(<function>)`` around each dispatch, and the
benchmark's ``bench.*`` (``bench.step`` around each engine step).
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "PjitFunction(")
STEP = "bench.step"


def find_xplane(log_dir: Path) -> Optional[Path]:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
    return []


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_events(planes) -> Dict[str, List[List[float]]]:
    """The host's annotations that label gaps: per name, merged
    intervals."""
    by_name: Dict[str, list] = defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIXES):
                    by_name[e.name].append(
                        (float(e.start_ns),
                         float(e.start_ns + e.duration_ns)))
    return {k: union(v) for k, v in by_name.items()}


def _overlap(merged: List[List[float]], s: float, e: float) -> float:
    i = bisect.bisect_right(merged, [s, float("inf")]) - 1
    total = 0.0
    for hs, he in merged[max(i, 0):]:
        if hs >= e:
            break
        total += max(0.0, min(e, he) - max(s, hs))
    return total


def _label_gap(s: float, e: float, host) -> str:
    """The host annotation that overlaps the gap most: the benchmark's
    own inner ones first, then a dispatch, then the whole step."""
    ov = {k: _overlap(v, s, e) for k, v in host.items()}
    ov = {k: v for k, v in ov.items() if v > 0}
    inner = {k: v for k, v in ov.items()
             if k.startswith("bench.") and k != STEP}
    dispatch = {k: v for k, v in ov.items()
                if k.startswith("PjitFunction(")}
    pick = inner or dispatch or ov
    return max(pick, key=pick.get) if pick else "host_outside_engine_calls"


def _ops_by_module(ops, mods):
    """(``<executable>/<instruction>``, duration) for each operation, the
    executable being the module event that holds the operation's start
    (``?`` where none does)."""
    mods = sorted((s, s + d, n.split("(")[0]) for n, s, d in mods)
    starts = [m[0] for m in mods]
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        yield f"{mod}/{name.split(' = ')[0]}", d


def reduce(path: Path) -> Dict:
    """Per chip: busy seconds, the traced span, device seconds per
    executable and per operation; over all chips: idle gaps by what the
    host was doing."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    chips = [p for p in planes if p.name.startswith("/device:TPU:")]
    host = _host_events(planes)
    out = {"chips": {}, "ops": defaultdict(float),
           "gaps": defaultdict(float)}
    for plane in sorted(chips, key=lambda p: p.name):
        ops = _events(plane, OPS_LINE)
        mods = _events(plane, MODULES_LINE)
        if not ops:
            continue
        busy = union((s, s + d) for _, s, d in ops)
        modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for name, _, d in mods:
            m = modules[name.split("(")[0]]
            m[0] += 1
            m[1] += d * 1e-9
        for name, d in _ops_by_module(ops, mods):
            out["ops"][name] += d * 1e-9
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            out["gaps"][_label_gap(e0, s1, host)] += (s1 - e0) * 1e-9
        out["chips"][plane.name] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "first_ns": busy[0][0], "last_ns": busy[-1][1],
            "modules": {k: {"calls": v[0], "seconds": v[1]}
                        for k, v in modules.items()},
        }
    out["ops"] = dict(out["ops"])
    out["gaps"] = dict(out["gaps"])
    return out


def module_seconds(reduced: Dict, function: str) -> Tuple[int, float]:
    """Calls and device seconds of executable ``jit_<function>``, summed
    over chips."""
    calls, secs = 0, 0.0
    for chip in reduced["chips"].values():
        for name, m in chip["modules"].items():
            if name == f"jit_{function}" or name.startswith(
                    f"jit_{function}."):
                calls += m["calls"]
                secs += m["seconds"]
    return calls, secs


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
