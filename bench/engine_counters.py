"""The engine's own work counters over the window: ``gen.stats`` as the
benchmark snapshots it at the window's open and close, per replica.

A program that does not report a counter (one older than its phases and
work counters) gives ``None``, so that its metric falls silent."""
from __future__ import annotations

from typing import List, Optional


def deltas(run, key: str, phase: Optional[str] = None
           ) -> Optional[List[int]]:
    """Per replica, the change of ``gen.stats[key]`` (of its ``phase``
    entry, for the per-phase dicts) over the window."""
    out = []
    for a, b in zip(run.snap["stats0"], run.snap["stats1"]):
        va, vb = a.get(key), b.get(key)
        if phase is not None:
            va = va.get(phase) if isinstance(va, dict) else None
            vb = vb.get(phase) if isinstance(vb, dict) else None
        if va is None or vb is None:
            return None
        out.append(vb - va)
    return out
