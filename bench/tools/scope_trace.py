#!/usr/bin/env python3
"""A traced run of a cell with the device time of its decode step and
prefill chunk put on the program's name scopes.

    python3 bench/tools/scope_trace.py --workload granite3moe.doc_qa \\
        --seed 7 --seconds 40

It runs the cell as ``bench/run.py --trace 1`` does, and keeps the
compiled text of every engine's decode step and prefill chunk (lowered
at their first call; the compile cache serves the second compile).  Each
operation of the device trace is joined by its instruction name to the
``op_name`` metadata of its executable's text, and a scope's time in an
executable is the union of its operations' intervals.  The last line of
standard output is the run's result with a ``scopes`` object added: per
executable, its calls, device ms per call, and each scope's share of
its device time in % (``moe.*``: the MoE layer's four scopes together),
mean over chips.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
# executable -> the engine's attribute that holds it
EXECUTABLES = {"decode_step": "_decode_jit", "prefill_chunk": "_chunk_jit"}
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?(%[\w.\-]+) = [^\n]*?'
                    r'op_name="([^"]*)"', re.M)


def scope_of(text: str, scopes=SCOPES) -> Dict[str, str]:
    """Instruction name -> the scope its ``op_name`` lies in, for the
    instructions of compiled HLO ``text`` that lie in one of ``scopes``."""
    out = {}
    for name, op in _INSTR.findall(text):
        parts = op.split("/")
        for s in scopes:
            if s in parts:
                out[name] = s
                break
    return out


def scope_seconds(planes, texts: Dict[str, str], scopes=SCOPES) -> Dict:
    """Per executable of ``texts``: calls, device seconds and seconds per
    scope (interval unions), summed over the chips' planes."""
    from bench.trace_reduce import MODULES_LINE, OPS_LINE, _events, union
    where = {exe: scope_of(t, scopes) for exe, t in texts.items()}
    out = {exe: {"calls": 0, "seconds": 0.0,
                 "scopes": defaultdict(float)} for exe in texts}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        mods = sorted((s, s + d, n.split("(")[0][len("jit_"):])
                      for n, s, d in _events(plane, MODULES_LINE))
        starts = [m[0] for m in mods]
        spans = defaultdict(list)
        for s, e, exe in mods:
            if exe in out:
                out[exe]["calls"] += 1
                out[exe]["seconds"] += (e - s) * 1e-9
        for name, s, d in _events(plane, OPS_LINE):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1] or mods[i][2] not in out:
                continue
            exe = mods[i][2]
            scope = where[exe].get(name.split(" = ")[0])
            if scope:
                spans[exe, scope].append((s, s + d))
                spans[exe, "moe.*"].append((s, s + d))
        for (exe, scope), iv in spans.items():
            out[exe]["scopes"][scope] += sum(
                e - s for s, e in union(iv)) * 1e-9
    for v in out.values():
        v["scopes"] = dict(v["scopes"])
    return out


def keep_texts(texts: Dict[str, str]):
    """A function of a new engine that stores, at each executable's first
    call, the compiled text of that call in ``texts``."""
    def install(serve):
        for exe, attr in EXECUTABLES.items():
            fn = getattr(serve, attr, None)
            if fn is None:
                continue

            def first(*a, _fn=fn, _exe=exe, _attr=attr):
                texts[_exe] = _fn.lower(*a).compile().as_text()
                setattr(serve, _attr, _fn)
                return _fn(*a)
            setattr(serve, attr, first)
    return install


def traced_run(cell, *, seed: int, seconds: float,
               require_tpu: bool = True) -> dict:
    """``bench/run.py``'s traced run of ``cell`` with ``scopes`` added."""
    from jax.profiler import ProfileData

    from bench import faults, run, trace_reduce

    texts: Dict[str, str] = {}
    reduce = trace_reduce.reduce

    def reduce_with_scopes(path):
        out = reduce(path)
        planes = list(ProfileData.from_file(str(path)).planes)
        out["scopes"] = scope_seconds(planes, texts)
        return out

    kept = {}
    serve = run._serve

    def serve_and_keep(*a, **kw):
        kept.update(serve(*a, **kw))
        return kept

    trace_reduce.reduce, run._serve = reduce_with_scopes, serve_and_keep
    try:
        with faults.planted(keep_texts(texts)):
            out = run.run_cell(cell, seed=seed, seconds=seconds,
                               traced=True, require_tpu=require_tpu)
    finally:
        trace_reduce.reduce, run._serve = reduce, serve
    found = (kept.get("reduced") or {}).get("scopes", {})
    n_chips = max(len((kept.get("reduced") or {}).get("chips", {})), 1)
    out["scopes"] = {
        exe: {"calls": v["calls"] / n_chips,
              "ms_per_call": 1e3 * v["seconds"] / max(v["calls"], 1),
              "share_pct": {k: 100.0 * s / v["seconds"]
                            for k, s in sorted(v["scopes"].items())}
              if v["seconds"] else {}}
        for exe, v in found.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import run, spec

    cell = spec.load_cell(args.workload)
    run.enable_cache()
    try:
        out = traced_run(cell, seed=args.seed, seconds=args.seconds)
    except run.NoChip as e:
        run.log(str(e))
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
