#!/usr/bin/env python3
"""A traced run of a cell with the engine's step phases laid against the
device trace.

    python3 bench/tools/phase_trace.py --workload qwen05b.short_decode \\
        --seed 7 --seconds 40

First it times one phase enter and exit with the profiler off, over a
loop.  Then it runs the cell as ``bench/run.py --trace 1`` does, with
``bench/phase_gaps.py``'s reduction added to the trace's and with
``idle_in_sample_pct`` among the cell's per-layer metrics.  The last
line of standard output is the run's result with a ``phases`` object
added: the phase's cost, each phase's host ms and calls per decode step
over the window (``gen.stats`` deltas), the idle seconds by phase (mean
over chips), and how many decode steps lay wholly inside
``serve.decode``.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

COST_LOOPS = 200_000
IDLE_IN_SAMPLE = {"name": "idle_in_sample_pct", "unit": "%",
                  "better": "lower", "source": "device_trace",
                  "layer": "device", "moves": "tpot_p95_ms"}


def phase_cost_ns(loops: int = COST_LOOPS) -> float:
    """Mean ns of one empty phase, enter to exit, profiler off."""
    from repro.telemetry import metrics
    from repro.telemetry.phases import Phases
    ph = Phases({"probe": metrics.counter("bench.phase_cost.ns")},
                {"probe": metrics.counter("bench.phase_cost.calls")})
    t0 = time.perf_counter_ns()
    for _ in range(loops):
        with ph("probe"):
            pass
    return (time.perf_counter_ns() - t0) / loops


def per_decode_step(snap, key: str):
    """Per phase, the window's ``gen.stats[key]`` delta per decode
    step, summed over replicas."""
    s0, s1 = snap["stats0"], snap["stats1"]
    steps = sum(b["decode_steps"] - a["decode_steps"]
                for a, b in zip(s0, s1))
    if not steps:
        return {}
    return {p: sum(b[key][p] - a[key][p] for a, b in zip(s0, s1)) / steps
            for p in s1[0][key]}


def traced_run(cell, *, seed: int, seconds: float,
               require_tpu: bool = True) -> dict:
    """``bench/run.py``'s traced run of ``cell`` with the phases added
    (``NoChip`` without the chips the cell asks for)."""
    from bench import phase_gaps, run, trace_reduce

    cost = phase_cost_ns()
    run.log(f"one phase, enter to exit, profiler off: {cost:.1f} ns")
    reduce, serve, kept = trace_reduce.reduce, run._serve, {}

    def reduce_with_phases(path):
        out = reduce(path)
        out.update(phase_gaps.reduce(path))
        return out

    def serve_and_keep(*a, **kw):
        kept.update(serve(*a, **kw))
        return kept

    trace_reduce.reduce = reduce_with_phases
    run._serve = serve_and_keep
    try:
        out = run.run_cell(cell, seed=seed, seconds=seconds, traced=True,
                           require_tpu=require_tpu)
    finally:
        trace_reduce.reduce, run._serve = reduce, serve
    reduced = kept["reduced"] or {"chips": {}}
    n_chips = max(len(reduced["chips"]), 1)
    out["phases"] = {
        "phase_cost_ns": cost,
        "ms_per_decode_step": {
            k: v * 1e-6 for k, v in
            per_decode_step(kept["snap"], "phase_ns").items()},
        "calls_per_decode_step": per_decode_step(kept["snap"],
                                                 "phase_calls"),
        "idle_s_by_phase": {k: v / n_chips for k, v in
                            reduced.get("phase_gaps", {}).items()},
        "decode_in_phase": reduced.get("decode_in_phase"),
        "traced_s": (kept["traced"][1] - kept["traced"][0]
                     if kept["traced"] else None),
    }
    return out


def with_idle_in_sample(benchmark: dict) -> dict:
    """``benchmark`` with ``idle_in_sample_pct`` among its per-layer
    metrics, for every cell."""
    return dict(benchmark, per_layer=benchmark["per_layer"]
                + [IDLE_IN_SAMPLE])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import run, spec

    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = spec.load_cell(args.workload, benchmark=with_idle_in_sample(bm))
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
