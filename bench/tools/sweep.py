#!/usr/bin/env python3
"""Find an open-loop cell's knee: run its traffic at several arrival
rates in one process and print, per rate, what was offered and served.

    python3 bench/tools/sweep.py --workload qwen05b.chat_sessions \\
        --rates 0.5,1,1.5,2,3 --seconds 20 --seed 5

The knee is the highest rate at which the generator keeps to its
schedule and requests due late in the window wait no longer than those
due early (no growing backlog).  The cell's mix file then fixes a rate
below it.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import gc

    import jax
    from bench import measure, run, spec
    from repro.models import Model

    base = spec.load_cell(args.workload)
    run.enable_cache()
    devices = run.chips_for(base.chips)[:base.chips]
    model = Model(base.family.program_config(base.config))
    w0 = base.family.make_weights(model, args.seed, base.config,
                                  devices[0])
    params = [w0] + [jax.device_put(w0, d) for d in devices[1:]]
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.copy(base)
        cell.traffic = copy.deepcopy(base.traffic)
        cell.traffic["arrival"]["rate_per_s"] = rate
        got = run._serve(cell, model, params, devices, seed=args.seed,
                         seconds=args.seconds, traced=False,
                         require_tpu=True)
        t0, t1 = got["window"]
        records, stats1 = got["records"], got["snap"]["stats1"]
        e2e = measure.end_to_end(records, t0, t1)
        mid = (t0 + t1) / 2
        halves = [[measure.ttft_ms(r) for r in records
                   if r["ok"] and a <= r["due"] < b]
                  for a, b in ((t0, mid), (mid, t1))]
        late = measure.lateness_ms(measure.in_window(records, t0, t1))
        del got
        gc.collect()          # the last stack's caches leave the chip
        print(json.dumps({
            "rate_per_s": rate, **e2e,
            "ttft_p50_first_half": measure.percentile(halves[0], 50),
            "ttft_p50_second_half": measure.percentile(halves[1], 50),
            "lateness_p95_ms": measure.percentile(late, 95),
            "queued_at_close": sum(s["queued"] for s in stats1),
            "active_at_close": sum(s["active_slots"] for s in stats1)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
