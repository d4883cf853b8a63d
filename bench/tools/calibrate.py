#!/usr/bin/env python3
"""Readings that a configuration's ``max_logit_gap`` limit is set from.

    python3 bench/tools/calibrate.py --workload qwen05b.chat_sessions \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --seconds 8

In one process, for each seed: weights from the seed, a short window of
the cell's own traffic at its own load, then the same sample of served
requests that a run compares, judged twice against the float32
reference: as served (the program's reading) and with the token that the
float8 control puts first (the control's reading), each also judged
against the configuration's limits as a run judges it.  The limit lies
above the largest program reading and below the smallest control
reading, so every ``control_passed`` reads false.
Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def judged(cell, gaps) -> bool:
    """Whether ``gaps`` pass the configuration's limits, as a run judges
    its own numbers."""
    from bench import run
    return run.passed({k: {"value": gaps[k], "limit": v}
                       for k, v in cell.config["limits"].items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    import gc

    import jax
    from bench import measure, run, spec
    from repro.models import Model

    cell = spec.load_cell(args.workload)
    run.enable_cache()
    devices = run.chips_for(cell.chips)[:cell.chips]
    model = Model(cell.family.program_config(cell.config))
    ref = cell.reference
    for seed in [int(s) for s in args.seeds.split(",")]:
        w0 = cell.family.make_weights(model, seed, cell.config,
                                      devices[0])
        params = [w0] + [jax.device_put(w0, d) for d in devices[1:]]
        got = run._serve(cell, model, params, devices, seed=seed,
                         seconds=args.seconds, traced=False,
                         require_tpu=True)
        del params
        gc.collect()
        due = measure.in_window(got["records"], *got["window"])
        sample = run.sample_requests(due, seed)
        prog = run.gap_numbers(cell, w0, sample, ref)
        ctrl = run.gap_numbers(cell, w0, sample, ref, control=True)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "program_passed": judged(cell, prog),
                          "control_passed": judged(cell, ctrl),
                          "tokens": sum(r["n_out"] for r in sample),
                          "due": len(due),
                          "failed": sum(not r["ok"] for r in due)}),
              flush=True)
        del w0
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
