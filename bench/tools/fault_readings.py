#!/usr/bin/env python3
"""What the program and each planted fault read against a configuration's
limits, at a cell's own size and load.

    python3 bench/tools/fault_readings.py --workload granite3moe.doc_qa \\
        --seeds 1,2 --faults none,kv_unwritten,half_batch --seconds 8

For each seed, and for each fault in turn (``none`` is the program as it
is, the others are ``bench/faults.py``'s, planted in every engine): weights
from the seed, a short window of the cell's own traffic, then the sample
of served requests that a run compares, judged against the float32
reference.  One JSON line each: the widest and the mean gap, and whether
the configuration's limits pass them as a run judges its own numbers.
A limit that catches a fault lies below that fault's reading and above
every ``none`` reading.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def reading(cell, model, seed: int, fault: str, seconds: float, *,
            require_tpu: bool = True) -> dict:
    """One window of ``cell`` at ``seed`` with ``fault`` planted, judged."""
    import jax
    from bench import faults, measure, run

    devices = run.chips_for(cell.chips, require_tpu)[:cell.chips]
    w0 = cell.family.make_weights(model, seed, cell.config, devices[0])
    params = [w0] + [jax.device_put(w0, d) for d in devices[1:]]
    with faults.planted(faults.FAULTS.get(fault, lambda serve: None)):
        got = run._serve(cell, model, params, devices, seed=seed,
                         seconds=seconds, traced=False,
                         require_tpu=require_tpu)
    del params
    gc.collect()
    due = measure.in_window(got["records"], *got["window"])
    sample = run.sample_requests(due, seed)
    gaps = run.gap_numbers(cell, w0, sample, cell.reference)
    judged = run.passed({k: {"value": gaps[k], "limit": v}
                         for k, v in cell.config["limits"].items()})
    return {"seed": seed, "fault": fault, **gaps, "passed": judged,
            "tokens": sum(r["n_out"] for r in sample),
            "requests": len(sample), "due": len(due),
            "failed": sum(not r["ok"] for r in due)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none,kv_unwritten,half_batch")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    from bench import faults, run, spec
    from repro.models import Model

    names = args.faults.split(",")
    unknown = [f for f in names if f != "none" and f not in faults.FAULTS]
    if unknown:
        ap.error(f"no fault {', '.join(unknown)}")
    cell = spec.load_cell(args.workload)
    run.enable_cache()
    model = Model(cell.family.program_config(cell.config))
    for seed in [int(s) for s in args.seeds.split(",")]:
        for fault in names:
            print(json.dumps(reading(cell, model, seed, fault,
                                     args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
