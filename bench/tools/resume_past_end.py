#!/usr/bin/env python3
"""Witness of a program fault: a session resumed so close to the end of
the cache that its padded prefill chunk passes ``max_len``.

    JAX_PLATFORMS=cpu python3 bench/tools/resume_past_end.py

The tiny fixture model runs in float32 with a float32 cache, where a
sound engine matches the float32 reference to rounding.  For each first
prompt length, turn 1 pins a session and turn 2 resumes it; the line
prints where the resumed chunk ends and the widest gap of each turn's
served tokens below the reference's best logit.  Gaps stay 0 while the
chunk ends inside the cache, and not once it passes the end.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

MAX_LEN, CHUNK = 256, 32


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import spec
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "witness", "config": "tiny-decoder",
                        "traffic": "tiny_chat", "chips": 1, "why": "-"}]
    cell = spec.load_cell("witness", benchmark=bm,
                          data_dir=ROOT / "bench" / "tests" / "fixture")
    cfg = cell.family.program_config(cell.config).replace(
        compute_dtype="float32")
    model = Model(cfg)
    w = cell.family.make_weights(model, 2 ** 33 + 17, cell.config,
                                 jax.devices()[0])
    rng = np.random.default_rng(0)
    for first in (150, 200, 215, 220, 228):
        eng = ServeEngine(model, w, max_len=MAX_LEN, n_slots=2,
                          chunk_tokens=CHUNK, impl="xla",
                          cache_dtype=jnp.float32, session_cap=2)
        p1 = rng.integers(1, 512, first).tolist()
        o1 = eng.generate([p1], max_new=10, session_ids=["s"])[0]
        p2 = p1 + o1 + rng.integers(1, 512, 8).tolist()
        o2 = eng.generate([p2], max_new=10, session_ids=["s"])[0]
        at = len(p1) + len(o1) - 1
        g1 = float(np.max(cell.reference.gaps(cell.config, w, p1, o1)))
        g2 = float(np.max(cell.reference.gaps(cell.config, w, p2, o2)))
        print(f"resume at {at}, chunk ends {at + CHUNK} (cache {MAX_LEN}), "
              f"prefix hits {eng.stats()['prefix_hits']}: widest gap "
              f"turn 1 {g1:.5f}, turn 2 {g2:.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
