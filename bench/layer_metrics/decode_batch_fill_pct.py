"""Engine step loop: share of the decode batch's slots that produced a
token, over the window's decode steps, in % (``gen.stats`` deltas
summed over replicas: ``decode_tokens`` / (``decode_steps`` ×
``n_slots``))."""
from bench.engine_counters import deltas


def read(run):
    tokens = deltas(run, "decode_tokens")
    steps = deltas(run, "decode_steps")
    if tokens is None or steps is None:
        return None
    rows = sum(n * s["n_slots"] for n, s in zip(steps, run.snap["stats1"]))
    return 100.0 * sum(tokens) / rows if rows else None
