"""Engine step loop: host time in the engine's ``serve.sample`` phase
per decode step over the window, in ms (``gen.stats`` deltas summed
over replicas: ``phase_ns[serve.sample]`` / ``decode_steps``).  The
phase holds the per-slot sample and emit of every decode step and the
first-token sample of each finished prefill."""
from bench.engine_counters import deltas


def read(run):
    ns = deltas(run, "phase_ns", "serve.sample")
    steps = deltas(run, "decode_steps")
    if ns is None or steps is None or not sum(steps):
        return None
    return 1e-6 * sum(ns) / sum(steps)
