"""Engine sessions: share of session lookups in the window that resumed
a pinned KV cache (``gen.stats`` delta over all replicas:
prefix_hits / (prefix_hits + prefix_misses)), in %."""


def read(run):
    s0, s1 = run.snap["stats0"], run.snap["stats1"]
    hits = sum(b["prefix_hits"] - a["prefix_hits"] for a, b in zip(s0, s1))
    miss = sum(b["prefix_misses"] - a["prefix_misses"]
               for a, b in zip(s0, s1))
    return 100.0 * hits / (hits + miss) if hits + miss else None
