"""Engine step loop: share of the prefill chunks' rows over the window
that held a real prompt token, the rest being the last chunk's padding,
in % (``gen.stats`` deltas summed over replicas: ``prefill_tokens`` /
(``prefill_chunks`` × ``chunk_tokens``))."""
from bench.engine_counters import deltas


def read(run):
    tokens = deltas(run, "prefill_tokens")
    chunks = deltas(run, "prefill_chunks")
    if tokens is None or chunks is None or not sum(chunks) or not run.chunk:
        return None
    return 100.0 * sum(tokens) / (sum(chunks) * run.chunk)
