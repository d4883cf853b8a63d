"""Model prefill chunk: the mean least time of a prefill chunk of the
window's requests (``bench/work.py``) against the peaks, times the
chunks in the profiled part of the window, over the device time of the
``jit_prefill_chunk`` executable there, in %."""
from bench.layer_metrics_common import chunk_roofline


def read(run):
    return chunk_roofline(run)
