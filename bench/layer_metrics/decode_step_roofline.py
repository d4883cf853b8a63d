"""Model decode step: the least time the chip could take for the decode
steps in the profiled part of the window (their weights once each, the
K/V and operations of the tokens they produced, ``bench/work.py``)
against the peaks, over the device time of the ``jit_decode_step``
executable there, in %."""
from bench.layer_metrics_common import decode_roofline


def read(run):
    return decode_roofline(run)
