"""Whole step: model operations of every prompt token prefilled and
every token decoded in the window, at their live lengths
(``bench/work.py``), over the window times the chips times the
bfloat16 peak, in %."""
from bench.layer_metrics_common import step_mfu


def read(run):
    return step_mfu(run)
