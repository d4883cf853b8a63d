"""Whole step, beside the prefill chunk's roofline: ``step_mfu``'s
number, as the metric that moves the time to first token."""
from bench.layer_metrics_common import step_mfu


def read(run):
    return step_mfu(run)
