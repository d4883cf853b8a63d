"""MoE layer: share of the expert-FFN rows that the dispatch computed
over the window that carried a routed token, in % (``gen.stats`` deltas
summed over replicas: ``moe_assignments`` / ``moe_rows``).  Silent for a
program without the counters, or a model without experts."""
from bench.engine_counters import deltas


def read(run):
    assignments = deltas(run, "moe_assignments")
    rows = deltas(run, "moe_rows")
    if assignments is None or rows is None or not sum(rows):
        return None
    return 100.0 * sum(assignments) / sum(rows)
