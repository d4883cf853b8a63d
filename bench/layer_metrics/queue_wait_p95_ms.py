"""Gateway admission and queue: 95th percentile of the time a request
waited in the gateway before it took an engine slot, in ms, exact per
request from the ``gen.serve`` spans' ``queue_ms`` (trace sampling on
for the whole traced run)."""
from bench.measure import percentile


def read(run):
    waits = [s["tags"]["queue_ms"] for s in run.spans
             if "queue_ms" in s.get("tags", {})]
    return percentile(waits, 95)
