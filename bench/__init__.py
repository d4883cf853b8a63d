"""Chip benchmark of the serving path: cells, traffic, reference check and
the reduction from traces, spans and counters to metrics.

Run ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the repository root on a machine that holds the
cell's chips.  ``BENCHMARK.json`` names every cell; each configuration,
traffic mix and per-layer metric is a file of its own under this
directory, found by its name.
"""
