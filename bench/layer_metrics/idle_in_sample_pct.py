"""Device: share of the profiled window in which a chip was idle while
the host was in the engine's ``serve.sample`` phase, mean over the
cell's chips, in % (``bench/phase_gaps.py``; silent where the run's
trace reduction carries no ``phase_gaps``)."""


def read(run):
    reduced = run.reduced
    if not reduced or not reduced["chips"] or "phase_gaps" not in reduced:
        return None
    span = run.traced[1] - run.traced[0]
    idle = reduced["phase_gaps"].get("serve.sample", 0.0)
    return 100.0 * idle / len(reduced["chips"]) / span
