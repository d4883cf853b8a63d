"""Engine step loop: mean wall time of the ``ServeEngine.step`` calls in
the window that did work, in ms (timed by the benchmark's wrapper around
the public ``step()``)."""


def read(run):
    if run.steps is None:
        return None
    steps = run.steps.steps_in(*run.window)
    return 1e3 * sum(steps) / len(steps) if steps else None
