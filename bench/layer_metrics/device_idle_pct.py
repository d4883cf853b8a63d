"""Device: share of the profiled window in which no operation ran on a
chip, mean over the cell's chips, in %."""


def read(run):
    if not run.reduced or not run.reduced["chips"]:
        return None
    span = run.traced[1] - run.traced[0]
    chips = list(run.reduced["chips"].values())
    busy = sum(c["busy_s"] for c in chips) / len(chips)
    return 100.0 * max(0.0, 1.0 - busy / span)
