"""Client pool and affinity: share of follow-up turns in the window that
the routed pool sent to the replica holding their session
(``SessionAffinity.stats()`` delta: hits / (hits + moves)), in %."""


def read(run):
    a0, a1 = run.snap.get("aff0"), run.snap.get("aff1")
    if a0 is None or a1 is None:
        return None
    hits = a1["hits"] - a0["hits"]
    follow_ups = hits + a1["moves"] - a0["moves"]
    return 100.0 * hits / follow_ups if follow_ups else None
