"""``{"dist": "uniform_int", "lo": a, "hi": b}``: each of a..b alike."""
import math


def ppf(spec, u, draws):
    return spec["lo"] + math.floor(u * (spec["hi"] - spec["lo"] + 1))
