"""``{"dist": "lognormal", "median": m, "sigma": s}``: m * exp(s * z)."""
import math
import statistics

_NORMAL = statistics.NormalDist()


def ppf(spec, u, draws):
    return spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
