"""``{"dist": "exponential", "mean": m}``: Poisson gaps, think times."""
import math


def ppf(spec, u, draws):
    return -spec["mean"] * math.log1p(-u)
