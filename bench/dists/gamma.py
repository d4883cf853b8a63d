"""``{"dist": "gamma", "mean": m, "cv": c}``: shape 1/c**2, so c = 1 is
exponential and c > 1 bursty (arrival gaps of BurstGPT-like traffic)."""
from scipy.stats import gamma as _gamma


def ppf(spec, u, draws):
    shape = 1.0 / spec["cv"] ** 2
    return float(_gamma.ppf(u, shape, scale=spec["mean"] / shape))
