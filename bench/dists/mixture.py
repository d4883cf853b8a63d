"""``{"dist": "mixture", "parts": [{"weight": w, "of": {...}}, ...]}``:
the quantiles are split among the parts by weight, each part drawn at
its own quantiles, so every block of draws holds each part's share
(e.g. long and short prompts in one queue)."""


def ppf(spec, u, draws):
    parts = spec["parts"]
    total = sum(p["weight"] for p in parts)
    lo = 0.0
    for p in parts:
        w = p["weight"] / total
        if u < lo + w or p is parts[-1]:
            return draws.ppf(p["of"], min((u - lo) / w, 1 - 1e-12))
        lo += w
