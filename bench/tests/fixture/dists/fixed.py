"""``{"dist": "fixed", "value": v}``: every draw is v."""


def ppf(spec, u, draws):
    return spec["value"]
