"""Granite-3.0 MoE decoders: ``decoder.py``'s stacks, weights, seed
words and cost shape, with Granite's four multipliers mapped onto the
program's fields of the same names.

``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier`` and ``logits_scaling`` are the source's keys and
the program's field names.  Each sets its field; a value that differs
from the program's preset, and is not in ``reduced``, is an error before
anything runs, as a width is in ``decoder.py``.  ``dtypes.params`` sets
``param_dtype``: the preset keeps float32 weights for training, and the
weights are made and served in the file's dtype.  A program without the
four fields cannot run such a configuration and raises at once.

Weights are ``decoder.py``'s 1/sqrt(fan-in) draw, rescaled so that each
layer sees the activations that draw gives a plain decoder with a dense
SwiGLU: the embedding table ÷ ``embedding_multiplier``; q and k ×
sqrt(1/sqrt(head_dim) ÷ ``attention_multiplier``) each; attention's
output projection ÷ ``residual_multiplier``; each expert's output
projection × sqrt(top-k) ÷ ``residual_multiplier``, since k gates that
sum to 1 over independent expert outputs give 1/sqrt(k) of one
expert's norm.  Drawn plainly, the multipliers leave a random model
degenerate: the scaled embedding outweighs the branches and the tied
head repeats one token, or, with only the table rescaled, near-uniform
attention and the weak mixture make every position's state alike over
a long prompt; either way greedy decoding serves one token, which a
broken program or the float8 control would serve too.  The program
still has to apply each multiplier: drop one and every layer changes.
"""
from __future__ import annotations

import math
from typing import Dict

from bench.families import decoder

MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")

cost_shape = decoder.cost_shape
seed_words = decoder.seed_words


def make_weights(model, seed: int, c: Dict, device):
    """``decoder.make_weights``, rescaled against the multipliers."""
    import jax
    import jax.numpy as jnp
    cfg = model.cfg
    qk = math.sqrt(1.0 / math.sqrt(cfg.hd) / cfg.attention_multiplier)
    out = 1.0 / cfg.residual_multiplier
    scale = {("embed", "embedding"): 1.0 / cfg.embedding_multiplier,
             ("attn", "wq"): qk, ("attn", "wk"): qk, ("attn", "wo"): out,
             ("moe", "wo"): out * math.sqrt(cfg.moe.top_k)}

    def rescale(path, x):
        s = scale.get(decoder._names(path)[-2:], 1.0)
        if s == 1.0:
            return x
        return (x.astype(jnp.float32) * s).astype(x.dtype)

    w = decoder.make_weights(model, seed, c, device)
    return jax.jit(lambda w: jax.tree_util.tree_map_with_path(rescale, w),
                   donate_argnums=0)(w)


def program_config(c: Dict):
    """The ``ModelConfig`` that runs configuration file ``c``."""
    from repro import configs

    preset = configs.get(c["program_config"])
    missing = [k for k in MULTIPLIERS if not hasattr(preset, k)]
    if missing:
        raise ValueError(f"{c['name']}: the program has no field for "
                         f"{', '.join(missing)}")
    absent = [k for k in MULTIPLIERS if k not in c]
    if absent:
        raise ValueError(f"{c['name']}: the file gives no "
                         f"{', '.join(absent)}")
    # widths, experts and the compute dtype, each checked against the
    # preset; the weights' dtype is the file's
    params = c["dtypes"]["params"]
    if params not in ("float32", "bfloat16"):
        raise ValueError(f"{c['name']}: dtypes.params {params} is neither "
                         f"float32 nor bfloat16")
    plain = {k: v for k, v in c.items() if k not in MULTIPLIERS}
    plain["dtypes"] = dict(c["dtypes"], params=preset.param_dtype)
    cfg = decoder.program_config(plain)
    reduced = set(c.get("reduced", []))
    for key in MULTIPLIERS:
        if key not in reduced and not math.isclose(
                float(c[key]), float(getattr(preset, key)), rel_tol=1e-9):
            raise ValueError(f"{c['name']}: {key}={c[key]} differs from "
                             f"the program's {getattr(preset, key)} and "
                             f"is not in reduced")
    return cfg.replace(param_dtype=params,
                       **{k: float(c[k]) for k in MULTIPLIERS})
