"""Decoder stacks of attention layers, dense or with a mixture of
experts: from a configuration file to the program's ``ModelConfig``, the
weights, and the shapes the cost of a call is counted from.

The file uses the source's own key names and holds the configuration as
it is run.  Each key the program has a field for is set from the file;
a key that is not listed in ``reduced`` must equal the program's preset,
and keys the program has no field for must equal what it does.  Any
mismatch is an error before anything runs.

Weights are random from ``--seed``, made on the device in one jitted
call, in the layout the program's ``Model`` takes (read from an abstract
``init``, which allocates nothing) and the dtype it serves them in.  The
benchmark makes them, so the reference reads the same arrays without
taking anything the program made.  Matrices are normal with standard
deviation 1/sqrt(fan-in); q/k/v biases are normal with ``bias_std``;
norm scales are 1 plus ``norm_jitter`` times a normal draw, so biases
and norm scales are exercised too.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

from bench import cost

# source key -> ModelConfig field
_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}
# source key -> MoEConfig field
_MOE_FIELDS = {"num_local_experts": "num_experts",
               "num_experts_per_tok": "top_k"}


def _fixed_by_program(cfg, c: Dict) -> Dict:
    """Keys the program has no field for, with the value it runs."""
    fixed = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
             "logits_scaling": 1.0,
             "attention_multiplier": 1.0 / math.sqrt(cfg.hd),
             "attention_bias": cfg.qkv_bias}
    return {k: v for k, v in fixed.items() if k in c}


def program_config(c: Dict):
    """The ``ModelConfig`` that runs configuration file ``c``."""
    from repro import configs

    preset = configs.get(c["program_config"])
    reduced = set(c.get("reduced", []))
    top, moe = {}, {}
    for key, field in _FIELDS.items():
        if key not in c:
            continue
        if key not in reduced and getattr(preset, field) != c[key]:
            raise ValueError(f"{c['name']}: {key}={c[key]} differs from the "
                             f"program's {field}={getattr(preset, field)} "
                             f"and is not in reduced")
        top[field] = c[key]
    for key, field in _MOE_FIELDS.items():
        if key not in c:
            continue
        if key not in reduced and getattr(preset.moe, field) != c[key]:
            raise ValueError(f"{c['name']}: {key}={c[key]} differs from the "
                             f"program's moe.{field}")
        moe[field] = c[key]
    cfg = preset.replace(**top)
    if moe:
        cfg = cfg.replace(moe=preset.moe.__class__(
            **{**preset.moe.__dict__, **moe}))
    for key, value in _fixed_by_program(cfg, c).items():
        if not math.isclose(float(c[key]), float(value), rel_tol=1e-9):
            raise ValueError(f"{c['name']}: {key}={c[key]}, but the program "
                             f"runs {value}")
    dt = c["dtypes"]
    if (cfg.param_dtype, cfg.compute_dtype) != (dt["params"],
                                                dt["compute"]):
        raise ValueError(f"{c['name']}: the program runs "
                         f"{cfg.param_dtype}/{cfg.compute_dtype}")
    if cfg.family not in ("dense", "moe") or cfg.period != ("attn",):
        raise ValueError(f"{c['name']}: the decoder reference covers "
                         f"uniform attention stacks only")
    return cfg


def cost_shape(c: Dict) -> cost.Shape:
    """The shapes ``bench/cost.py`` counts a call's work from."""
    return cost.Shape.from_config(c)


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """Two 32-bit words from any whole number (seeds may pass
    32 signed bits)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return np.uint32(a), np.uint32(b)


def _names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        key = getattr(p, "key", None)
        if isinstance(key, str):
            out.append(key)
    return tuple(out)


def _fan_in(names: Tuple[str, ...], shape) -> int:
    leaf = names[-1]
    if "attn" in names and leaf in ("wq", "wk", "wv"):
        return shape[-3]                      # (L, d, H, D)
    if "attn" in names and leaf == "wo":
        return shape[-3] * shape[-2]          # (L, H, D, d)
    return shape[-2]                          # (.., in, out)


def make_weights(model, seed: int, c: Dict, device):
    """The weight tree of ``model`` from ``seed``, on ``device``."""
    wcfg = c["weights"]
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import unzip

    abstract, _ = unzip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    bias_std = float(wcfg["bias_std"])
    jitter = float(wcfg["norm_jitter"])

    def leaf_value(key, names, sds):
        shape, dt = sds.shape, sds.dtype
        leaf = names[-1]
        z = jax.random.normal(key, shape, jnp.float32)
        if leaf == "embedding":
            v = z / math.sqrt(shape[-1])
        elif leaf in ("bq", "bk", "bv"):
            v = z * bias_std
        elif "norm" in leaf:
            v = 1.0 + jitter * z
        elif leaf.startswith("w") or leaf == "router":
            v = z / math.sqrt(_fan_in(names, shape))
        else:
            raise ValueError(f"no init rule for weight {'/'.join(names)}")
        return v.astype(dt)

    def gen(a, b):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), a), b)
        vals = [leaf_value(jax.random.fold_in(key, i), _names(path), sds)
                for i, (path, sds) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    a, b = seed_words(seed)
    fn = jax.jit(gen, out_shardings=SingleDeviceSharding(device))
    return jax.block_until_ready(fn(a, b))
