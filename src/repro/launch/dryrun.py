import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Per cell this produces:
  * the PRODUCTION compile — scanned layers, remat, memory-efficient ops;
    its success is the deliverable ("the sharding is coherent"), and its
    ``memory_analysis()`` proves the per-device footprint;
  * two COST compiles — small *unrolled* depths (1 and 2 scan periods)
    with scan-free ops (impl="cost") — cost_analysis/collective bytes are
    linear in depth, so a 2-point fit extrapolates exact full-depth
    FLOPs/bytes/collective-bytes in seconds of compile time (XLA's
    cost_analysis counts a while body once, which would otherwise
    undercount scanned layers);
  * a JSON record under experiments/dryrun/ consumed by
    ``benchmarks.roofline`` / ``benchmarks.report``.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--no-cost]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro import configs
from repro.configs.base import ModelConfig, ParallelConfig, SHAPES, ShapeSpec
from repro.distrib import merge_rules, tree_shardings, tree_specs
from repro.distrib.sharding import DEFAULT_RULES, bytes_per_device
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import Model, unzip
from repro.models.moe import padded_experts
from repro.train import optim
from repro.train.step import make_train_step

OUT_DIR = Path("experiments/dryrun")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
               "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
               "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
               "c128": 16}

COLL_RE = re.compile(
    r"=\s*(\(?.*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
GROUPS_ITOA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(txt):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> list:
    """Per-device collective records from optimized HLO text."""
    out = []
    for line in hlo.splitlines():
        line = line.strip()
        m = COLL_RE.search(line)
        if not m:
            continue
        result_txt, kind, variant = m.group(1), m.group(2), m.group(3)
        if variant == "-done":
            continue            # counted at -start
        rbytes = _shape_bytes(result_txt)
        group = 1
        gm = GROUPS_ITOA_RE.search(line)
        if gm:
            group = int(gm.group(2))
        else:
            gm = GROUPS_LIST_RE.search(line)
            if gm:
                group = len([x for x in gm.group(1).split(",") if x.strip()])
        out.append({"op": kind, "result_bytes": rbytes, "group": group})
    return out


def wire_bytes(rec: dict) -> float:
    """Per-device ICI wire traffic of one collective (ring algorithms)."""
    n = max(rec["group"], 1)
    r = rec["result_bytes"]
    if n == 1:
        return 0.0
    if rec["op"] == "all-reduce":
        return 2.0 * r * (n - 1) / n
    if rec["op"] == "all-gather":
        return r * (n - 1) / n            # result is the gathered buffer
    if rec["op"] == "reduce-scatter":
        return r * (n - 1)                 # operand = result * n
    if rec["op"] == "all-to-all":
        return r * (n - 1) / n
    if rec["op"] == "collective-permute":
        return float(r)
    return float(r)


# ===========================================================================
# per-cell builders
# ===========================================================================
def batch_specs(cfg: ModelConfig, shape: ShapeSpec, kind: str):
    """ShapeDtypeStruct stand-ins for every model input."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if kind == "train":
        F = cfg.frontend_seq if cfg.family == "vlm" else 0
        b = {"tokens": sds((B, S - F), jnp.int32),
             "targets": sds((B, S), jnp.int32)}
        if cfg.frontend != "none":
            b["frontend"] = sds((B, cfg.frontend_seq, cfg.frontend_dim),
                                jnp.float32)
            if cfg.family != "vlm":
                b["targets"] = sds((B, S), jnp.int32)
        return b
    if kind == "prefill":
        F = cfg.frontend_seq if cfg.family == "vlm" else 0
        b = {"tokens": sds((B, S - F), jnp.int32)}
        if cfg.frontend != "none":
            b["frontend"] = sds((B, cfg.frontend_seq, cfg.frontend_dim),
                                jnp.float32)
        return b
    # decode: one new token against a cache of S
    return {"tokens": sds((B, 1), jnp.int32)}


def batch_shardings(specs, mesh, dp_over=None):
    dp = dp_over or dp_axes(mesh)

    def sh(sds):
        dims = [dp if (sds.shape and sds.shape[0] %
                       int(np.prod([mesh.shape[a] for a in dp])) == 0)
                else None]
        dims += [None] * (len(sds.shape) - 1)
        return NamedSharding(mesh, PS(*dims))

    return jax.tree_util.tree_map(sh, specs)


def cell_rules(shape: ShapeSpec) -> dict:
    if shape.name == "long_500k":
        # batch=1: spread the KV sequence over (data, model) = 256-way
        return {"kv_seq": ("data", "model")}
    return {}


def opt_for(cfg: ModelConfig) -> optim.OptConfig:
    if cfg.name == "nemotron-4-340b":
        return optim.OptConfig(state_dtype="bfloat16")
    return optim.OptConfig()


def par_for(cfg: ModelConfig, mesh, shape: ShapeSpec) -> ParallelConfig:
    return ParallelConfig(
        pod_axis="pod" if "pod" in mesh.shape else None,
        microbatches=1,
        remat="block",
    )


def act_sharding_for(cfg: ModelConfig, mesh, shape: ShapeSpec):
    """Residual-stream constraint at block boundaries.  Batch over the DP
    axes always (GSPMD left alone picks pathological layouts); wide dense
    models additionally shard the sequence over ``model``
    (Korthikanti-style SP: saved block inputs shrink by 1/TP)."""
    dp = dp_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    ndp = int(np.prod([mesh.shape[a] for a in dp]))
    if B % ndp:
        return None
    wide = cfg.d_model >= 3840 and not cfg.moe.num_experts
    if wide and shape.kind == "train" and S % mesh.shape["model"] == 0:
        return NamedSharding(mesh, PS(dp, "model", None))
    return NamedSharding(mesh, PS(dp, None, None))


def logits_sharding_for(cfg: ModelConfig, mesh, shape: ShapeSpec):
    dp = dp_axes(mesh)
    B = shape.global_batch
    ndp = int(np.prod([mesh.shape[a] for a in dp]))
    if B % ndp:
        return None
    return NamedSharding(mesh, PS(dp, None, "model"))


def abstract_state(model: Model, opt_cfg: optim.OptConfig):
    def build(rng):
        params_p = model.init(rng)
        opt_p = optim.adamw_init(params_p)
        if opt_cfg.state_dtype != "float32":
            opt_p = optim.cast_state(opt_p, opt_cfg.state_dtype)
        return {"params": params_p, "opt": opt_p}
    tree_p = jax.eval_shape(build, jax.random.PRNGKey(0))
    return unzip(tree_p)


def abstract_params(model: Model):
    tree_p = jax.eval_shape(lambda r: model.init(r), jax.random.PRNGKey(0))
    return unzip(tree_p)


def abstract_cache(model: Model, batch: int, seq: int):
    tree_p = jax.eval_shape(
        lambda: model.cache_specs(batch, seq, jnp.bfloat16))
    return unzip(tree_p)


# ===========================================================================
# lower+compile one cell
# ===========================================================================
def lower_cell(arch: str, shape_name: str, mesh, *, unroll_periods: int = 0,
               impl: str = "xla", remat: str = "block",
               overrides: Optional[dict] = None):
    """Build and lower one cell. unroll_periods>0 → cost-mode variant with
    that many unrolled periods. ``overrides`` (hillclimb variants):
      moe_dispatch: "cumsum"       — sort-free MoE dispatch
      param_gather: "bfloat16"     — cast params before use (16-bit FSDP
                                     gathers / grad reduces)
      flat_dp: True                — no TP: both mesh axes are data
                                     parallel, params FSDP over all chips
    Returns (lowered, meta)."""
    import dataclasses as _dc
    overrides = overrides or {}
    cfg = configs.get(arch)
    if overrides.get("moe_dispatch") and cfg.moe.num_experts:
        cfg = cfg.replace(moe=_dc.replace(cfg.moe,
                                          dispatch=overrides["moe_dispatch"]))
    shape = SHAPES[shape_name]
    e_pad = padded_experts(cfg, mesh.shape["model"]) \
        if cfg.moe.num_experts else None

    if unroll_periods > 0:
        plen = len(cfg.period)
        prefix = 1 if (cfg.moe.first_layer_dense and cfg.moe.num_experts) \
            else 0
        trail = cfg.n_layers % plen if plen > 1 else 0
        n_layers = prefix + unroll_periods * plen + trail
        cfg_v = cfg.replace(n_layers=n_layers)
        model = Model(cfg_v, e_pad=e_pad, unroll=True)
        remat = "none"
    else:
        cfg_v = cfg
        model = Model(cfg_v, e_pad=e_pad)

    par = par_for(cfg_v, mesh, shape)
    opt_cfg = opt_for(cfg)
    rules = cell_rules(shape)
    dp_all = tuple(dp_axes(mesh)) + ("model",)
    if overrides.get("flat_dp"):
        rules.update({"heads": (), "kv_heads": (), "kv_heads_dim": (),
                      "mlp": (), "vocab": (),
                      "experts": (), "inner": (), "lru": (),
                      "ssm_heads": (), "embed": dp_all, "batch": dp_all})
    pg_dtype = overrides.get("param_gather")

    def cast_params(params):
        if not pg_dtype:
            return params
        dt = jnp.dtype(pg_dtype)
        return jax.tree_util.tree_map(
            lambda x: x.astype(dt) if x.dtype == jnp.float32 else x, params)
    specs_b = batch_specs(cfg_v, shape, shape.kind)
    b_sh = batch_shardings(specs_b, mesh,
                           dp_over=dp_all if overrides.get("flat_dp")
                           else None)
    meta: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                            "kind": shape.kind,
                            "n_layers": cfg_v.n_layers}

    if shape.kind == "train":
        state_sds, state_axes = abstract_state(model, opt_cfg)
        st_sh = tree_shardings(state_sds, state_axes, mesh, rules)
        if overrides.get("flat_dp"):
            act_sh = NamedSharding(mesh, PS(dp_all, None, None))
        else:
            act_sh = act_sharding_for(cfg_v, mesh, shape)
        step_fn = make_train_step(model, opt_cfg, par, mesh, impl=impl)

        def train_step(state, batch):
            # thread act_sharding / ce_chunk via a wrapper loss
            return step_fn(state, batch)

        # rebuild step with act_sharding by overriding model.loss_fn call
        from repro.models.moe import MoESpmd
        from repro.train.step import make_moe_spmd
        spmd = make_moe_spmd(cfg_v, par, mesh)
        ce_chunk = shape.seq_len if impl == "cost" else 512

        if overrides.get("flat_dp"):
            logits_sh = NamedSharding(mesh, PS(dp_all, None, None))
            if cfg_v.moe.num_experts:
                from repro.models.moe import MoESpmd
                spmd = MoESpmd(mesh=mesh, token_axes=dp_all,
                               expert_axis=None)
            else:
                spmd = None
        else:
            logits_sh = logits_sharding_for(cfg_v, mesh, shape)

        inner_sh = None
        if overrides.get("gather_once") and act_sh is not None:
            # one explicit SP gather per block: post-norm activations go
            # to (dp-batch, full-seq) exactly once for both branches
            inner_sh = NamedSharding(mesh, PS(dp_axes(mesh), None, None))

        def loss_of(params, b):
            return model.loss_fn(cast_params(params), b, spmd=spmd,
                                 impl=impl, remat=remat,
                                 act_sharding=act_sh,
                                 logits_sharding=logits_sh,
                                 inner_sharding=inner_sh,
                                 ce_chunk=ce_chunk)

        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        n_micro = int(overrides.get("microbatches", 1))

        def full_step(state, batch):
            if n_micro > 1:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                        + x.shape[1:]), batch)

                def acc(carry, mb):
                    g_acc, l_acc = carry
                    (l, m), g = grad_fn(state["params"], mb)
                    return (jax.tree_util.tree_map(jnp.add, g_acc, g),
                            l_acc + l), m

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                (grads, loss), ms = jax.lax.scan(
                    acc, (g0, jnp.float32(0)), micro)
                grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
                loss = loss / n_micro
                metrics = jax.tree_util.tree_map(lambda x: x[-1], ms)
            else:
                (loss, metrics), grads = grad_fn(state["params"], batch)
            opt = state["opt"]
            new_params, m_new, v_new, count, stats = optim.adamw_update(
                opt_cfg, state["params"], grads, opt["m"], opt["v"],
                opt["count"])
            metrics = dict(metrics); metrics.update(stats)
            return ({"params": new_params,
                     "opt": {"m": m_new, "v": v_new, "count": count}},
                    metrics)

        lowered = jax.jit(
            full_step,
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, None),
        ).lower(state_sds, specs_b)
        meta["state_bytes_analytic"] = bytes_per_device(
            state_sds, state_axes, mesh, rules)
        return lowered, meta

    params_sds, params_axes = abstract_params(model)
    p_sh = tree_shardings(params_sds, params_axes, mesh, rules)

    if shape.kind == "prefill":
        act_sh = act_sharding_for(cfg_v, mesh, shape)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, cache_len=shape.seq_len,
                                 impl=impl, capacity_factor=2.0,
                                 act_sharding=act_sh)

        lowered = jax.jit(
            prefill_fn, in_shardings=(p_sh, b_sh),
        ).lower(params_sds, specs_b)
        meta["state_bytes_analytic"] = bytes_per_device(
            params_sds, params_axes, mesh, rules)
        return lowered, meta

    # decode
    cache_sds, cache_axes = abstract_cache(model, shape.global_batch,
                                           shape.seq_len)
    c_sh = tree_shardings(cache_sds, cache_axes, mesh, rules)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, impl=impl)

    lowered = jax.jit(
        decode_fn,
        in_shardings=(p_sh, c_sh, b_sh["tokens"], None),
        out_shardings=(None, c_sh),
    ).lower(params_sds, cache_sds, specs_b["tokens"], pos_sds)
    meta["state_bytes_analytic"] = bytes_per_device(
        params_sds, params_axes, mesh, rules)
    meta["cache_bytes_analytic"] = bytes_per_device(
        cache_sds, cache_axes, mesh, rules)
    return lowered, meta


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             with_cost: bool = True, verbose: bool = True,
             overrides: Optional[dict] = None,
             variant: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "ok": False}
    if variant:
        rec["variant"] = variant
        rec["overrides"] = {k: str(v) for k, v in (overrides or {}).items()}
    t0 = time.monotonic()
    with mesh:
        lowered, meta = lower_cell(arch, shape_name, mesh,
                                   overrides=overrides)
        rec.update(meta)
        compiled = lowered.compile()
        rec["compile_s"] = round(time.monotonic() - t0, 1)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)}
        rec["ok"] = True

        if with_cost and mesh_kind == "single":
            fits = {}
            for k in (1, 2):
                tl, _ = lower_cell(arch, shape_name, mesh,
                                   unroll_periods=k, impl="cost",
                                   overrides=overrides)
                tc = tl.compile()
                ca = tc.cost_analysis()
                colls = parse_collectives(tc.as_text())
                fits[k] = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0)),
                    "coll_wire": sum(wire_bytes(c) for c in colls),
                    "colls": colls,
                }
            cfg = configs.get(arch)
            plen = len(cfg.period)
            prefix = 1 if (cfg.moe.first_layer_dense
                           and cfg.moe.num_experts) else 0
            n_periods = (cfg.n_layers - prefix) // plen
            full = {}
            for key in ("flops", "bytes", "coll_wire"):
                b = fits[2][key] - fits[1][key]       # per period
                a = fits[1][key] - b                  # fixed part
                full[key] = a + b * n_periods
                full[key + "_per_period"] = b
                full[key + "_fixed"] = a
            rec["cost_fit"] = full
            rec["cost_points"] = {k: {kk: v[kk] for kk in
                                      ("flops", "bytes", "coll_wire")}
                                  for k, v in fits.items()}
            # collective mix at depth 2 (for the report's dominant-op line)
            mix: Dict[str, float] = {}
            for c in fits[2]["colls"]:
                mix[c["op"]] = mix.get(c["op"], 0.0) + wire_bytes(c)
            rec["coll_mix_k2"] = mix
    if verbose:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "ok", "compile_s")}))
    return rec


def save_rec(rec: dict):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{rec['variant']}" if rec.get("variant") else ""
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"
    (OUT_DIR / name).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-cost", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--variant", default="",
                    help="hillclimb variant: comma list of "
                         "moe_dispatch=cumsum, param_gather=bfloat16, "
                         "flat_dp")
    args = ap.parse_args()
    overrides = {}
    for item in args.variant.split(","):
        if not item:
            continue
        if "=" in item:
            k, v = item.split("=", 1)
            overrides[k] = v
        else:
            overrides[item] = True

    if args.all:
        cells = configs.all_cells()
    else:
        shapes = [args.shape] if args.shape else \
            list(configs.shapes_for(args.arch))
        cells = [(args.arch, s) for s in shapes]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch, shape in cells:
        for mk in meshes:
            suffix = f"__{args.variant.replace(',', '+').replace('=', '-')}" \
                if args.variant else ""
            out = OUT_DIR / f"{arch}_{shape}_{mk}{suffix}.json"
            if args.skip_done and out.exists() and \
                    json.loads(out.read_text()).get("ok"):
                continue
            try:
                rec = run_cell(arch, shape, mk,
                               with_cost=not args.no_cost,
                               overrides=overrides or None,
                               variant=args.variant.replace(",", "+")
                               .replace("=", "-"))
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mk,
                       "ok": False, "error": f"{type(e).__name__}: {e}"}
                failures.append((arch, shape, mk))
            save_rec(rec)
    if failures:
        print("FAILED CELLS:", failures)
        raise SystemExit(1)
    print("all requested cells OK")


if __name__ == "__main__":
    main()
