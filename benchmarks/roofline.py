"""Roofline analysis from the dry-run JSON records.

Per (arch × shape), single-pod mesh (256 chips of TPU v5e):
  compute   = HLO_FLOPs / peak_FLOPs                  [per chip, seconds]
  memory    = HLO_bytes / HBM_bw                      [per chip, seconds]
  collective= wire_bytes / (links_per_ring × link_bw) [per chip, seconds]

FLOPs/bytes/wire come from the dry-run's 2-point unrolled-depth linear
fit (exact at full depth; see launch/dryrun.py).  The memory term is
reported twice:
  * ``mem_hlo``   — straight XLA "bytes accessed" (includes the S×T score
    materialization of the *CPU-lowered* attention; an upper bound);
  * ``mem_adj``   — kernel-adjusted: the attention-score materialization
    bytes are replaced by the Pallas flash kernel's actual HBM traffic
    (q,k,v read once per q-block pass + o written), which is what the TPU
    target executes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from repro import configs
from repro.configs.base import SHAPES
from repro.launch.mesh import hw_for

DRYRUN_DIR = Path("experiments/dryrun")
CHIPS_SINGLE = 256
# the production pod the dry-run models is TPU v5e
HW = hw_for("TPU v5 lite")


def attention_adjustment(arch: str, shape_name: str) -> Dict[str, float]:
    """Estimate (per device) the cost-mode attention materialization bytes
    and the flash-kernel replacement traffic."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        # decode materializes (B,H,1,T) logits — tiny; no adjustment
        return {"mat": 0.0, "flash": 0.0}
    n_dev = CHIPS_SINGLE
    # per-kind effective kv length
    mat = 0.0
    flash = 0.0
    fwd_bwd = 3.0 if shape.kind == "train" else 1.0     # bwd ~2x fwd
    # ~6 materialized (B,H,S,T)-sized f32 tensors across fwd+bwd softmax
    K_MAT = 6.0 if shape.kind == "train" else 3.0
    D = cfg.hd
    for i in range(cfg.n_layers):
        kind = cfg.kind_at(i)
        if kind not in ("attn", "local", "global"):
            continue
        T_eff = min(2 * cfg.window, S) if kind == "local" else S
        mat += B * cfg.n_heads * S * T_eff * 4.0 * K_MAT
        # flash: q read once, k/v read once per q-block sweep (block 128),
        # o written once — per head-dim D bytes bf16
        passes = max(S // 128, 1)
        flash += fwd_bwd * B * 2.0 * (
            cfg.n_heads * S * D + cfg.n_kv_heads * T_eff * D * 1) \
            + B * cfg.n_kv_heads * T_eff * D * 2.0 * passes * 0.0
        # conservative flash traffic: q+o (+dq etc) once, k/v once per pass
        flash += fwd_bwd * B * cfg.n_kv_heads * T_eff * D * 2.0
    if cfg.n_enc_layers:
        F = cfg.frontend_seq
        mat += cfg.n_enc_layers * B * cfg.n_heads * F * F * 4.0 * K_MAT
        mat += cfg.n_layers * B * cfg.n_heads * S * F * 4.0 * K_MAT
    return {"mat": mat / n_dev, "flash": flash / n_dev}


def analyze_record(rec: dict) -> Optional[dict]:
    if not rec.get("ok") or "cost_fit" not in rec:
        return None
    arch, shape_name = rec["arch"], rec["shape"]
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    fit = rec["cost_fit"]

    flops = fit["flops"]
    bytes_hlo = fit["bytes"]
    wire = fit["coll_wire"]

    adj = attention_adjustment(arch, shape_name)
    bytes_adj = max(bytes_hlo - adj["mat"] + adj["flash"], 0.0)

    t_compute = flops / HW["peak_flops_bf16"]
    t_mem_hlo = bytes_hlo / HW["hbm_bw"]
    t_mem_adj = bytes_adj / HW["hbm_bw"]
    t_coll = wire / (HW["ici_links_per_ring"] * HW["ici_link_bw"])

    terms = {"compute": t_compute, "memory": t_mem_adj,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    # roofline fraction: useful-compute time / bound time
    tokens = shape.tokens
    if shape.kind == "decode":
        tokens = shape.global_batch
    n_active = cfg.active_param_count()
    model_flops_global = (6 if shape.kind == "train" else 2) \
        * n_active * tokens
    model_flops = model_flops_global / CHIPS_SINGLE
    t_useful = model_flops / HW["peak_flops_bf16"]
    if shape.kind == "decode":
        # decode is bandwidth-bound by construction: utilization = the
        # unavoidable traffic (params once + cache once per step) over
        # the achieved bound
        ideal_bytes = (2.0 * n_active
                       + rec.get("cache_bytes_analytic", 0)
                       * CHIPS_SINGLE) / CHIPS_SINGLE
        t_useful = ideal_bytes / HW["hbm_bw"]
    frac = t_useful / bound if bound > 0 else 0.0

    lever = {
        "compute": "cut non-useful FLOPs (remat policy, capacity factor, "
                   "padding) or raise MXU utilization (tile alignment)",
        "memory": "fuse/stream the dominant materialization (flash-style "
                  "blocking), cast accumulations bf16, shard longer dims",
        "collective": "reshard to cut the dominant collective (less TP "
                      "for small models, sequence-parallel boundaries, "
                      "overlap via scan structure)",
    }[dominant]

    return {
        "arch": arch, "shape": shape_name,
        "flops_dev": flops, "bytes_dev_hlo": bytes_hlo,
        "bytes_dev_adj": bytes_adj, "wire_dev": wire,
        "t_compute": t_compute, "t_mem_hlo": t_mem_hlo,
        "t_mem_adj": t_mem_adj, "t_coll": t_coll,
        "dominant": dominant,
        "model_flops_dev": model_flops,
        "useful_ratio": model_flops / flops if flops else 0.0,
        "roofline_frac": frac,
        "lever": lever,
        "coll_mix": rec.get("coll_mix_k2", {}),
        "memory_analysis": rec.get("memory", {}),
    }


def load_all(mesh: str = "single") -> List[dict]:
    rows = []
    for f in sorted(DRYRUN_DIR.glob(f"*_{mesh}.json")):
        rec = json.loads(f.read_text())
        row = analyze_record(rec)
        if row:
            rows.append(row)
    return rows


def table(rows: List[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s (hlo→adj) | coll s | "
           "dominant | 6ND/HLO | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute']:.3g} | "
            f"{r['t_mem_hlo']:.3g}→{r['t_mem_adj']:.3g} | "
            f"{r['t_coll']:.3g} | {r['dominant']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_frac']:.3f} |")
    return hdr + "\n".join(lines)


def main():
    rows = load_all("single")
    print(table(rows))
    print()
    for r in sorted(rows, key=lambda r: r["roofline_frac"])[:5]:
        print(f"worst: {r['arch']} {r['shape']} frac={r['roofline_frac']:.3f}"
              f" dominant={r['dominant']} -> {r['lever']}")


if __name__ == "__main__":
    main()
