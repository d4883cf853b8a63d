"""Model assembly: blocks → period-scanned stacks → Model API.

Layers are stacked with ``lax.scan`` over *periods* (the repeating block
pattern, e.g. gemma3's 5×local+1×global) so compile time stays flat in
depth; heterogeneous trailing layers and special first layers (deepseek's
dense layer 0) are unrolled.

The Model API (all pure functions of (params, inputs)):
  * ``init(rng)``                          → P-tree (arrays + logical axes)
  * ``loss_fn(params, batch, ...)``        → (loss, metrics)      [train]
  * ``prefill(params, batch, ...)``        → (last_logits, cache) [serve]
  * ``decode_step(params, cache, tok, pos)``→ (logits, new cache) [serve]
  * ``cache_specs(batch, cache_len)``      → P-tree of zeroed caches
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ATTN_KINDS, ModelConfig
from . import attention as attn_mod
from ..kernels import ops
from . import moe as moe_mod
from . import rglru_block, ssd_block
from .common import (P, dense_p, embed_params, embed_tokens, chunked_ce_loss,
                     ones_p, rms_norm, stack_p, unembed, unzip)

AUX_KEYS = ("moe_lb", "moe_z")


def _zero_aux():
    return {k: jnp.float32(0) for k in AUX_KEYS}


def _add_aux(a, b):
    return {k: a[k] + b.get(k, 0.0) for k in AUX_KEYS}


# ===========================================================================
# single block
# ===========================================================================
def block_params(cfg: ModelConfig, rng, kind: str, path, *,
                 dense_ff: Optional[int] = None, cross: bool = False,
                 e_pad: Optional[int] = None) -> dict:
    """Parameters for one block of the given kind."""
    from .common import mlp_params
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": ones_p((d,), ("embed",), dt)}
    if kind in ATTN_KINDS:
        p["attn"] = attn_mod.attn_params(cfg, rng, path + ("attn",))
    elif kind == "rglru":
        p["rec"] = rglru_block.rglru_params(cfg, rng, path + ("rec",))
    elif kind == "ssd":
        p["rec"] = ssd_block.ssd_params(cfg, rng, path + ("rec",))
    else:
        raise ValueError(kind)
    if cross:
        p["cross_norm"] = ones_p((d,), ("embed",), dt)
        p["cross"] = attn_mod.attn_params(cfg, rng, path + ("cross",))
    # feed-forward half (ssd blocks have none; d_ff == 0)
    if cfg.d_ff > 0 or dense_ff:
        if cfg.moe.num_experts and dense_ff is None:
            p["moe"] = moe_mod.moe_params(cfg, rng, path + ("moe",),
                                          e_pad=e_pad)
        else:
            p["mlp"] = mlp_params(cfg, rng, path + ("mlp",), d_ff=dense_ff)
        if not cfg.parallel_block:
            p["norm2"] = ones_p((d,), ("embed",), dt)
    return p


def _ffn(cfg, p, x, *, spmd, capacity_factor, impl, dropless=False):
    from .common import mlp_apply
    if "moe" in p:
        return moe_mod.moe_apply(cfg, p["moe"], x, spmd=spmd,
                                 capacity_factor=capacity_factor,
                                 dropless=dropless, impl=impl)
    return mlp_apply(cfg, p["mlp"], x), {}


def block_apply(cfg: ModelConfig, p: dict, x, kind: str, *,
                mode: str,     # "train" | "prefill" | "chunk" | "decode"
                cache: Optional[dict] = None,
                pos=None, cache_len: int = 0,
                prefix_len=None, spmd=None, impl: str = "auto",
                capacity_factor: Optional[float] = None,
                memory_kv: Optional[dict] = None,
                causal: bool = True,
                inner_sharding=None, layer=None):
    """Apply one block. Returns (x, aux, new_cache).

    ``inner_sharding``: optional constraint on the post-norm activations —
    under sequence-parallel residuals this pins ONE gather point that both
    the attention and (parallel-block) MLP branches consume, instead of
    letting GSPMD reshard per consumer.

    ``layer`` (decode only): ``cache`` is the stack of this block's
    position in the period, and the block reads and writes its own layer
    of it."""
    aux = {}
    new_cache = dict(cache) if cache is not None else None
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if inner_sharding is not None:
        h = jax.lax.with_sharding_constraint(h, inner_sharding)

    if kind in ATTN_KINDS:
        if mode == "train":
            mix = attn_mod.attn_apply(cfg, p["attn"], h, kind=kind,
                                      causal=causal, prefix_len=prefix_len,
                                      impl=impl)
        elif mode == "prefill":
            mix, kv = attn_mod.attn_prefill(cfg, p["attn"], h, kind=kind,
                                            cache_len=cache_len,
                                            prefix_len=prefix_len, impl=impl)
            new_cache = dict(new_cache or {}); new_cache.update(kv)
        elif mode == "chunk":
            kv = {"k": cache["k"], "v": cache["v"]}
            mix, kv = attn_mod.attn_prefill_chunk(cfg, p["attn"], h, kv,
                                                  pos, kind=kind,
                                                  prefix_len=prefix_len)
            new_cache.update(kv)
        else:
            kv = {"k": cache["k"], "v": cache["v"]}
            mix, kv = attn_mod.attn_decode(cfg, p["attn"], h, kv, pos,
                                           kind=kind, layer=layer, impl=impl)
            new_cache.update(kv)
    elif kind == "rglru":
        if mode == "chunk":
            raise ValueError("chunked prefill requires attention-family "
                             "blocks (rglru carries no resumable prefill "
                             "state)")
        if mode == "decode":
            mix, st = rglru_block.rglru_block_decode(
                cfg, p["rec"], h, _layer_of(cache, layer))
            new_cache.update(_into_layer(cache, st, layer))
        else:
            mix, st = rglru_block.rglru_block_apply(
                cfg, p["rec"], h, impl=impl, want_cache=(mode == "prefill"))
            if mode == "prefill":
                new_cache = st
    elif kind == "ssd":
        if mode == "chunk":
            raise ValueError("chunked prefill requires attention-family "
                             "blocks (ssd carries no resumable prefill "
                             "state)")
        if mode == "decode":
            mix, st = ssd_block.ssd_block_decode(
                cfg, p["rec"], h, _layer_of(cache, layer))
            new_cache.update(_into_layer(cache, st, layer))
        else:
            mix, st = ssd_block.ssd_block_apply(
                cfg, p["rec"], h, impl=impl, want_cache=(mode == "prefill"))
            if mode == "prefill":
                new_cache = st
    else:
        raise ValueError(kind)

    # serving is dropless unless an explicit capacity factor is given
    # (training always uses the configured capacity factor)
    dropless = mode != "train" and capacity_factor is None
    if cfg.parallel_block and ("mlp" in p or "moe" in p):
        y, aux = _ffn(cfg, p, h, spmd=spmd, capacity_factor=capacity_factor,
                      impl=impl, dropless=dropless)
        x = x + _residual(cfg, mix) + _residual(cfg, y)
    else:
        x = x + _residual(cfg, mix)
        if "cross" in p and memory_kv is not None:
            hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
            x = x + _residual(cfg, attn_mod.cross_attn_apply(
                cfg, p["cross"], hc, memory_kv, impl=impl))
        if "mlp" in p or "moe" in p:
            h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            y, aux = _ffn(cfg, p, h2, spmd=spmd,
                          capacity_factor=capacity_factor, impl=impl,
                          dropless=dropless)
            x = x + _residual(cfg, y)
    return x, aux, new_cache


def _residual(cfg: ModelConfig, branch):
    """A branch's output as it joins the residual stream: scaled by
    ``residual_multiplier`` (granite), untouched at 1."""
    if cfg.residual_multiplier == 1.0:
        return branch
    return branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)


def _layer_of(cache, layer):
    """One layer of a stacked recurrent state (the state itself when
    ``layer`` is None)."""
    if layer is None:
        return cache
    return {n: a[layer] for n, a in cache.items()}


def _into_layer(cache, state, layer):
    """``state`` written back at ``layer`` of the stacked ``cache``."""
    if layer is None:
        return state
    return {n: jax.lax.dynamic_update_index_in_dim(
        cache[n], state[n].astype(cache[n].dtype), layer, 0) for n in state}


def _experts_in_place(layer_p, stacks, layer):
    """A scanned period's block params, each MoE block's expert matrices
    swapped for the stack's in ``stacks`` (``params["periods"]``) and
    ``layer``, the scan's index: serving reads a layer's experts where
    they lie (:func:`moe.with_stacked_experts`)."""
    return tuple(
        dict(p, moe=moe_mod.with_stacked_experts(p["moe"], s["moe"], layer))
        if "moe" in p else p for p, s in zip(layer_p, stacks))


# ===========================================================================
# the Model
# ===========================================================================
class Model:
    """One architecture, parameterized by its ModelConfig."""

    def __init__(self, cfg: ModelConfig, e_pad: Optional[int] = None,
                 unroll: bool = False):
        self.cfg = cfg
        self.unroll = unroll
        self.e_pad = e_pad or (moe_mod.padded_experts(cfg, 1)
                               if cfg.moe.num_experts else None)
        # layout: [prefix (unrolled)] + n_scan periods + [trailing (unrolled)]
        self.prefix_count = 1 if (cfg.moe.first_layer_dense
                                  and cfg.moe.num_experts) else 0
        rest = cfg.n_layers - self.prefix_count
        if unroll:
            # cost-compile mode: every layer unrolled (exact FLOP counting)
            self.n_scan_periods = 0
            self.trailing_kinds = tuple(
                cfg.kind_at(self.prefix_count + i) for i in range(rest))
        else:
            self.n_scan_periods = rest // len(cfg.period)
            self.trailing_kinds = tuple(
                cfg.kind_at(self.prefix_count + self.n_scan_periods
                            * len(cfg.period) + i)
                for i in range(rest % len(cfg.period)))
        self.is_encdec = cfg.n_enc_layers > 0

    # ------------------------------------------------------------------ init
    def init(self, rng) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        params: Dict[str, Any] = {"embed": embed_params(cfg, rng)}
        cross = self.is_encdec

        if self.prefix_count:
            params["prefix"] = tuple(
                block_params(cfg, rng, cfg.kind_at(i), ("prefix", i),
                             dense_ff=cfg.moe.first_dense_ff or cfg.d_ff,
                             cross=cross, e_pad=self.e_pad)
                for i in range(self.prefix_count))

        plen = len(cfg.period)
        periods = []
        if self.n_scan_periods:
            for pos in range(plen):
                kind = cfg.period[pos]
                layers = [
                    block_params(cfg, rng, kind,
                                 ("scan", j * plen + pos), cross=cross,
                                 e_pad=self.e_pad)
                    for j in range(self.n_scan_periods)]
                periods.append(stack_p(layers))
        params["periods"] = tuple(periods)

        params["trailing"] = tuple(
            block_params(cfg, rng, kind, ("trailing", i), cross=cross,
                         e_pad=self.e_pad)
            for i, kind in enumerate(self.trailing_kinds))

        params["final_norm"] = ones_p((cfg.d_model,), ("embed",), dt)

        if self.is_encdec:
            enc_layers = [
                block_params(cfg, rng, "attn", ("enc", i), e_pad=None)
                for i in range(cfg.n_enc_layers)]
            params["encoder"] = {
                "stack": stack_p(enc_layers),
                "final_norm": ones_p((cfg.d_model,), ("embed",), dt),
            }
        return params

    # -------------------------------------------------------------- embedding
    def _embed_inputs(self, params, batch):
        """Token (+ modality-stub) embedding → (h, prefix_len)."""
        cfg = self.cfg
        emb = params["embed"]
        prefix_len = None
        if cfg.family == "vlm" and "frontend" in batch:
            cdt = jnp.dtype(cfg.compute_dtype)
            patches = batch["frontend"].astype(cdt) @ \
                emb["frontend_proj"].astype(cdt)           # (B,F,d)
            text = embed_tokens(cfg, emb, batch["tokens"])
            h = jnp.concatenate([patches, text], axis=1)
            prefix_len = jnp.int32(cfg.frontend_seq)
            if cfg.prefix_lm:
                pass                                        # mask uses prefix_len
            else:
                prefix_len = None
        else:
            h = embed_tokens(cfg, emb, batch["tokens"])
            if cfg.prefix_lm and "prefix_len" in batch:
                prefix_len = batch["prefix_len"]
        return h, prefix_len

    def _encode(self, params, batch, *, impl):
        """Encoder for enc-dec families: frontend frames → memory."""
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        mem = batch["frontend"].astype(cdt) @ \
            params["embed"]["frontend_proj"].astype(cdt)

        def body(h, layer_p):
            h, _, _ = block_apply(cfg, layer_p, h, "attn", mode="train",
                                  causal=False, impl=impl)
            return h, None

        mem, _ = jax.lax.scan(body, mem, params["encoder"]["stack"])
        return rms_norm(mem, params["encoder"]["final_norm"], cfg.norm_eps)

    # ------------------------------------------------------------------ train
    def loss_fn(self, params, batch, *, spmd=None, impl: str = "auto",
                remat: str = "block", z_coef: float = 1e-4,
                act_sharding=None, logits_sharding=None,
                inner_sharding=None, ce_chunk: int = 512):
        """Teacher-forced LM loss. batch: tokens (B,S), targets (B,S),
        optional frontend. params: plain value tree (not P-tree).
        ``act_sharding``: optional sharding constraint applied to the
        residual stream at block boundaries (sequence-parallel layout for
        big-model memory)."""
        cfg = self.cfg

        def constrain(h):
            if act_sharding is not None:
                return jax.lax.with_sharding_constraint(h, act_sharding)
            return h

        h, prefix_len = self._embed_inputs(params, batch)
        h = constrain(h)
        memory_kv = None
        if self.is_encdec:
            memory = self._encode(params, batch, impl=impl)
            # cross K/V are shared across decoder layers' own projections —
            # each layer computes its own K/V from memory inside the block;
            # we pass the memory through a per-layer projection lazily.
            memory_kv = memory   # sentinel: projected per block below

        aux = _zero_aux()

        def apply_one(h, p, kind, aux):
            mkv = None
            if memory_kv is not None and "cross" in p:
                mkv = attn_mod.cross_kv(cfg, p["cross"], memory_kv)
            h, a, _ = block_apply(cfg, p, h, kind, mode="train",
                                  prefix_len=prefix_len, spmd=spmd,
                                  impl=impl, memory_kv=mkv,
                                  inner_sharding=inner_sharding)
            return constrain(h), _add_aux(aux, a)

        for p in params.get("prefix", ()):
            h, aux = apply_one(h, p, cfg.period[0] if cfg.period[0] not in
                               ("rglru", "ssd") else cfg.period[0], aux)

        plen = len(cfg.period)

        def period_body(carry, xs):
            h, aux = carry
            for pos in range(plen):
                h, aux = apply_one(h, xs[pos], cfg.period[pos], aux)
            return (h, aux), None

        body = period_body
        if remat == "block":
            body = jax.checkpoint(period_body, prevent_cse=False)
        if self.n_scan_periods:
            (h, aux), _ = jax.lax.scan(body, (h, aux), params["periods"])

        for p, kind in zip(params["trailing"], self.trailing_kinds):
            h, aux = apply_one(h, p, kind, aux)

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        loss, metrics = chunked_ce_loss(cfg, params["embed"], h,
                                        batch["targets"], z_coef=z_coef,
                                        chunk=ce_chunk,
                                        logits_sharding=logits_sharding)
        for k in AUX_KEYS:
            loss = loss + aux[k]
            metrics[k] = aux[k]
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------ serve
    def prefill(self, params, batch, *, cache_len: Optional[int] = None,
                spmd=None, impl: str = "auto",
                capacity_factor: Optional[float] = None,
                act_sharding=None):
        """Prompt pass. Returns (last_logits (B,V), cache pytree)."""
        cfg = self.cfg
        h, prefix_len = self._embed_inputs(params, batch)
        if act_sharding is not None:
            h = jax.lax.with_sharding_constraint(h, act_sharding)
        S = h.shape[1]
        cache_len = cache_len or S
        memory = self._encode(params, batch, impl=impl) if self.is_encdec \
            else None
        cache: Dict[str, Any] = {}

        def apply_one(h, p, kind):
            mkv = None
            if memory is not None and "cross" in p:
                mkv = attn_mod.cross_kv(cfg, p["cross"], memory)
            h, _, c = block_apply(cfg, p, h, kind, mode="prefill",
                                  cache_len=cache_len, prefix_len=prefix_len,
                                  spmd=spmd, impl=impl,
                                  capacity_factor=capacity_factor,
                                  memory_kv=mkv)
            if mkv is not None:
                c = dict(c or {}); c["cross_k"] = mkv["k"]; c["cross_v"] = mkv["v"]
            if act_sharding is not None:
                h = jax.lax.with_sharding_constraint(h, act_sharding)
            return h, c

        cache["prefix"] = []
        for p in params.get("prefix", ()):
            h, c = apply_one(h, p, cfg.period[0])
            cache["prefix"].append(c)
        cache["prefix"] = tuple(cache["prefix"])

        plen = len(cfg.period)

        def period_body(h, xs):
            layer_p, layer = xs
            layer_p = _experts_in_place(layer_p, params["periods"], layer)
            cs = []
            for pos in range(plen):
                h, c = apply_one(h, layer_p[pos], cfg.period[pos])
                cs.append(c)
            return h, tuple(cs)

        if self.n_scan_periods:
            h, cache["periods"] = jax.lax.scan(
                period_body, h, (params["periods"], self._moe_layer_ids()))
        else:
            cache["periods"] = ()

        cache["trailing"] = []
        for p, kind in zip(params["trailing"], self.trailing_kinds):
            h, c = apply_one(h, p, kind)
            cache["trailing"].append(c)
        cache["trailing"] = tuple(cache["trailing"])

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], h[:, -1:])[:, 0]
        return logits, cache

    def decode_step(self, params, cache, tokens, pos, *, spmd=None,
                    impl: str = "auto"):
        """One token for every sequence. tokens: (B,1); pos: a scalar int32
        or (B,), each slot's position.  Returns (logits (B,V), new cache).

        The layer scan carries the stacked cache and hands each block its
        layer index: each attention layer writes its new K/V rows at
        (layer, slot, pos) and reads the stack where it lies, so under a
        jit that donates the cache the step updates it in place.
        Cross-attention K/V pass through unwritten."""
        cfg = self.cfg
        h = embed_tokens(cfg, params["embed"], tokens)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape[:1])

        def apply_one(h, p, kind, c, layer=None):
            mkv = None
            if "cross_k" in c:
                mkv = {"k": c["cross_k"], "v": c["cross_v"]}
                if layer is not None:
                    mkv = {n: a[layer] for n, a in mkv.items()}
            h, _, nc = block_apply(cfg, p, h, kind, mode="decode", cache=c,
                                   pos=pos, spmd=spmd, impl=impl,
                                   capacity_factor=None, memory_kv=mkv,
                                   layer=layer)
            return h, nc

        new_cache: Dict[str, Any] = {}
        new_cache["prefix"] = []
        for p, c in zip(params.get("prefix", ()), cache.get("prefix", ())):
            h, nc = apply_one(h, p, cfg.period[0], c)
            new_cache["prefix"].append(nc)
        new_cache["prefix"] = tuple(new_cache["prefix"])

        plen = len(cfg.period)

        def period_body(carry, xs):
            h, stacks = carry
            layer_p, layer = xs
            layer_p = _experts_in_place(layer_p, params["periods"], layer)
            stacks = list(stacks)
            for posn in range(plen):
                h, stacks[posn] = apply_one(h, layer_p[posn],
                                            cfg.period[posn], stacks[posn],
                                            layer)
            return (h, tuple(stacks)), None

        if self.n_scan_periods:
            (h, new_cache["periods"]), _ = jax.lax.scan(
                period_body, (h, cache["periods"]),
                (params["periods"],
                 jnp.arange(self.n_scan_periods, dtype=jnp.int32)))
        else:
            new_cache["periods"] = ()

        new_cache["trailing"] = []
        for (p, kind), c in zip(zip(params["trailing"], self.trailing_kinds),
                                cache["trailing"]):
            h, nc = apply_one(h, p, kind, c)
            new_cache["trailing"].append(nc)
        new_cache["trailing"] = tuple(new_cache["trailing"])

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], h)[:, 0]
        return logits, new_cache

    # ------------------------------------------------------------ MoE work
    def _moe_layer_ids(self):
        """The layer scan's index as a scanned input, for the MoE blocks
        to find their experts in the stack (:func:`_experts_in_place`);
        None, an input of no arrays, for a model without experts."""
        if not self.moe_layers:
            return None
        return jnp.arange(self.n_scan_periods, dtype=jnp.int32)

    @property
    def moe_layers(self) -> int:
        """Layers whose feed-forward half is the mixture of experts."""
        cfg = self.cfg
        if not cfg.moe.num_experts or cfg.d_ff <= 0:
            return 0
        return cfg.n_layers - self.prefix_count

    def moe_rows(self, tokens: int, impl: str = "auto") -> int:
        """Expert-FFN rows that a serving call over ``tokens`` tokens
        computes, summed over the stack: in each MoE layer the dropless
        dispatch's grouped matmul over the call's tokens × top-k routed
        rows, as the ``impl`` path multiplies them (the kernel pads them
        to its row tile)."""
        if not self.moe_layers:
            return 0
        rows = ops.grouped_matmul_rows(tokens * self.cfg.moe.top_k,
                                       self.e_pad, impl)
        return rows * self.moe_layers

    # ------------------------------------------------------------ chunked
    @property
    def supports_chunked_prefill(self) -> bool:
        """True when the prompt can be prefilled in fixed-size chunks
        (and a session's KV resumed at an offset): every block must
        support continuation against an absolute-position cache.
        Attention caches do; the recurrent families (rglru/ssd) expose
        no carried-state prefill, and prefix-LM masks / enc-dec
        cross-attention are whole-prompt constructs."""
        kinds = set(self.cfg.period) | set(self.trailing_kinds)
        if self.prefix_count:
            kinds.add(self.cfg.kind_at(0))
        return (not self.is_encdec and not self.cfg.prefix_lm
                and all(k in ATTN_KINDS for k in kinds))

    def prefill_chunk(self, params, cache, tokens, offset, *, spmd=None,
                      impl: str = "auto"):
        """One fixed-size prefill chunk: ``tokens`` (B,C) land at
        absolute positions ``offset .. offset+C`` of an existing
        full-length cache (zeroed for a fresh prompt; a pinned session's
        KV for a resumed one).  Returns (logits (B,C,V), new cache) —
        the caller samples from the position of the last *real* prompt
        token once the final chunk lands.  Requires
        :attr:`supports_chunked_prefill`."""
        cfg = self.cfg
        h = embed_tokens(cfg, params["embed"], tokens)

        def apply_one(h, p, kind, c):
            h, _, nc = block_apply(cfg, p, h, kind, mode="chunk", cache=c,
                                   pos=offset, spmd=spmd, impl=impl,
                                   capacity_factor=None)
            return h, nc

        new_cache: Dict[str, Any] = {}
        new_cache["prefix"] = []
        for p, c in zip(params.get("prefix", ()), cache.get("prefix", ())):
            h, nc = apply_one(h, p, cfg.period[0], c)
            new_cache["prefix"].append(nc)
        new_cache["prefix"] = tuple(new_cache["prefix"])

        plen = len(cfg.period)

        def period_body(h, xs):
            layer_p, layer_c, layer = xs
            layer_p = _experts_in_place(layer_p, params["periods"], layer)
            ncs = []
            for posn in range(plen):
                h, nc = apply_one(h, layer_p[posn], cfg.period[posn],
                                  layer_c[posn])
                ncs.append(nc)
            return h, tuple(ncs)

        if self.n_scan_periods:
            h, new_cache["periods"] = jax.lax.scan(
                period_body, h,
                (params["periods"], cache["periods"],
                 self._moe_layer_ids()))
        else:
            new_cache["periods"] = ()

        new_cache["trailing"] = []
        for (p, kind), c in zip(zip(params["trailing"], self.trailing_kinds),
                                cache["trailing"]):
            h, nc = apply_one(h, p, kind, c)
            new_cache["trailing"].append(nc)
        new_cache["trailing"] = tuple(new_cache["trailing"])

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return unembed(cfg, params["embed"], h), new_cache

    # ------------------------------------------------------------------ specs
    def cache_specs(self, batch_size: int, cache_len: int,
                    dtype=jnp.bfloat16) -> dict:
        """P-tree of zeroed decode caches (axes included for sharding)."""
        cfg = self.cfg

        def one(kind):
            if kind in ATTN_KINDS:
                # lane-dense: the kv heads side by side in the minor dim
                shape = (batch_size, cache_len, cfg.n_kv_heads * cfg.hd)
                axes = ("batch", "kv_seq", "kv_heads_dim")
                c = {"k": P(jnp.zeros(shape, dtype), axes),
                     "v": P(jnp.zeros(shape, dtype), axes)}
            elif kind == "rglru":
                s = rglru_block.rglru_cache_spec(cfg, batch_size, dtype)
                c = {"h": P(s["h"], ("batch", "lru")),
                     "conv": P(s["conv"], ("batch", "conv", "lru"))}
            elif kind == "ssd":
                s = ssd_block.ssd_cache_spec(cfg, batch_size, dtype)
                c = {"h": P(s["h"], ("batch", "ssm_heads", "head_dim", "state")),
                     "conv": P(s["conv"], ("batch", "conv", "conv_ch"))}
            else:
                raise ValueError(kind)
            if self.is_encdec:
                c["cross_k"] = P(jnp.zeros((batch_size, cfg.frontend_seq,
                                            cfg.n_kv_heads, cfg.hd), dtype),
                                 ("batch", "enc_seq", "kv_heads", "head_dim"))
                c["cross_v"] = P(jnp.zeros((batch_size, cfg.frontend_seq,
                                            cfg.n_kv_heads, cfg.hd), dtype),
                                 ("batch", "enc_seq", "kv_heads", "head_dim"))
            return c

        def stack_cache(c):
            return jax.tree_util.tree_map(
                lambda p: P(jnp.zeros((self.n_scan_periods,) + p.value.shape,
                                      p.value.dtype), ("layers",) + p.axes),
                c, is_leaf=lambda x: isinstance(x, P))

        cache = {
            "prefix": tuple(one(cfg.period[0])
                            for _ in range(self.prefix_count)),
            "periods": tuple(stack_cache(one(k)) for k in cfg.period)
            if self.n_scan_periods else (),
            "trailing": tuple(one(k) for k in self.trailing_kinds),
        }
        return cache
