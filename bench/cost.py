"""Operations and bytes each serving call needs, from shapes alone.

"Needed" is what the work asks for, whatever implements it, so a later
change that does less shows as a gain and never as a share over 100%:

  * weights count once per call at the compute dtype's width (bfloat16,
    2 bytes a parameter), although they are stored in float32;
  * every expert's weights count: a full decode batch routes to nearly
    all of them;
  * K/V count at the cache dtype, for live positions only, plus the
    positions written;
  * operations count for real tokens only: the active slots of a decode
    step, the prompt tokens of a chunk (not its padding), experts only
    for the tokens routed to them, and the vocabulary projection only
    where a token is sampled from it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Tuple

COMPUTE_BYTES = 2                     # bfloat16


@dataclass(frozen=True)
class Shape:
    d: int                            # hidden size
    heads: int
    kv_heads: int
    head_dim: int
    ff: int                           # MLP width, or one expert's width
    layers: int
    vocab: int
    experts: int = 0                  # 0: dense MLP
    top_k: int = 0
    qkv_bias: bool = False

    @classmethod
    def from_config(cls, c: Dict) -> "Shape":
        heads = c["num_attention_heads"]
        return cls(d=c["hidden_size"], heads=heads,
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // heads),
                   ff=c["intermediate_size"],
                   layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   qkv_bias=bool(c.get("qkv_bias", False)))

    # ---------------------------------------------------------- parameters
    def attn_params(self) -> int:
        q = self.d * self.heads * self.head_dim
        kv = 2 * self.d * self.kv_heads * self.head_dim
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return 2 * q + kv + bias                 # wq, wo, wk, wv

    def ffn_params(self) -> int:
        one = 3 * self.d * self.ff               # gate, up, down
        if self.experts:
            return self.experts * one + self.d * self.experts
        return one

    def layer_params(self) -> int:
        return self.attn_params() + self.ffn_params() + 2 * self.d

    def params(self) -> int:
        """All parameters, the tied embedding once."""
        return self.layers * self.layer_params() + self.vocab * self.d \
            + self.d

    # ----------------------------------------------------- per-token flops
    def token_matmul_flops(self) -> int:
        """Projections and FFN for one token, routed experts only."""
        attn = 2 * self.attn_params()
        if self.experts:
            ffn = 2 * (self.top_k * 3 * self.d * self.ff
                       + self.d * self.experts)
        else:
            ffn = 2 * 3 * self.d * self.ff
        return self.layers * (attn + ffn)

    def attention_flops(self, context: int) -> int:
        """QK^T and PV for one query over ``context`` positions."""
        return self.layers * 4 * self.heads * self.head_dim * context

    def unembed_flops(self) -> int:
        return 2 * self.d * self.vocab

    def kv_bytes_per_position(self, cache_bytes: int) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * cache_bytes


def decode_cost(s: Shape, live: Iterable[int], cache_bytes: int = 2
                ) -> Tuple[float, float]:
    """One decode step over the active slots; ``live`` gives each slot's
    attended positions (its position + 1).  Returns (flops, bytes)."""
    live = list(live)
    n = len(live)
    flops = n * (s.token_matmul_flops() + s.unembed_flops()) \
        + sum(s.attention_flops(t) for t in live)
    weights = COMPUTE_BYTES * (s.layers * s.layer_params()
                               + s.vocab * s.d + s.d)
    kv = s.kv_bytes_per_position(cache_bytes) * (sum(live) + n)
    return float(flops), float(weights + kv)


def chunk_cost(s: Shape, offset: int, n_real: int, final: bool,
               cache_bytes: int = 2) -> Tuple[float, float]:
    """One prefill chunk of ``n_real`` prompt tokens at absolute
    positions ``offset..offset+n_real``; the vocabulary projection is
    needed only for the last token of the final chunk."""
    ctx = sum(offset + i + 1 for i in range(n_real))
    flops = n_real * s.token_matmul_flops() + s.attention_flops(ctx) \
        + (s.unembed_flops() if final else 0)
    weights = COMPUTE_BYTES * (s.layers * s.layer_params() + s.d
                               + n_real * s.d
                               + (s.vocab * s.d if final else 0))
    kv = s.kv_bytes_per_position(cache_bytes) * (offset + 2 * n_real)
    return float(flops), float(weights + kv)


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bw"])
