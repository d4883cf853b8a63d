"""Operations and bytes per call against hand counts at qwen widths."""
import json
import os
import sys
from pathlib import Path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench import cost, peaks  # noqa: E402

CONFIGS = Path(ROOT) / "bench" / "configs"


def shape(name):
    return cost.Shape.from_config(
        json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen_parameters_and_weight_bytes():
    s = shape("qwen1.5-0.5b")
    layer = (4 * 1024 * 1024 + 3 * 1024) + 3 * 1024 * 2816 + 2 * 1024
    assert s.layer_params() == layer
    assert s.params() == 24 * layer + 151936 * 1024 + 1024
    assert s.params() == pytest.approx(464e6, rel=0.001)
    flops, nbytes = cost.decode_cost(s, [])
    assert flops == 0
    assert nbytes == pytest.approx(0.928e9, rel=0.001)   # bf16, once


def test_qwen_decode_step_by_hand():
    s = shape("qwen1.5-0.5b")
    live = [100, 300]
    flops, nbytes = cost.decode_cost(s, live)
    per_tok = 2 * 24 * (4 * 1024 * 1024 + 3 * 1024 + 3 * 1024 * 2816) \
        + 2 * 1024 * 151936
    attn = 24 * 4 * 16 * 64 * (100 + 300)
    assert flops == 2 * per_tok + attn
    kv = 24 * 2 * 16 * 64 * 2 * (400 + 2)
    assert nbytes == 2 * s.params() + kv


def test_qwen_chunk_counts_real_tokens_and_final_logits():
    s = shape("qwen1.5-0.5b")
    f_mid, b_mid = cost.chunk_cost(s, 256, 256, final=False)
    f_end, b_end = cost.chunk_cost(s, 256, 256, final=True)
    assert f_end - f_mid == 2 * 1024 * 151936
    assert b_end - b_mid == 2 * 1024 * 151936
    ctx = sum(256 + i + 1 for i in range(256))
    assert f_mid == 256 * s.token_matmul_flops() + 24 * 4 * 16 * 64 * ctx


def test_granite_counts_every_expert_once_and_routed_flops():
    # granite-3.0-3b-a800m's widths, 16 of its 32 layers
    s = cost.Shape(d=1536, heads=24, kv_heads=8, head_dim=64, ff=512,
                   layers=16, vocab=49155, experts=40, top_k=8)
    moe = 40 * 3 * 1536 * 512 + 1536 * 40
    attn = 2 * 1536 * 24 * 64 + 2 * 1536 * 8 * 64
    assert s.layer_params() == attn + moe + 2 * 1536
    per_tok_ffn = 2 * (8 * 3 * 1536 * 512 + 1536 * 40)
    assert s.token_matmul_flops() == 16 * (2 * attn + per_tok_ffn)
    assert s.params() == pytest.approx(1.687e9, rel=0.002)


def test_roofline_takes_the_binding_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert cost.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert cost.roofline_seconds(1.0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
