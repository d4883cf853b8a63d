"""The work of the served requests, from client records alone, against
the per-call counts of ``cost.py``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench import cost, work  # noqa: E402

QWEN = cost.Shape(d=1024, heads=16, kv_heads=16, head_dim=64, ff=2816,
                  layers=24, vocab=151936, qkv_bias=True)


def rec(prompt, send, ttft_ms, resp, n_out, resume_at=0, ok=True):
    return {"prompt": [1] * prompt, "send": send, "ttft_ms": ttft_ms,
            "resp": resp, "n_out": n_out, "ok": ok, "resume_at": resume_at}


def test_decode_work_counts_each_later_token_at_its_live_length():
    r = rec(100, send=0.0, ttft_ms=100.0, resp=0.3, n_out=3)  # 0.1 .2 .3
    n, flops, kv = work.decode_work([r], QWEN, 0.0, 1.0)
    f_ref, b_ref = cost.decode_cost(QWEN, [101, 102])
    weights = cost.decode_cost(QWEN, [])[1]
    assert n == 2
    assert flops == pytest.approx(f_ref)
    assert kv == pytest.approx(b_ref - weights)
    # only the tokens made inside the span, none of a failed request
    assert work.decode_work([r], QWEN, 0.25, 1.0)[0] == 1
    assert work.decode_work([dict(r, ok=False)], QWEN, 0.0, 1.0)[0] == 0


def test_prefill_chunks_weigh_resume_and_whole_prefill_by_the_hit_share():
    r = rec(300, send=0.0, ttft_ms=50.0, resp=1.0, n_out=4, resume_at=250)
    chunks = work.prefill_chunks([r], QWEN, 128, 0.75, 0.0, 1.0)
    expect = [(0.75,) + cost.chunk_cost(QWEN, 250, 50, True)] + [
        (0.25,) + cost.chunk_cost(QWEN, off, n, off == 256)
        for off, n in ((0, 128), (128, 128), (256, 44))]
    assert chunks == pytest.approx(expect)
    fresh = dict(r, resume_at=0)
    assert [w for w, _, _ in work.prefill_chunks([fresh], QWEN, 128, 0.75,
                                                 0.0, 1.0)] == [1.0] * 3
    # a first token outside the span leaves the request out
    assert work.prefill_chunks([r], QWEN, 128, 0.75, 0.1, 1.0) == []


def test_follow_up_hit_share_from_the_gateways_counters():
    rs = [rec(300, 0.0, 50.0, 1.0, 4, resume_at=250),
          rec(200, 0.0, 50.0, 1.0, 4, resume_at=150),
          rec(200, 0.0, 50.0, 1.0, 4)]
    s0 = [{"prefix_hits": 3}, {"prefix_hits": 0}]
    s1 = [{"prefix_hits": 4}, {"prefix_hits": 0}]
    assert work.follow_up_hit_share(rs, s0, s1, 0.0, 1.0) == 0.5
    s1 = [{"prefix_hits": 9}, {"prefix_hits": 0}]
    assert work.follow_up_hit_share(rs, s0, s1, 0.0, 1.0) == 1.0
    assert work.follow_up_hit_share(rs[2:], s0, s1, 0.0, 1.0) == 0.0
