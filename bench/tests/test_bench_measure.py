"""Percentile, rate and lateness arithmetic on synthetic records."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from bench import measure  # noqa: E402


def rec(due, send, resp, ttft, n_out, ok=True):
    return {"due": due, "send": send, "resp": resp, "ttft_ms": ttft,
            "n_out": n_out, "ok": ok}


def test_percentile_is_linear_between_ranks():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert measure.percentile(xs, 0) == 1.0
    assert measure.percentile(xs, 50) == 3.0
    assert measure.percentile(xs, 100) == 5.0
    assert measure.percentile(xs, 95) == pytest.approx(4.8)
    assert measure.percentile([], 50) is None
    assert measure.percentile([7.0], 95) == 7.0


def test_ttft_counts_the_generator_wait():
    r = rec(due=10.0, send=10.004, resp=11.0, ttft=20.0, n_out=5)
    assert measure.ttft_ms(r) == pytest.approx(24.0)


def test_tpot_spreads_decode_time_over_later_tokens():
    r = rec(due=0.0, send=0.0, resp=1.0, ttft=100.0, n_out=10)
    assert measure.tpot_ms(r) == pytest.approx(100.0)
    assert measure.tpot_ms(rec(0, 0, 1, 100.0, 1)) is None


def test_end_to_end_window_rules():
    records = [
        rec(0.5, 0.5, 1.5, 10.0, 4),       # due before the window
        rec(1.0, 1.0, 2.0, 10.0, 4),       # due in it
        rec(2.0, 2.1, 3.0, 30.0, 8),       # due in it, answered in it
        rec(2.5, 2.5, 9.0, 50.0, 6),       # due in it, answered after
        rec(2.8, 2.8, 3.0, 0.0, 0, ok=False),
        rec(4.0, 4.0, 4.5, 5.0, 3),        # due after the window
    ]
    e = measure.end_to_end(records, 1.0, 4.0)
    assert e["attempted"] == 4 and e["failed"] == 1
    assert e["n_ttft"] == 3
    assert e["ttft_p50_ms"] == pytest.approx(50.0)    # 10, 130, 50
    # tokens produced inside [1, 4), each answer's tokens spread from its
    # first token to its end: 2 + 4 + 8 + 2 (0.51 s + 0.33 s steps,
    # 1.01 s + 0.33 s, 2.13 s on, 2.55 s + 1.29 s steps)
    assert e["output_tok_s"] == pytest.approx(16 / 3.0)
    # an answer still decoding at the close counts what it made inside
    late = [rec(3.0, 3.0, 5.0, 0.0, 3)]          # tokens at 3, 4, 5
    assert measure.end_to_end(late, 1.0, 4.0)["output_tok_s"] == \
        pytest.approx(1 / 3.0)


def test_token_times_spread_the_decode_span():
    r = rec(due=0.0, send=1.0, resp=2.1, ttft=100.0, n_out=3)
    assert measure.token_times(r) == pytest.approx([1.1, 1.6, 2.1])
    assert measure.token_times(rec(0, 1.0, 1.2, 50.0, 1)) == \
        pytest.approx([1.05])


def test_lateness():
    records = [rec(1.0, 1.002, 2.0, 1.0, 2), rec(2.0, 2.010, 3.0, 1.0, 2)]
    assert measure.lateness_ms(records) == pytest.approx([2.0, 10.0])
