"""Arithmetic over request records: percentiles, rates, lateness.

A record is one request as the client saw it, every time on the host's
monotonic clock in seconds:

  ``due``   when the schedule wanted it sent
  ``send``  when the client put it on the wire
  ``resp``  when the answer came back (None: never)
  ``ttft_ms`` the gateway's submit-to-first-token time
  ``n_out`` tokens in the answer; ``ok`` whether it succeeded
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between closest ranks
    (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(r: dict) -> float:
    """Time to first token as the user feels it: the wait between when
    the request was due and when it was sent, plus the gateway's
    submit-to-first-token time."""
    return (r["send"] - r["due"]) * 1e3 + r["ttft_ms"]


def tpot_ms(r: dict) -> Optional[float]:
    """Mean gap between output tokens after the first, from the client's
    side; None for answers of fewer than two tokens."""
    if r["n_out"] < 2:
        return None
    decode_ms = (r["resp"] - r["send"]) * 1e3 - r["ttft_ms"]
    return decode_ms / (r["n_out"] - 1)


def token_times(r: dict) -> List[float]:
    """When each output token of an answered request was produced, on
    the host clock: the first at ``ttft_ms`` after the send, the others
    evenly over the rest of the time up to the answer."""
    first = r["send"] + r["ttft_ms"] / 1e3
    n = r["n_out"]
    if n < 2:
        return [first] * n
    gap = (r["resp"] - first) / (n - 1)
    return [first + j * gap for j in range(n)]


def in_window(records: Iterable[dict], t0: float, t1: float) -> List[dict]:
    """Requests due inside the measured window."""
    return [r for r in records if t0 <= r["due"] < t1]


def end_to_end(records: List[dict], t0: float, t1: float) -> Dict:
    """The end-to-end numbers of one window: latencies over every request
    due in it that succeeded, the rate over every answer completed in it,
    and the counts."""
    due = in_window(records, t0, t1)
    ok = [r for r in due if r["ok"]]
    ttft = [ttft_ms(r) for r in ok]
    tpot = [v for v in (tpot_ms(r) for r in ok) if v is not None]
    done_tokens = sum(1 for r in records if r["ok"]
                      for t in token_times(r) if t0 <= t < t1)
    return {
        "attempted": len(due),
        "failed": len(due) - len(ok),
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p95_ms": percentile(tpot, 95),
        "output_tok_s": done_tokens / (t1 - t0),
        "n_ttft": len(ttft),
        "n_tpot": len(tpot),
    }


def lateness_ms(records: Iterable[dict]) -> List[float]:
    """How late the generator sent each request, in ms."""
    return [(r["send"] - r["due"]) * 1e3 for r in records
            if r.get("send") is not None]
