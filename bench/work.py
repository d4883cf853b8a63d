"""The work the served requests needed, from the client's records and
the gateways' ``gen.stats`` alone (nothing inside the engine is read).

Decode: an answer of n tokens was decoded in n - 1 steps, spread evenly
from its first token to the answer (``measure.token_times``); the step
that makes token j of a prompt of P tokens attends P + j positions.

Prefill: the prompt of a request is prefilled in chunks before its first
token.  A session follow-up resumes at ``resume_at`` where the engine
still holds its history, else it is prefilled whole; the share of
follow-ups that resumed is the gateways' ``prefix_hits`` in the window
over the follow-ups whose first token fell in it, and each follow-up
counts as both cases, weighted by that share.

Costs per call come from ``bench/cost.py``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from . import cost
from .measure import token_times


def decode_work(records: Iterable[Dict], shape: cost.Shape, t0: float,
                t1: float, cache_bytes: int = 2) -> Tuple[int, float, float]:
    """(tokens, flops, K/V bytes) of the decode steps whose tokens were
    produced in [t0, t1); the weights, read once per step, are not in
    the bytes."""
    n = 0
    flops = kv = 0.0
    per_token = shape.token_matmul_flops() + shape.unembed_flops()
    kv_pos = shape.kv_bytes_per_position(cache_bytes)
    for r in records:
        if not r["ok"]:
            continue
        P = len(r["prompt"])
        for j, t in enumerate(token_times(r)):
            if j and t0 <= t < t1:
                live = P + j
                n += 1
                flops += per_token + shape.attention_flops(live)
                kv += kv_pos * (live + 1)
    return n, flops, kv


def follow_up_hit_share(records: Iterable[Dict], stats0: List[Dict],
                        stats1: List[Dict], t0: float, t1: float) -> float:
    """Share of the session follow-ups first answered in [t0, t1) that
    resumed a pinned cache, from the ``prefix_hits`` delta."""
    follow = sum(1 for r in records if r["ok"] and r.get("resume_at")
                 and t0 <= token_times(r)[0] < t1)
    if not follow:
        return 0.0
    hits = sum(b["prefix_hits"] - a["prefix_hits"]
               for a, b in zip(stats0, stats1))
    return min(1.0, max(0.0, hits / follow))


def prefill_chunks(records: Iterable[Dict], shape: cost.Shape, chunk: int,
                   hit_share: float, t0: float, t1: float,
                   cache_bytes: int = 2) -> List[Tuple[float, float, float]]:
    """(weight, flops, bytes) of each prefill chunk of the requests whose
    first token fell in [t0, t1); a follow-up's chunks come twice,
    weighted by whether it resumed."""
    out = []
    for r in records:
        if not r["ok"] or not t0 <= token_times(r)[0] < t1:
            continue
        P = len(r["prompt"])
        at = r.get("resume_at") or 0
        cases = [(hit_share, at), (1.0 - hit_share, 0)] if at else [(1.0, 0)]
        for weight, lo in cases:
            if weight <= 0:
                continue
            for off in range(lo, P, chunk):
                n_real = min(chunk, P - off)
                f, b = cost.chunk_cost(shape, off, n_real, off + chunk >= P,
                                       cache_bytes)
                out.append((weight, f, b))
    return out
