"""What every traffic generator produces, and the seeded draws it makes.

A mix is a JSON file under ``bench/traffic/`` that names its
``generator``, a file ``bench/generators/<generator>.py`` with
``make_plan(traffic, *, draws, seed, seconds, max_len, n_slots,
replicas) -> Plan``.  Sizes, gaps and pauses are distributions named in
the mix, ``{"dist": <name>, ...}``, each a file
``bench/dists/<name>.py`` with ``ppf(spec, u, draws)``; ``lo`` and ``hi``
clip any of them.  So a new mix, distribution or generator is a new
file and an entry, and no file that is there changes.

Every seed gets the same set of sizes, gaps and pauses: each stream is
cut into blocks of 16 draws at evenly spaced quantiles of its
distribution, and the seed only permutes each block (and picks the token
ids).  So any 16 consecutive requests, conversations or gaps are the
same work whatever the seed, in another order, and the spread between
seeds is not a spread of work.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np


class Draws:
    """Quantiles of the distributions a mix names, found by name."""

    def __init__(self, find=None):
        if find is None:
            from .spec import Finder
            find = Finder()
        self.find = find

    def ppf(self, spec: Dict, u: float) -> float:
        """The ``u``-quantile of distribution ``spec`` (clipped to
        lo/hi)."""
        v = self.find.module("dists", spec["dist"]).ppf(spec, u, self)
        lo, hi = spec.get("lo", -math.inf), spec.get("hi", math.inf)
        return min(max(v, lo), hi)

    def stratified(self, spec: Dict, n: int, rng: np.random.Generator,
                   integer: bool = True, block: int = 16) -> np.ndarray:
        """``n`` draws in blocks of ``block``: each block holds the draws
        at the quantiles (i + 0.5) / block, in an order from ``rng``.
        Every run of ``block`` consecutive draws is then the same set of
        values whatever the seed."""
        q = np.array([self.ppf(spec, (i + 0.5) / block)
                      for i in range(block)])
        if integer:
            q = np.round(q).astype(np.int64)
        blocks = -(-n // block)
        return np.concatenate([q[rng.permutation(block)]
                               for _ in range(blocks)])[:n]


@dataclass
class Conversation:
    cid: int
    arrival: float                 # seconds after the schedule starts
    first_prompt: int
    follow_ups: List[int]          # new user tokens of turns 2..n
    max_new: List[int]             # per turn
    think: List[float]             # pause before turns 2..n

    @property
    def turns(self) -> int:
        return len(self.max_new)


@dataclass
class Plan:
    loop: str
    sessions: bool
    warmup_s: float
    seconds: float
    tail_s: float
    clients: int = 0
    conversations: List[Conversation] = field(default_factory=list)
    # closed loop: request k of the shared stream
    prompt_lens: np.ndarray = None
    max_news: np.ndarray = None

    @property
    def window(self):
        """(open, close) of the measured window, seconds after start."""
        return self.warmup_s, self.warmup_s + self.seconds


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *salt])


def make_plan(traffic: Dict, *, find=None, **kw) -> Plan:
    """The plan of mix ``traffic`` from its own generator."""
    draws = Draws(find)
    gen = draws.find.module("generators", traffic["generator"])
    return gen.make_plan(traffic, draws=draws, **kw)


def tokens(seed: int, key: int, turn: int, n: int, vocab: int) -> List[int]:
    """Token ids of one prompt or follow-up, fixed by (seed, key, turn)."""
    return rng(seed, 1, key, turn).integers(1, vocab, n).tolist()
