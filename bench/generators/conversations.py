"""Conversations, open or closed loop.

A mix for this generator holds:

  ``loop``          ``"open"`` (conversations arrive on a schedule) or
                    ``"closed"`` (a fixed number of clients, each sending
                    its next request when the last one is answered)
  ``arrival``       open loop: ``{"rate_per_s": r, "gaps": {"dist": ...}}``,
                    conversations per second over the whole cell; the gap
                    distribution gets the mean 1 / r (default exponential:
                    Poisson arrivals)
  ``clients_per_slot`` closed loop: clients per engine slot, so the count
                    follows the configuration's deployment and replicas
  ``turns``         distribution of turns per conversation
  ``first_prompt``  distribution of the first prompt's length
  ``follow_up``     distribution of the new user tokens of a later turn
  ``max_new``       distribution of the tokens asked for (no EOS)
  ``think_s``       distribution of the pause before a follow-up turn
  ``sessions``      whether turns carry a ``session_id``
  ``warmup_s``      seconds of this traffic before the window opens
  ``tail_s``        seconds the schedule runs on after the window, so that
                    requests due late in the window are served under load

A conversation ends before its history and answer would pass the cache
length.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from bench.traffic_gen import Conversation, Plan, rng as seeded


def make_plan(traffic: Dict, *, draws, seed: int, seconds: float,
              max_len: int, n_slots: int, replicas: int) -> Plan:
    loop = traffic["loop"]
    plan = Plan(loop=loop, sessions=bool(traffic.get("sessions", False)),
                warmup_s=float(traffic["warmup_s"]), seconds=float(seconds),
                tail_s=float(traffic["tail_s"]))
    horizon = plan.warmup_s + plan.seconds + plan.tail_s
    rng = seeded(seed, 0)
    if loop == "closed":
        plan.clients = int(traffic["clients_per_slot"] * n_slots * replicas)
        n = int(traffic.get("pool", 4096))
        plan.prompt_lens = draws.stratified(traffic["first_prompt"], n, rng)
        plan.max_news = draws.stratified(traffic["max_new"], n, rng)
        bad = plan.prompt_lens + plan.max_news > max_len
        if bad.any():
            raise ValueError(f"mix asks for prompt + max_new over the "
                             f"cache length {max_len}")
        return plan
    if loop != "open":
        raise ValueError(f"unknown loop {loop!r}")
    arr = traffic["arrival"]
    rate = float(arr["rate_per_s"])
    n = int(math.ceil(rate * horizon)) + 1
    gap = dict(arr.get("gaps", {"dist": "exponential"}), mean=1.0 / rate)
    gaps = draws.stratified(gap, n, rng, integer=False)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    n_conv = int(np.searchsorted(arrivals, horizon))
    max_turns = int(traffic["turns"]["hi"])
    turns = draws.stratified(traffic["turns"], n, rng)
    first = draws.stratified(traffic["first_prompt"], n, rng)

    def per_turn(key, integer=True):
        """(conversation, turn) draws, blocked along conversations."""
        return np.stack([draws.stratified(traffic[key], n, rng, integer)
                         for _ in range(max_turns)], axis=1)

    follow, max_new = per_turn("follow_up"), per_turn("max_new")
    think = per_turn("think_s", integer=False)
    for c in range(n_conv):
        fu, mn, th = [], [int(max_new[c, 0])], []
        hist = int(first[c]) + mn[0]
        if hist > max_len:
            raise ValueError("a first turn does not fit the cache length")
        for t in range(1, int(turns[c])):
            if hist + follow[c, t] + max_new[c, t] > max_len:
                break                  # the conversation ends here
            fu.append(int(follow[c, t]))
            mn.append(int(max_new[c, t]))
            th.append(float(think[c, t]))
            hist += fu[-1] + mn[-1]
        plan.conversations.append(Conversation(
            cid=c, arrival=float(arrivals[c]), first_prompt=int(first[c]),
            follow_ups=fu, max_new=mn, think=th))
    return plan
