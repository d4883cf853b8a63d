"""Open loop, one turn per request, no sessions: ``arrival`` as the
conversations generator reads it, ``prompt`` and ``max_new``
distributions, ``warmup_s`` and ``tail_s``."""
import numpy as np

from bench.traffic_gen import Conversation, Plan, rng as seeded


def make_plan(traffic, *, draws, seed, seconds, max_len, n_slots,
              replicas):
    plan = Plan(loop="open", sessions=False,
                warmup_s=float(traffic["warmup_s"]), seconds=float(seconds),
                tail_s=float(traffic["tail_s"]))
    rate = float(traffic["arrival"]["rate_per_s"])
    horizon = plan.warmup_s + seconds
    n = 16 * (int(rate * horizon) // 4 + 1)      # bursts leave long gaps
    rng = seeded(seed, 0)
    gaps = draws.stratified(dict(traffic["arrival"]["gaps"],
                                 mean=1.0 / rate), n, rng, integer=False)
    prompts = draws.stratified(traffic["prompt"], n, rng)
    max_new = draws.stratified(traffic["max_new"], n, rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plan.conversations = [
        Conversation(cid=i, arrival=float(arrivals[i]),
                     first_prompt=int(prompts[i]), follow_ups=[],
                     max_new=[int(max_new[i])], think=[])
        for i in range(n) if arrivals[i] < horizon]
    return plan
