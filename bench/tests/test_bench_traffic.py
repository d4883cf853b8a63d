"""The traffic generator: deterministic from the seed, and the same set
of sizes for every seed in another order."""
import json
import os
import sys
from pathlib import Path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import traffic_gen  # noqa: E402

MIXES = Path(ROOT) / "bench" / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def plan(name, seed, **kw):
    args = dict(seconds=40.0, max_len=2048, n_slots=24, replicas=1)
    args.update(kw)
    return traffic_gen.make_plan(mix(name), seed=seed, **args)


def conv_sizes(p):
    return [(c.arrival, c.first_prompt, tuple(c.follow_ups),
             tuple(c.max_new), tuple(c.think)) for c in p.conversations]


def test_same_seed_same_plan_and_tokens():
    big = 2 ** 33 + 12345
    assert conv_sizes(plan("chat_sessions", big)) == \
        conv_sizes(plan("chat_sessions", big))
    assert traffic_gen.tokens(big, 3, 1, 50, 1000) == \
        traffic_gen.tokens(big, 3, 1, 50, 1000)
    assert traffic_gen.tokens(big, 3, 1, 50, 1000) != \
        traffic_gen.tokens(big + 1, 3, 1, 50, 1000)


def test_seeds_permute_one_set_of_sizes():
    a = plan("short_decode", 1)
    b = plan("short_decode", 2)
    assert a.clients == b.clients == 48
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    for k in range(0, 256, 16):        # every block of 16 requests
        assert sorted(a.prompt_lens[k:k + 16]) == \
            sorted(b.prompt_lens[k:k + 16])
        assert sorted(a.max_news[k:k + 16]) == sorted(b.max_news[k:k + 16])
    ca, cb = plan("chat_sessions", 1), plan("chat_sessions", 2)
    assert len(ca.conversations) == len(cb.conversations)
    for k in range(0, len(ca.conversations) - 16, 16):
        assert ca.conversations[k].arrival == \
            pytest.approx(cb.conversations[k].arrival)
        assert sorted(c.first_prompt for c in ca.conversations[k:k + 16]) \
            == sorted(c.first_prompt for c in cb.conversations[k:k + 16])


@pytest.mark.parametrize("name,key,median,lo,hi", [
    ("short_decode", "prompt_lens", 64, 8, 256),
    ("short_decode", "max_news", 128, 32, 384),
])
def test_closed_loop_distributions(name, key, median, lo, hi):
    p = plan(name, 7, max_len=4096, n_slots=12)
    vals = getattr(p, key)
    assert vals.min() >= lo and vals.max() <= hi
    assert abs(np.median(vals) - median) <= 1


def test_chat_conversations_fit_the_cache_and_keep_the_shape():
    p = plan("chat_sessions", 5)
    rate = mix("chat_sessions")["arrival"]["rate_per_s"]
    horizon = p.warmup_s + p.seconds + p.tail_s
    assert abs(len(p.conversations) - rate * horizon) <= 0.1 * rate * horizon
    firsts = [c.first_prompt for c in p.conversations]
    assert min(firsts) >= 32 and max(firsts) <= 1280
    for c in p.conversations:
        hist = c.first_prompt + c.max_new[0]
        for fu, mn in zip(c.follow_ups, c.max_new[1:]):
            hist += fu + mn
        assert hist <= 2048
        assert 1 <= c.turns <= 5
        assert len(c.think) == c.turns - 1
    gaps = np.diff([c.arrival for c in p.conversations])
    assert abs(gaps[:32].mean() - 1 / rate) < 0.05 / rate


def test_quantile_draws_are_exact():
    draws = traffic_gen.Draws()
    d = {"dist": "lognormal", "median": 100, "sigma": 1.0, "lo": 1,
         "hi": 10 ** 6}
    assert draws.ppf(d, 0.5) == pytest.approx(100)
    e = {"dist": "exponential", "mean": 2.0}
    vals = draws.stratified(e, 2000, np.random.default_rng(0),
                            integer=False, block=2000)
    assert vals.mean() == pytest.approx(2.0, rel=0.01)
    u = {"dist": "uniform_int", "lo": 2, "hi": 5}
    vals = draws.stratified(u, 400, np.random.default_rng(0))
    assert sorted(set(vals)) == [2, 3, 4, 5]
    assert all((vals == v).sum() == 100 for v in (2, 3, 4, 5))


FIXTURE = Path(ROOT) / "bench" / "tests" / "fixture"


def test_a_mix_names_its_generator_and_distributions_found_by_name():
    """A later mix with its own generator, bursty (gamma) arrivals,
    a mixture of long and short prompts and a distribution of its own
    is new files alone: here the fixture's, found by name."""
    from bench.spec import Finder
    find = Finder(FIXTURE)
    traffic = find.json("traffic", "tiny_mixed")
    args = dict(seconds=20.0, max_len=256, n_slots=4, replicas=1)
    a = traffic_gen.make_plan(traffic, find=find, seed=2 ** 33 + 1, **args)
    b = traffic_gen.make_plan(traffic, find=find, seed=2 ** 33 + 1, **args)
    assert conv_sizes(a) == conv_sizes(b)
    assert all(c.turns == 1 and c.max_new == [5] for c in a.conversations)
    prompts = [c.first_prompt for c in a.conversations]
    for k in range(0, len(prompts) - 16, 16):    # 4 long in every 16
        assert sum(p >= 150 for p in prompts[k:k + 16]) == 4
    gaps = np.diff([c.arrival for c in a.conversations])
    assert gaps.mean() == pytest.approx(1 / 8.0, rel=0.3)
    assert gaps.std() / gaps.mean() > 1.5        # bursts, unlike Poisson


def test_an_unknown_generator_or_distribution_is_an_error():
    with pytest.raises(FileNotFoundError):
        traffic_gen.make_plan(dict(mix("short_decode"), generator="nope"),
                              seed=1, seconds=1.0, max_len=2048,
                              n_slots=4, replicas=1)
    with pytest.raises(FileNotFoundError):
        traffic_gen.Draws().ppf({"dist": "nope"}, 0.5)
