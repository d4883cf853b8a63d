"""Session-affine serving data path: chunked prefill correctness (incl.
EOS mid-chunk), KV-session pinning/resume/eviction edge cases, the
gateway's occupancy-aware load signal, and the client-side soft-affinity
layer (prefer_instance + SessionAffinity)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.fabric import SessionAffinity
from repro.fabric.balancer import prefer_instance
from repro.models import Model, unzip
from repro.serve import engine as engine_mod
from repro.serve.engine import PHASES, ServeEngine
from repro.services import ServingGateway

CFG = configs.reduced("qwen1.5-0.5b").replace(compute_dtype="float32")


@pytest.fixture(scope="module")
def model_and_params():
    m = Model(CFG)
    params, _ = unzip(m.init(jax.random.PRNGKey(0)))
    return m, params


def make_engine(m, params, **kw):
    # fp32 cache: chunked-vs-monolithic parity must not hinge on bf16
    # rounding of the cached K/V
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_len", 64)
    return ServeEngine(m, params, **kw)


# ---------------------------------------------------------------- chunked
def test_chunked_prefill_matches_monolithic(model_and_params):
    """A prompt prefilled in fixed-size chunks (last chunk padded) must
    decode exactly the tokens of one monolithic prefill pass."""
    m, params = model_and_params
    prompt = np.arange(1, 20)              # 19 tokens: 3 chunks, pad 5
    mono = make_engine(m, params, n_slots=2)
    want = mono.generate([prompt], max_new=8)[0]

    chunked = make_engine(m, params, n_slots=2, chunk_tokens=8)
    got = chunked.generate([prompt], max_new=8)[0]
    assert got == want


def test_chunked_interleaves_with_decode(model_and_params):
    """Chunked prefill of one slot must not disturb decode of another:
    outputs equal the isolated single-slot run."""
    m, params = model_and_params
    p_a, p_b = np.arange(1, 7), np.arange(3, 25)
    alone = make_engine(m, params, n_slots=1, chunk_tokens=8)
    want_a = alone.generate([p_a], max_new=6)[0]
    want_b = alone.generate([p_b], max_new=6)[0]

    eng = make_engine(m, params, n_slots=2, chunk_tokens=8)
    ra = eng.submit(p_a, max_new=6)
    rb = eng.submit(p_b, max_new=6)
    eng.drain()
    assert ra.out_tokens == want_a
    assert rb.out_tokens == want_b


def test_eos_on_chunked_prefill_first_token(model_and_params):
    """EOS sampled from the *prefill* chunk itself (first emitted token)
    must finish the request immediately and free the slot."""
    m, params = model_and_params
    prompt = np.arange(1, 20)
    probe = make_engine(m, params, n_slots=1, chunk_tokens=8)
    toks = probe.generate([prompt], max_new=4)[0]

    eng = make_engine(m, params, n_slots=1, chunk_tokens=8)
    req = eng.submit(prompt, max_new=4, eos_id=toks[0])
    eng.drain()
    assert req.out_tokens == toks[:1]
    assert req.done_event.is_set()
    assert eng.stats()["active_slots"] == 0
    # the freed slot is immediately reusable for a full generation
    assert eng.generate([prompt], max_new=4)[0] == toks


def test_eos_mid_decode_after_chunked_prefill(model_and_params):
    m, params = model_and_params
    prompt = np.arange(1, 20)
    probe = make_engine(m, params, n_slots=1, chunk_tokens=8)
    toks = probe.generate([prompt], max_new=6)[0]
    # the emitted token whose FIRST occurrence is latest: maximizes the
    # chance the EOS cut lands mid-decode, whatever the tiny random
    # model happens to emit
    eos = max(set(toks), key=toks.index)
    k = toks.index(eos)

    eng = make_engine(m, params, n_slots=1, chunk_tokens=8)
    req = eng.submit(prompt, max_new=6, eos_id=eos)
    eng.drain()
    assert req.out_tokens == toks[:k + 1]


# ---------------------------------------------------------------- sessions
def test_session_resume_matches_fresh_prefill(model_and_params):
    """A follow-up turn resumed from pinned KV (suffix-only prefill)
    must produce exactly the tokens of a from-scratch prefill."""
    m, params = model_and_params
    prompt = np.arange(1, 21)
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=4)
    turn1 = eng.generate([prompt], max_new=4, session_ids=["conv"])[0]
    follow = np.concatenate([prompt, np.asarray(turn1, np.int32),
                             np.asarray([7, 9], np.int32)])

    fresh = make_engine(m, params, n_slots=2, chunk_tokens=8)
    want = fresh.generate([follow], max_new=4)[0]

    got = eng.generate([follow], max_new=4, session_ids=["conv"])[0]
    assert got == want
    st = eng.stats()
    assert st["prefix_hits"] == 1
    # everything up to the last emitted token of turn 1 was reused
    assert st["prefix_tokens_saved"] == len(prompt) + len(turn1) - 1


def test_stale_prefix_misses_and_recovers(model_and_params):
    """A follow-up whose prompt does NOT extend the cached history must
    evict the stale session and full-prefill — correctness never depends
    on the cache."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=4)
    eng.generate([np.arange(1, 21)], max_new=4, session_ids=["conv"])

    other = np.arange(5, 30)               # unrelated prompt, same sid
    fresh = make_engine(m, params, n_slots=2, chunk_tokens=8)
    want = fresh.generate([other], max_new=4)[0]
    got = eng.generate([other], max_new=4, session_ids=["conv"])[0]
    assert got == want
    st = eng.stats()
    assert st["prefix_hits"] == 0
    assert st["prefix_misses"] == 2        # both turns missed
    assert st["session_evictions"] == 1    # the stale pin was dropped


def test_eviction_racing_follow_up(model_and_params):
    """A follow-up arriving after its session was LRU-evicted (slot
    pressure from fresh conversations) degrades to a miss + full
    prefill with identical output."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=2)
    prompt = np.arange(1, 15)
    t1 = eng.generate([prompt], max_new=3, session_ids=["victim"])[0]
    # flood: enough fresh sessions to evict "victim" from both the
    # 2-entry table and its slot
    for i in range(3):
        eng.generate([np.arange(2 + i, 20 + i)], max_new=3,
                     session_ids=[f"flood{i}"])
    assert "victim" not in eng.sessions
    follow = np.concatenate([prompt, np.asarray(t1, np.int32),
                             np.asarray([4], np.int32)])
    fresh = make_engine(m, params, n_slots=2, chunk_tokens=8)
    want = fresh.generate([follow], max_new=3)[0]
    hits_before = eng.stats()["prefix_hits"]
    got = eng.generate([follow], max_new=3, session_ids=["victim"])[0]
    assert got == want
    assert eng.stats()["prefix_hits"] == hits_before   # no phantom hit


def test_all_slots_pinned_no_starvation(model_and_params):
    """Every slot pinned by an idle session must not starve fresh
    requests: the LRU pin is evicted and the request runs."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=4)
    eng.generate([np.arange(1, 10), np.arange(2, 12)], max_new=3,
                 session_ids=["a", "b"])
    st = eng.stats()
    assert st["pinned_sessions"] == 2 and st["active_slots"] == 0

    fresh_prompt = np.arange(4, 18)
    req = eng.submit(fresh_prompt, max_new=3)
    eng.drain()
    assert len(req.out_tokens) == 3
    # LRU ("a", the older pin) was sacrificed; "b" survived
    assert "a" not in eng.sessions and "b" in eng.sessions


def test_drain_with_pinned_sessions_terminates(model_and_params):
    """Pinned sessions hold no slot_req: drain() must return with
    sessions still resident (a pinned engine is an idle engine)."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=4)
    eng.generate([np.arange(1, 10)], max_new=3, session_ids=["keep"])
    eng.drain()                            # must not spin forever
    st = eng.stats()
    assert st["pinned_sessions"] == 1
    assert st["active_slots"] == 0 and st["occupancy"] == 0.0
    # and the pin is still usable afterwards
    assert "keep" in eng.sessions


def test_sessions_disabled_on_unchunkable_model(model_and_params,
                                                monkeypatch):
    """chunk_tokens/session_cap are silently ignored when the model
    cannot continue prefill at an offset — the engine falls back to
    monolithic prefill and stateless serving."""
    m, params = model_and_params
    monkeypatch.setattr(type(m), "supports_chunked_prefill",
                        property(lambda self: False))
    eng = ServeEngine(m, params, max_len=64, n_slots=2,
                      chunk_tokens=8, session_cap=4,
                      cache_dtype=jnp.float32)
    assert eng.chunk == 0 and eng.session_cap == 0
    out = eng.generate([np.arange(1, 8)], max_new=3, session_ids=["x"])[0]
    assert len(out) == 3
    st = eng.stats()
    assert st["pinned_sessions"] == 0
    assert st["prefix_hits"] == 0 and st["prefix_misses"] == 0


# ------------------------------------------------------ counters, phases
def test_work_counters_and_phases(model_and_params):
    """The engine's work counters count what it served: one first token
    per request comes from its prefill, every other from a decode step;
    the chunk path carries every real prompt token (of a resumed
    session, only the suffix) in ceil(n / C) chunks; every phase ran."""
    m, params = model_and_params
    C = 8
    eng = make_engine(m, params, n_slots=3, chunk_tokens=C, session_cap=4)
    prompts = [np.arange(1, 20), np.arange(2, 7), np.arange(3, 19)]
    outs = eng.generate(prompts, max_new=4, session_ids=["a", None, None])
    st = eng.stats()
    assert st["decode_tokens"] + len(prompts) == sum(map(len, outs))
    assert st["decode_steps"] >= max(map(len, outs)) - 1
    assert st["prefill_tokens"] == sum(map(len, prompts))
    assert st["prefill_chunks"] == sum(-(-len(p) // C) for p in prompts)

    follow = np.concatenate([prompts[0], np.asarray(outs[0], np.int32),
                             np.asarray([7, 9], np.int32)])
    out = eng.generate([follow], max_new=3, session_ids=["a"])[0]
    st2 = eng.stats()
    assert st2["prefix_hits"] == 1
    suffix = len(follow) - st2["prefix_tokens_saved"]
    assert st2["prefill_tokens"] - st["prefill_tokens"] == suffix
    assert st2["prefill_chunks"] - st["prefill_chunks"] == -(-suffix // C)
    assert st2["decode_tokens"] - st["decode_tokens"] == len(out) - 1
    assert set(st2["phase_calls"]) == set(PHASES)
    for name in PHASES:
        assert st2["phase_calls"][name] > 0, name
        assert st2["phase_ns"][name] > 0, name
    assert st2["phase_calls"]["serve.decode"] == st2["decode_steps"]
    assert st2["phase_calls"]["serve.prefill_chunk"] == \
        st2["prefill_chunks"]


@pytest.mark.parametrize("chunk", [8, 0])
def test_moe_work_counters(chunk):
    """A MoE engine counts, from shapes alone, the routed assignments of
    its real tokens (tokens × k × MoE layers) and the expert rows its
    dropless dispatch computes (E × C × layers, C the tokens of the call:
    a chunk's size, the slots of a decode step, a whole prompt without
    chunks), in stats() and in the registry's counters alike."""
    from repro.telemetry import metrics
    cfg = configs.reduced("granite-moe-3b-a800m").replace(
        compute_dtype="float32")
    m = Model(cfg)
    params, _ = unzip(m.init(jax.random.PRNGKey(0)))
    reg = {name: metrics.counter(f"serve.engine.{name}")
           for name in ("moe_assignments", "moe_rows")}
    before = {name: c.value for name, c in reg.items()}
    n_slots = 3
    eng = make_engine(m, params, n_slots=n_slots, chunk_tokens=chunk)
    prompts = [np.arange(1, 20), np.arange(2, 7), np.arange(3, 19)]
    eng.generate(prompts, max_new=4)
    st = eng.stats()
    E, k, L = cfg.moe.num_experts, cfg.moe.top_k, cfg.n_layers
    prompt_tokens = sum(map(len, prompts))
    assert st["moe_assignments"] == \
        (prompt_tokens + st["decode_tokens"]) * k * L
    prefill_rows = st["prefill_chunks"] * chunk if chunk else prompt_tokens
    assert st["moe_rows"] == \
        (prefill_rows + st["decode_steps"] * n_slots) * E * L
    for name, c in reg.items():
        assert c.value - before[name] == st[name], name


def test_dense_engine_counts_no_moe_work(model_and_params):
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8)
    eng.generate([np.arange(1, 12)], max_new=3)
    st = eng.stats()
    assert st["moe_assignments"] == st["moe_rows"] == 0


# -------------------------------------------------------------- sampling
def _per_row_argmax(logits, reqs):
    """Sampling as a per-slot loop did it: the first argmax of each
    row, one row at a time (greedy requests only)."""
    logits, first = logits if isinstance(logits, tuple) else (logits, 0)
    rows = logits.reshape(-1, logits.shape[-1])
    return np.asarray([int(jnp.argmax(rows[first + i]))
                       for i in range(len(reqs))], np.int32)


def _turns(eng):
    """Two turns over three slots, chunked: three prompts (one of them a
    session), then a follow-up that resumes the session beside a fresh
    prompt."""
    prompts = [np.arange(1, 20), np.arange(2, 7), np.arange(3, 19)]
    outs = eng.generate(prompts, max_new=6, session_ids=["a", None, None])
    follow = np.concatenate([prompts[0], np.asarray(outs[0], np.int32),
                             np.asarray([7, 9], np.int32)])
    outs += eng.generate([follow, np.arange(5, 30)], max_new=5,
                         session_ids=["a", None])
    return outs


def test_greedy_tokens_are_each_rows_argmax(model_and_params):
    """Every token a decode step emits is the first argmax of its own
    slot's row of that step's logits, over a multi-slot, multi-step,
    chunked run with a session resume; and every token, first tokens
    too, is what per-slot argmax sampling gives."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=3, chunk_tokens=8, session_cap=4)
    decode = eng._decode_jit
    steps = []

    def step(p, c, t, pos):
        logits, nc = decode(p, c, t, pos)
        live = [(i, r, len(r.out_tokens))
                for i, r in enumerate(eng.slot_req)
                if r is not None and i not in eng._prefill
                and not r.done_event.is_set()]
        steps.append((np.asarray(logits), live))
        return logits, nc
    eng._decode_jit = step
    outs = _turns(eng)
    assert eng.stats()["prefix_hits"] == 1
    checked = 0
    for logits, live in steps:
        for i, req, n in live:
            assert req.out_tokens[n] == int(np.argmax(logits[i]))
            checked += 1
    assert checked == eng.stats()["decode_tokens"] > 10

    per_row = make_engine(m, params, n_slots=3, chunk_tokens=8,
                          session_cap=4)
    per_row._sample = _per_row_argmax
    assert _turns(per_row) == outs


def test_one_token_read_per_decode_step_and_prompt(model_and_params):
    """One device->host copy of tokens per sampler call: per decode step,
    and per completed prompt (chunked or monolithic); ``_sample`` is the
    one method every emitted token passes through."""
    from repro.telemetry import metrics
    m, params = model_and_params
    reg = metrics.counter("serve.engine.token_reads")
    for chunk in (8, 0):
        before = reg.value
        eng = make_engine(m, params, n_slots=3, chunk_tokens=chunk)
        sample, calls = eng._sample, []

        def counted(logits, reqs):
            toks = sample(logits, reqs)
            calls.append(len(reqs))
            return toks
        eng._sample = counted
        prompts = [np.arange(1, 20), np.arange(2, 7), np.arange(3, 19),
                   np.arange(4, 9)]
        outs = eng.generate(prompts, max_new=5)
        st = eng.stats()
        assert st["token_reads"] == st["decode_steps"] + len(prompts)
        assert st["token_reads"] == len(calls) == reg.value - before
        assert calls.count(3) == st["decode_steps"]
        assert st["decode_tokens"] + len(prompts) == sum(map(len, outs))


def test_sampler_rows_and_temperatures():
    """The sampler alone: a greedy row takes the first of tied maxima,
    the key advances on every call, and a row at temperature T draws
    from softmax(logits / T)."""
    sample = engine_mod.sample_executable()
    key = jax.random.PRNGKey(3)
    n = 4000
    logits = np.zeros((n, 4), np.float32)
    logits[:, 1] = logits[:, 3] = np.log(3.0)       # a tie at 1 and 3
    logits[:, 0] = np.log(1.0)
    logits[:, 2] = -np.inf
    temps = np.zeros((n,), np.float32)
    temps[n // 2:] = 2.0
    toks, key2 = sample(logits, 0, temps, key)
    toks = np.asarray(toks)
    assert toks.dtype == np.int32 and toks.shape == (n,)
    assert (toks[:n // 2] == 1).all()
    hot = toks[n // 2:]
    assert not (hot == 2).any()
    # softmax(logits / 2) = (1, √3, 0, √3) / (1 + 2√3)
    want = 1 / (1 + 2 * np.sqrt(3.0))
    assert abs(np.mean(hot == 0) - want) < 0.04
    assert not np.array_equal(np.asarray(key2), np.asarray(key))
    again, _ = sample(logits, 0, temps, key)
    assert np.array_equal(np.asarray(again), toks)
    # leading dims flatten into rows, and only the rows from ``first``
    # are sampled: one row of a chunk's (1, C, V) logits
    chunk = np.zeros((1, 8, 4), np.float32)
    chunk[0, np.arange(8), np.arange(8) % 4] = 1.0
    one, _ = sample(chunk, 5, np.zeros((1,), np.float32), key)
    assert np.asarray(one).tolist() == [1]
    two, _ = sample(chunk, 2, np.zeros((2,), np.float32), key)
    assert np.asarray(two).tolist() == [2, 3]


def test_temperature_slots_beside_greedy_ones(model_and_params):
    """Greedy slots serve the same tokens whatever their neighbours'
    temperatures; sampled tokens are in the vocabulary, the same for the
    same engine seed and different for another; a tiny temperature
    serves the greedy tokens."""
    m, params = model_and_params
    prompts = [np.arange(1, 20), np.arange(2, 7), np.arange(3, 19),
               np.arange(4, 9)]

    def serve(temps, seed=0):
        eng = make_engine(m, params, n_slots=4, chunk_tokens=8, seed=seed)
        reqs = [eng.submit(p, max_new=8, temperature=t)
                for p, t in zip(prompts, temps)]
        eng.drain()
        return [r.out_tokens for r in reqs]

    greedy = serve([0.0] * 4)
    mixed = serve([0.0, 2.0, 0.0, 2.0])
    assert mixed[0] == greedy[0] and mixed[2] == greedy[2]
    drawn = mixed[1] + mixed[3]
    assert all(0 <= t < CFG.vocab for t in drawn)
    assert mixed != greedy
    assert serve([0.0, 2.0, 0.0, 2.0]) == mixed
    other = serve([0.0, 2.0, 0.0, 2.0], seed=1)
    assert other[1] + other[3] != drawn
    assert other[0] == greedy[0] and other[2] == greedy[2]
    assert serve([1e-6] * 4, seed=5) == greedy


# -------------------------------------------------------------- donation
@pytest.fixture
def donating(monkeypatch):
    """Engines built under it donate the batched cache, as they do on a
    chip."""
    monkeypatch.setattr(engine_mod, "donates", lambda device=None: True)


def _deleted(tree):
    return [x.is_deleted() for x in jax.tree_util.tree_leaves(tree)]


def test_step_consumes_the_donated_cache(model_and_params, donating):
    """A step's slot scatter and decode update the cache in place: the
    cache leaves held before the step are deleted after it, and the
    tokens are those of an engine that keeps its inputs."""
    m, params = model_and_params
    prompt = np.arange(1, 7)
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8)
    req = eng.submit(prompt, max_new=6)
    before = eng.cache
    eng.step()                       # the prompt's one chunk, then decode
    assert all(_deleted(before))
    assert not any(_deleted(eng.cache))
    eng.drain()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "donates", lambda device=None: False)
        keeps = make_engine(m, params, n_slots=2, chunk_tokens=8)
    assert req.out_tokens == keeps.generate([prompt], max_new=6)[0]


def test_fail_all_recovers_a_donated_cache(model_and_params, donating):
    """A decode that raised after its dispatch consumed the cache leaves
    ``self.cache`` deleted; ``fail_all`` fails the request and zeroes the
    cache, and the next request is served as a fresh engine serves it."""
    m, params = model_and_params
    eng = make_engine(m, params, n_slots=2, chunk_tokens=8, session_cap=2)
    decode = eng._decode_jit

    def lost(p, c, t, pos):
        decode(p, c, t, pos)
        raise RuntimeError("device lost mid-step")
    eng._decode_jit = lost
    req = eng.submit(np.arange(1, 7), max_new=5, session_id="s")
    with pytest.raises(RuntimeError, match="device lost"):
        eng.step()
    assert any(_deleted(eng.cache))
    assert eng.fail_all("step failed") == 1
    assert req.error == "step failed" and not any(_deleted(eng.cache))

    eng._decode_jit = decode
    prompt = np.arange(3, 17)
    got = eng.generate([prompt], max_new=6)[0]
    want = make_engine(m, params, n_slots=2, chunk_tokens=8,
                       session_cap=2).generate([prompt], max_new=6)[0]
    assert got == want


# ---------------------------------------------------------------- gateway
class _StubServe:
    """Just enough ServeEngine surface for ServingGateway._load."""
    def __init__(self, active, queued, pinned):
        self._s = {"active_slots": active, "queued": queued,
                   "pinned_sessions": pinned}

    def stats(self):
        return dict(self._s)


def test_gateway_load_counts_occupancy():
    """Regression: a gateway with a full batch and an empty queue must
    not report near-idle — active slots dominate the balancing signal."""
    gw = ServingGateway.__new__(ServingGateway)   # formula-only unit test
    gw.serve = _StubServe(active=4, queued=0, pinned=0)
    busy = ServingGateway._load(gw)
    gw.serve = _StubServe(active=0, queued=0, pinned=0)
    idle = ServingGateway._load(gw)
    assert idle == 0.0
    assert busy >= 4.0, \
        "full batch with empty queue reported as near-idle"


def test_gateway_load_weights_pinned_sessions():
    """Pinned sessions hold no slot_req but admitting there costs an
    eviction: they must raise load, at less than a live slot's weight."""
    gw = ServingGateway.__new__(ServingGateway)
    gw.serve = _StubServe(active=0, queued=0, pinned=4)
    pinned = ServingGateway._load(gw)
    gw.serve = _StubServe(active=4, queued=0, pinned=0)
    active = ServingGateway._load(gw)
    assert 0.0 < pinned < active


# ---------------------------------------------------------------- affinity
class _Rep:
    def __init__(self, iid):
        self.iid = iid


def test_prefer_instance_ordering():
    ranked = [_Rep("a"), _Rep("b"), _Rep("c")]
    assert prefer_instance(ranked, None) is ranked
    out = prefer_instance(ranked, "b")
    assert [r.iid for r in out] == ["b", "a", "c"]
    # unknown iid: ranking untouched (dead/evicted replica fallback)
    assert [r.iid for r in prefer_instance(ranked, "zz")] == ["a", "b", "c"]
    assert prefer_instance([], "a") == []


class _FakePool:
    """Scripted pool: serves from ``homes`` (prefer honored only when
    still listed), recording what prefer= each call carried."""
    def __init__(self, default_iid):
        self.default = default_iid
        self.live = {default_iid}
        self.prefers = []

    def call_routed(self, rpc, arg=None, prefer=None, **kw):
        self.prefers.append(prefer)
        iid = prefer if prefer in self.live else self.default
        return {"ok": True}, iid


def test_session_affinity_hit_miss_move():
    pool = _FakePool("r1")
    aff = SessionAffinity(pool)
    _, iid = aff.call_routed("s1", "gen.generate", {})
    assert iid == "r1" and aff.misses == 1          # first turn: no map
    _, iid = aff.call_routed("s1", "gen.generate", {})
    assert iid == "r1" and aff.hits == 1
    assert pool.prefers == [None, "r1"]

    # preferred replica dies: the call lands elsewhere and the session
    # is re-homed (a move, not an error)
    pool.default = "r2"
    pool.live = {"r2"}
    _, iid = aff.call_routed("s1", "gen.generate", {})
    assert iid == "r2" and aff.moves == 1
    assert aff.lookup("s1") == "r2"

    aff.forget("s1")
    assert aff.lookup("s1") is None
    st = aff.stats()
    assert (st["hits"], st["misses"], st["moves"]) == (1, 1, 1)


def test_session_affinity_lru_capacity():
    pool = _FakePool("r1")
    aff = SessionAffinity(pool, capacity=2)
    for sid in ("a", "b", "c"):
        aff.call_routed(sid, "gen.generate", {})
    assert aff.lookup("a") is None                  # LRU-dropped
    assert aff.lookup("b") == "r1" and aff.lookup("c") == "r1"
