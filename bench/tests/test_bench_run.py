"""The harness end to end on the CPU at a tiny size.

A fixture configuration and mix under ``bench/tests/fixture`` are found
by name, as a later cell's files would be.  The run skips only the look
for a chip; with the timed path broken underneath, ``correct`` has to
come out false.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import spec  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixture"
SEED = 2 ** 33 + 17


# readers kept for the chat cells, which BENCHMARK.json holds back for now
CHAT_READERS = [{"name": "gen_lateness_p95_ms", "unit": "ms"},
                {"name": "prefix_hit_share", "unit": "%"},
                {"name": "affinity_hit_share", "unit": "%"}]


def fixture_cell(config="tiny-decoder", traffic="tiny_chat"):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.chat", "config": config,
                        "traffic": traffic, "chips": 1,
                        "why": "fixture"}]
    known = {m["name"] for m in bm["per_layer"]}
    bm["per_layer"] += [m for m in CHAT_READERS if m["name"] not in known]
    for m in bm["per_layer"] + bm["end_to_end"]:
        m.pop("workloads", None)
    return spec.load_cell("tiny.chat", benchmark=bm, data_dir=FIXTURE)


def run(cell, traced=False, seconds=2.0):
    from bench import run as brun
    return brun.run_cell(cell, seed=SEED, seconds=seconds, traced=traced,
                         require_tpu=False)


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen05b.short_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("config,traffic,loop", [
    ("tiny-decoder", "tiny_chat", "open"),
    ("tiny-decoder", "tiny_docs", "closed"),
    ("tiny-decoder", "tiny_mixed", None)])
def test_fixture_cell_is_found_by_name_and_served_correctly(config, traffic,
                                                            loop):
    cell = fixture_cell(config, traffic)
    assert cell.config["name"] == config
    assert cell.traffic.get("loop") == loop
    out = run(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "compared"


def test_traced_run_reports_host_side_layer_metrics():
    out = run(fixture_cell(), traced=True)
    assert out["correct"], out["compared"]
    for name in ("gen_lateness_p95_ms", "queue_wait_p95_ms",
                 "prefix_hit_share", "engine_step_ms"):
        assert name in out["metrics"], out["metrics"]
    assert out["metrics"]["prefix_hit_share"]["value"] > 0


def test_layer_readers_on_a_synthetic_run():
    """The per-layer readers that need the chip's peaks and trace, on a
    run built by hand: one request decoding 3 tokens and one prefilled
    in a single chunk, and a trace of two decode steps and one chunk."""
    from types import SimpleNamespace
    from bench import cost, peaks, spec
    shape = cost.Shape(d=1024, heads=16, kv_heads=16, head_dim=64,
                       ff=2816, layers=24, vocab=151936, qkv_bias=True)
    pk = peaks.peaks_for("TPU v5 lite")
    rec = {"prompt": [1] * 100, "send": 1.0, "ttft_ms": 100.0, "resp": 1.3,
           "n_out": 3, "ok": True, "resume_at": 0}
    chip = {"busy_s": 0.5, "modules": {
        "jit_decode_step": {"calls": 2, "seconds": 0.02},
        "jit_prefill_chunk": {"calls": 1, "seconds": 0.01}}}
    run = SimpleNamespace(
        records=[rec], window=(0.0, 2.0), traced=(0.0, 2.0),
        snap={"stats0": [{"prefix_hits": 0}], "stats1": [{"prefix_hits": 0}]},
        reduced={"chips": {"/device:TPU:0": chip}}, shape=shape, peaks=pk,
        chunk=256, chips=1)
    f, b = cost.decode_cost(shape, [101, 102])
    w = cost.decode_cost(shape, [])[1]
    need = cost.roofline_seconds(f, b + w, pk)        # two steps' weights
    read = lambda name: spec.metric_reader(name)(run)  # noqa: E731
    assert read("decode_step_roofline") == pytest.approx(100 * need / 0.02)
    cf, cb = cost.chunk_cost(shape, 0, 100, True)
    assert read("prefill_chunk_roofline") == pytest.approx(
        100 * cost.roofline_seconds(cf, cb, pk) / 0.01)
    assert read("step_mfu") == pytest.approx(
        100 * (f + cf) / (2.0 * pk["bf16_flops"]))
    assert read("step_mfu.ttft") == read("step_mfu")
    run.reduced = None                  # no trace: the rooflines fall silent
    assert read("decode_step_roofline") is None
    assert read("prefill_chunk_roofline") is None


def _patch_engine(monkeypatch, wrap):
    """Wrap every new ServeEngine's jitted decode step or sampler."""
    from repro.serve import engine as eng
    init = eng.ServeEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        wrap(self)
    monkeypatch.setattr(eng.ServeEngine, "__init__", patched)


def _state_unchanged(serve):
    decode = serve._decode_jit

    def step(p, c, t, pos):
        logits, _ = decode(p, c, t, pos)
        return logits, c                   # the cache is never written
    serve._decode_jit = step


def _half_batch(serve):
    decode = serve._decode_jit

    def step(p, c, t, pos):
        logits, nc = decode(p, c, t, pos)
        half = logits.shape[0] // 2        # rows past half: row 0's
        return logits.at[half:].set(logits[0]), nc
    serve._decode_jit = step


def _token_altered(serve):
    sample = serve._sample
    vocab = serve.model.cfg.vocab
    calls = [0]

    def altered(logits, req):
        calls[0] += 1
        tok = sample(logits, req)
        return (tok + 1) % vocab if calls[0] % 3 == 0 else tok
    serve._sample = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    _patch_engine(monkeypatch, fault)
    out = run(fixture_cell())
    assert not out["correct"], out["compared"]


def test_float8_control_fails_where_the_program_passes():
    """The control at the fixture's size: the reference in float8 puts
    first tokens that lie further below the float32 best than the
    program's bfloat16 tokens do, and past the limit."""
    import jax
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cell = fixture_cell()
    ref = cell.reference
    model = Model(cell.family.program_config(cell.config))
    w = cell.family.make_weights(model, SEED, cell.config,
                                 jax.devices()[0])
    eng = ServeEngine(model, w, max_len=256, n_slots=4, chunk_tokens=32,
                      impl="xla")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, n).tolist() for n in (40, 90, 120)]
    outs = eng.generate(prompts, max_new=24)
    prog = max(float(np.max(ref.gaps(cell.config, w, p, o)))
               for p, o in zip(prompts, outs))
    ctrl = max(float(np.max(ref.gaps(cell.config, w, p, o, control=True)))
               for p, o in zip(prompts, outs))
    limit = cell.config["limits"]["max_logit_gap"]
    assert prog <= limit < ctrl, (prog, limit, ctrl)


def test_moe_reference_agrees_with_the_program_in_float32():
    """The reference's mixture of experts (softmax over the top-k router
    logits, SwiGLU experts) is the program's: run in float32 with a
    float32 cache, the program's greedy tokens are the reference's best
    at every position.  (In bfloat16 a tiny MoE flips near-tied routing
    often enough that its gaps overlap the control's, so the limit is
    set at the full width on the chip.)"""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cell = fixture_cell("tiny-moe", "tiny_docs")
    ref = cell.reference
    cfg = cell.family.program_config(cell.config).replace(
        compute_dtype="float32")
    model = Model(cfg)
    w = cell.family.make_weights(model, SEED, cell.config,
                                 jax.devices()[0])
    eng = ServeEngine(model, w, max_len=256, n_slots=4, chunk_tokens=32,
                      impl="xla", cache_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 512, n).tolist() for n in (40, 90, 150)]
    outs = eng.generate(prompts, max_new=16)
    for p, o in zip(prompts, outs):
        assert float(np.max(ref.gaps(cell.config, w, p, o))) < 1e-4
