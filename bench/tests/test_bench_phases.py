"""The engine's step phases and work counters as the benchmark reads
them: idle gaps by phase (``bench/phase_gaps.py``) on planes built by
hand and on the committed v5e trace, the readers on runs built by hand,
and a traced fixture run through ``bench/tools/phase_trace.py``."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import pytest  # noqa: E402

from bench import phase_gaps as pg  # noqa: E402
from bench import spec  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixture"
SMALL = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"
SEED = 2 ** 33 + 29
NEW_READERS = ("engine_sample_ms", "prefill_useful_pct",
               "decode_batch_fill_pct")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


def hand_built_planes():
    """Five operations on one chip, so three gaps inside the step and
    one after it; three decode steps: one inside serve.decode, one that
    starts 10 ns before it, one away from it."""
    dev = plane("/device:TPU:0", {
        tr.OPS_LINE: [ev("fusion.1", 0, 100), ev("fusion.2", 200, 100),
                      ev("fusion.3", 500, 100), ev("fusion.4", 900, 100),
                      ev("fusion.5", 1200, 100)],
        tr.MODULES_LINE: [ev("jit_decode_step(3)", 140, 40),
                          ev("jit_decode_step(3)", 200, 100),
                          ev("jit_decode_step(3)", 500, 100)]})
    host = plane("/host:CPU", {"python3": [
        ev("serve.step", 50, 950), ev("serve.decode", 150, 170),
        ev("serve.sample", 320, 160), ev("serve.admit", 480, 40),
        ev("PjitFunction(squeeze)", 600, 300)]})
    return [host, dev]


def test_phase_gaps_on_hand_built_planes(monkeypatch):
    out = pg.reduce_planes(hand_built_planes())
    assert out["phase_gaps"] == {
        "serve.decode": pytest.approx(100e-9),   # 100-200: decode 50
        "serve.sample": pytest.approx(200e-9),   # 300-500: sample 160
        "serve.step": pytest.approx(300e-9),     # 600-900: only the step
        "outside_step": pytest.approx(200e-9)}   # 1000-1200
    assert out["decode_in_phase"] == {
        "inside": 1, "total": 3,
        "start_margin_us": [pytest.approx(-0.01), pytest.approx(0.05)],
        "end_margin_us": [pytest.approx(0.02), pytest.approx(0.14)]}

    import jax.profiler
    fake = NS(planes=hand_built_planes())
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: fake))
    gaps = tr.reduce("unused")["gaps"]
    assert sum(out["phase_gaps"].values()) == \
        pytest.approx(sum(gaps.values()))


def test_phase_gaps_on_the_recorded_v5e_trace():
    """A trace from before the phases: every gap falls outside the step,
    and the labels sum to the idle time ``trace_reduce`` finds."""
    before = tr.reduce(SMALL)
    out = pg.reduce(SMALL)
    after = tr.reduce(SMALL)
    for key in ("chips", "ops", "gaps"):
        assert after[key] == before[key]
    assert set(out["phase_gaps"]) == {pg.OUTSIDE}
    assert sum(out["phase_gaps"].values()) == \
        pytest.approx(sum(before["gaps"].values()))
    assert out["decode_in_phase"] == {"inside": 0, "total": 3,
                                      "start_margin_us": None,
                                      "end_margin_us": None}


def stats(**kw):
    base = {"n_slots": 4, "decode_steps": 0, "decode_tokens": 0,
            "prefill_chunks": 0, "prefill_tokens": 0,
            "phase_ns": {"serve.sample": 0, "serve.decode": 0},
            "phase_calls": {"serve.sample": 0, "serve.decode": 0}}
    base.update(kw)
    return base


def test_counter_readers_on_a_synthetic_run():
    """Two replicas' ``gen.stats`` at the window's open and close: the
    readers sum the deltas over replicas; a program without the
    counters leaves every reader silent."""
    s0 = [stats(decode_steps=10, decode_tokens=30, prefill_chunks=2,
                prefill_tokens=40,
                phase_ns={"serve.sample": 5_000_000}),
          stats(decode_steps=0, phase_ns={"serve.sample": 0})]
    s1 = [stats(decode_steps=20, decode_tokens=70, prefill_chunks=5,
                prefill_tokens=100,
                phase_ns={"serve.sample": 25_000_000}),
          stats(decode_steps=10, decode_tokens=20, prefill_chunks=1,
                prefill_tokens=4, phase_ns={"serve.sample": 10_000_000})]
    run = NS(snap={"stats0": s0, "stats1": s1}, chunk=32)
    read = lambda name: spec.metric_reader(name)(run)  # noqa: E731
    assert read("engine_sample_ms") == pytest.approx(30.0 / 20)
    assert read("decode_batch_fill_pct") == pytest.approx(
        100.0 * 60 / (20 * 4))
    assert read("prefill_useful_pct") == pytest.approx(
        100.0 * 64 / (4 * 32))

    older = [{"n_slots": 4, "prefix_hits": 0}]
    run.snap = {"stats0": older, "stats1": older}
    for name in NEW_READERS:
        assert read(name) is None, name


def test_idle_in_sample_on_a_synthetic_run():
    """Idle under ``serve.sample``, mean over chips, over the profiled
    window; silent without a trace or without phase gaps."""
    chips = {"/device:TPU:0": {"busy_s": 1.0},
             "/device:TPU:1": {"busy_s": 1.0}}
    run = NS(traced=(10.0, 14.0), reduced={
        "chips": chips, "phase_gaps": {"serve.sample": 1.2,
                                       "serve.decode": 0.5}})
    read = spec.metric_reader("idle_in_sample_pct")
    assert read(run) == pytest.approx(100.0 * 1.2 / 2 / 4.0)
    run.reduced["phase_gaps"] = {"outside_step": 0.3}
    assert read(run) == 0.0
    run.reduced = {"chips": chips, "gaps": {}}
    assert read(run) is None
    run.reduced = None
    assert read(run) is None


def fixture_cell():
    from bench.tools.phase_trace import with_idle_in_sample
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny-decoder",
                        "traffic": "tiny_chat", "chips": 1,
                        "why": "fixture"}]
    for m in bm["per_layer"] + bm["end_to_end"]:
        m.pop("workloads", None)
    return spec.load_cell("tiny.chat", benchmark=with_idle_in_sample(bm),
                          data_dir=FIXTURE)


def test_traced_fixture_run_reports_the_phase_metrics():
    """The engine's counters reach the readers through ``gen.stats``;
    on the CPU no device plane is traced, so the idle share under
    sampling falls silent while the phases still add up."""
    from bench.tools.phase_trace import traced_run
    out = traced_run(fixture_cell(), seed=SEED, seconds=2.0,
                     require_tpu=False)
    assert out["correct"], out["compared"]
    metrics = out["metrics"]
    for name in NEW_READERS:
        assert name in metrics, metrics
    assert metrics["engine_sample_ms"]["value"] > 0
    assert 0 < metrics["prefill_useful_pct"]["value"] <= 100
    assert 0 < metrics["decode_batch_fill_pct"]["value"] <= 100
    assert "idle_in_sample_pct" not in metrics
    ph = out["phases"]
    assert ph["phase_cost_ns"] > 0
    assert ph["calls_per_decode_step"]["serve.decode"] == pytest.approx(1)
    steps = ph["ms_per_decode_step"]
    assert steps["serve.step"] >= steps["serve.decode"] + \
        steps["serve.admit"] > 0
