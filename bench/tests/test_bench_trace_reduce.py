"""The reduction from a profiler trace to busy time, executables and
idle gaps: on planes built by hand, and on a small trace recorded on a
TPU v5e and committed beside this file."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench import trace_reduce as tr  # noqa: E402

SMALL = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_on_hand_built_planes(monkeypatch):
    dev = plane("/device:TPU:0", {
        tr.OPS_LINE: [ev("fusion.1", 0, 100), ev("fusion.2", 50, 100),
                      ev("fusion.1", 400, 100)],
        tr.MODULES_LINE: [ev("jit_decode_step(7)", 0, 150),
                          ev("jit_decode_step(7)", 400, 100)]})
    host = plane("/host:CPU", {"python3": [
        ev("bench.step", 0, 600), ev("bench.sample", 160, 200)]})
    fake = NS(planes=[host, dev])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: fake))
    out = tr.reduce("unused")
    chip = out["chips"]["/device:TPU:0"]
    assert chip["busy_s"] == pytest.approx(250e-9)
    assert out["ops"]["jit_decode_step/fusion.1"] == pytest.approx(200e-9)
    assert out["gaps"] == {"bench.sample": pytest.approx(250e-9)}
    assert tr.module_seconds(out, "decode_step") == \
        (2, pytest.approx(250e-9))
    assert tr.module_seconds(out, "prefill_chunk") == (0, 0.0)


def test_reduce_on_a_recorded_v5e_trace():
    out = tr.reduce(SMALL)
    assert list(out["chips"]) == ["/device:TPU:0"]
    chip = out["chips"]["/device:TPU:0"]
    calls, secs = tr.module_seconds(out, "decode_step")
    assert calls == 3 and secs > 0
    span = (chip["last_ns"] - chip["first_ns"]) * 1e-9
    # every operation ran inside one of the three executions, which also
    # hold the short waits between their operations
    assert 0 < chip["busy_s"] <= secs <= span
    assert "bench.sample" in out["gaps"]
    assert all(name.startswith("jit_decode_step/%") for name in out["ops"])
