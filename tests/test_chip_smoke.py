"""chip_smoke.py's phases, run on the CPU at the reduced size.

The script itself refuses to run without a TPU; these tests call its
functions directly with small shapes, so the serving path it drives on
the chip (client engine -> gateway -> engine, and device-pinned replicas
behind a routed, session-affine pool) is exercised on every test run.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402

SMALL = dict(prompt_lens=(16, 24), max_new=4, follow_up=4)


@pytest.fixture(scope="module")
def model_and_params():
    return chip_smoke.build(configs.reduced(chip_smoke.ARCH))


def test_gateway_phase_serves_and_matches_reference(model_and_params):
    model, params = model_and_params
    res = chip_smoke.gateway_phase(model, params, n_slots=4, max_len=64,
                                   session_cap=4, **SMALL)
    stats = res["stats"]
    assert stats["faults"] == 0
    # every follow-up resumed its pinned session
    assert stats["prefix_hits"] == 4 and stats["prefix_misses"] == 4
    ref = chip_smoke.reference_logits(model, params, res["prompt"])
    assert ref.shape == res["logits"].shape == (model.cfg.vocab,)
    assert chip_smoke.logits_error(res["logits"], ref) <= \
        chip_smoke.LOGITS_BOUND


REPLICA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax
    import chip_smoke as cs
    from repro import configs

    devices = jax.devices()
    assert len(devices) == 4, devices
    model, params = cs.build(configs.reduced(cs.ARCH))
    out = cs.replica_phase(model, params, devices, conversations=4,
                           turns=2, prompt_lens=(16, 24), max_new=4,
                           follow_up=4)
    assert out["pool"] == out["alone"]
    for d, eng, st in zip(devices, out["engines"], out["stats"]):
        held = cs.jax_leaves(eng.params, eng.cache, eng._cache1_zero)
        assert all(x.devices() == {d} for x in held), d
        assert st["steps"] > 0 and st["prefix_hits"] > 0, st
    assert out["affinity"]["hits"] == 4, out["affinity"]
    print("REPLICAS_OK")
""")


def test_replica_phase_pins_each_engine_to_its_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", REPLICA_SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "REPLICAS_OK" in r.stdout, r.stdout + r.stderr


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
