"""The Granite-3.0 MoE configuration through the harness on the CPU: its
family maps the file onto the program's preset and refuses what differs,
the tiny fixture ``tiny-granite`` is served and checked against the
float32 reference as a cell's requests are, and the MoE layer's reader
reads the engine's counters."""
import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import spec  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixture"
SEED = 2 ** 33 + 29


def granite_cell(config="tiny-granite", traffic="tiny_docs"):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.granite", "config": config,
                        "traffic": traffic, "chips": 1, "why": "fixture"}]
    for m in bm["per_layer"] + bm["end_to_end"]:
        m.pop("workloads", None)
    return spec.load_cell("tiny.granite", benchmark=bm, data_dir=FIXTURE)


def test_published_config_maps_onto_the_preset():
    cell = spec.load_cell("granite3moe.doc_qa")
    c = cell.config
    assert c["reduced"] == []
    cfg = cell.family.program_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab) == (32, 1536, 24, 8, 64, 512, 49155)
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (40, 8)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.015625, 0.22, 6.0)
    assert (cfg.param_dtype, cfg.compute_dtype) == ("bfloat16", "bfloat16")
    shape = cell.family.cost_shape(c)
    assert (shape.experts, shape.top_k, shape.ff) == (40, 8, 512)
    assert cell.traffic["clients_per_slot"] * \
        c["deployment"]["n_slots"] == 12


@pytest.mark.parametrize("key,value", [
    ("residual_multiplier", 1.0), ("attention_multiplier", 0.125),
    ("embedding_multiplier", 1.0), ("logits_scaling", 1.0),
    ("hidden_size", 1024)])
def test_a_key_off_the_preset_is_refused_unless_reduced(key, value):
    c = copy.deepcopy(spec.Finder().json("configs", "granite-3.0-3b-a800m"))
    fam = spec.Finder().module("families", "granite")
    c[key] = value
    with pytest.raises(ValueError, match=key):
        fam.program_config(c)
    c["reduced"] = [key]
    cfg = fam.program_config(c)
    field = {"hidden_size": "d_model"}.get(key, key)
    assert getattr(cfg, field) == value


@pytest.mark.parametrize("params", ["float32", "bfloat16", "float16"])
def test_weights_dtype_is_the_files(params):
    """``dtypes.params`` sets the weights' dtype over the preset's
    float32; one the program does not hold weights in is refused."""
    c = copy.deepcopy(spec.Finder().json("configs", "granite-3.0-3b-a800m"))
    c["dtypes"]["params"] = params
    fam = spec.Finder().module("families", "granite")
    if params == "float16":
        with pytest.raises(ValueError, match="float16"):
            fam.program_config(c)
    else:
        assert fam.program_config(c).param_dtype == params


def _served_in_float32(cell, **override):
    """The fixture's model served in float32 with a float32 cache (one
    field of the program's config overridden, for a fault), and its
    weights."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cfg = cell.family.program_config(cell.config).replace(
        compute_dtype="float32", **override)
    model = Model(cfg)
    w = cell.family.make_weights(model, SEED, cell.config,
                                 jax.devices()[0])
    eng = ServeEngine(model, w, max_len=256, n_slots=4, chunk_tokens=32,
                      impl="xla", cache_dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 512, n).tolist() for n in (40, 90, 150)]
    return w, prompts, eng.generate(prompts, max_new=16)


def _gaps(cell, w, prompts, outs):
    return np.concatenate([cell.reference.gaps(cell.config, w, p, o)
                           for p, o in zip(prompts, outs)])


def test_granite_reference_agrees_with_the_program_in_float32():
    """Found by name, the family's weights and the reference's float32
    pass: served in float32 (bfloat16 weights, as the configuration holds
    them), every greedy token is the reference's best, chunked prefill
    and cached decode alike."""
    cell = granite_cell()
    w, prompts, outs = _served_in_float32(cell)
    assert float(np.max(_gaps(cell, w, prompts, outs))) < 1e-4


def test_a_dropped_residual_multiplier_serves_other_tokens():
    """The same model served with ``residual_multiplier`` left at 1 puts
    first tokens that the reference ranks lower: their mean gap is past
    the fixture's limit, although computed in float32."""
    cell = granite_cell()
    w, prompts, outs = _served_in_float32(cell, residual_multiplier=1.0)
    assert float(np.mean(_gaps(cell, w, prompts, outs))) > \
        cell.config["limits"]["mean_logit_gap"]


def test_float8_control_fails_where_the_program_passes():
    """Served in bfloat16 as configured, the program's tokens lie on
    average within the fixture's limit of the reference's best; the
    float8 control's first tokens do not.  (Their widest gaps overlap: a
    bfloat16 router flips near-tied experts now and then, so the mean
    carries the limit.)"""
    import jax
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cell = granite_cell()
    model = Model(cell.family.program_config(cell.config))
    w = cell.family.make_weights(model, SEED, cell.config,
                                 jax.devices()[0])
    eng = ServeEngine(model, w, max_len=256, n_slots=4, chunk_tokens=32,
                      impl="xla")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 512, n).tolist() for n in (40, 90, 150, 200)]
    outs = eng.generate(prompts, max_new=16)
    prog = float(np.mean(_gaps(cell, w, prompts, outs)))
    ctrl = float(np.mean(np.concatenate([
        cell.reference.gaps(cell.config, w, p, o, control=True)
        for p, o in zip(prompts, outs)])))
    limit = cell.config["limits"]["mean_logit_gap"]
    assert prog <= limit < ctrl, (prog, limit, ctrl)


def test_moe_rows_useful_pct_reads_the_counters():
    read = spec.metric_reader("moe_rows_useful_pct")
    stats = lambda a, r: {"moe_assignments": a, "moe_rows": r}  # noqa: E731
    run = SimpleNamespace(snap={"stats0": [stats(10, 100), stats(0, 0)],
                                "stats1": [stats(30, 300), stats(25, 100)]})
    assert read(run) == pytest.approx(100.0 * (20 + 25) / (200 + 100))
    run.snap = {"stats0": [{}], "stats1": [{}]}      # an older program
    assert read(run) is None
    run.snap = {"stats0": [stats(0, 0)], "stats1": [stats(0, 0)]}
    assert read(run) is None                        # no experts


def test_traced_fixture_run_reads_the_moe_layer():
    """The tiny Granite through ``run_cell`` as a cell runs it (bfloat16,
    the gateway, the window), traced: its MoE reader reads the share of
    expert rows that carried a token, between the decode's and the
    chunk's bounds."""
    from bench import run as brun
    cell = granite_cell()
    out = brun.run_cell(cell, seed=SEED, seconds=2.0, traced=True,
                        require_tpu=False)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    share = out["metrics"]["moe_rows_useful_pct"]["value"]
    k, e = cell.config["num_experts_per_tok"], \
        cell.config["num_local_experts"]
    assert 0 < share <= 100.0 * k / e


@pytest.mark.parametrize("fault", ["kv_unwritten", "state_unchanged",
                                   "half_batch", "token_altered"])
def test_a_planted_fault_is_not_correct(fault):
    """Each of ``bench/faults.py``'s faults, planted in the tiny
    Granite's engine and run as a cell runs (the gateway, the window, the
    sample the reference checks): the configuration's limits refuse it
    (0.0035–0.018 against the limit's 0.001 and the program's 4e-5)."""
    from bench import faults
    from bench import run as brun
    with faults.planted(faults.FAULTS[fault]):
        out = brun.run_cell(granite_cell(), seed=SEED, seconds=2.0,
                            traced=False, require_tpu=False)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["compared"]


def test_fault_readings_tool_reads_the_program_and_a_fault():
    """``bench/tools/fault_readings.py`` at the fixture's size: the
    program passes the limits and the fault it plants does not, each
    with both gaps read."""
    from bench.tools.fault_readings import reading
    from repro.models import Model
    cell = granite_cell()
    model = Model(cell.family.program_config(cell.config))
    got = {f: reading(cell, model, SEED, f, 2.0, require_tpu=False)
           for f in ("none", "kv_unwritten")}
    assert got["none"]["passed"] and not got["kv_unwritten"]["passed"]
    for g in got.values():
        assert g["requests"] >= 3 and g["failed"] == 0
        assert 0 <= g["mean_logit_gap"] <= g["max_logit_gap"]
    assert got["kv_unwritten"]["mean_logit_gap"] > \
        got["none"]["mean_logit_gap"]


def test_compiled_moe_steps_carry_the_four_scopes():
    """The engine's decode step and prefill chunk, compiled for the tiny
    Granite, name the MoE layer's four stages in their instructions'
    metadata, where ``bench/tools/scope_trace.py`` finds them."""
    import jax
    from bench import faults
    from bench.tools.scope_trace import SCOPES, keep_texts, scope_of
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cell = granite_cell()
    model = Model(cell.family.program_config(cell.config))
    w = cell.family.make_weights(model, SEED, cell.config,
                                 jax.devices()[0])
    texts = {}
    with faults.planted(keep_texts(texts)):
        eng = ServeEngine(model, w, max_len=256, n_slots=4,
                          chunk_tokens=32, impl="xla")
    eng.generate([list(range(1, 60))], max_new=3)
    assert set(texts) == {"decode_step", "prefill_chunk"}
    for exe, text in texts.items():
        assert set(scope_of(text).values()) == set(SCOPES), exe


def test_scope_seconds_on_hand_built_planes():
    """Operations joined to scopes by instruction name, inside the
    executable whose module event holds their start; overlapping
    operations of one scope count once."""
    from bench.tools.scope_trace import scope_seconds
    text = "\n".join([
        '%fusion.1 = f32[4] fusion(), metadata={op_name="jit(d)/'
        'while/body/moe.experts/dot_general"}',
        '%fusion.2 = f32[4] fusion(), metadata={op_name="jit(d)/'
        'moe.route/top_k"}',
        'ROOT %copy.3 = f32[4] copy(), metadata={op_name="jit(d)/'
        'attn/copy"}'])
    ev = lambda n, s, d: SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d)
    line = lambda n, es: SimpleNamespace(name=n, events=es)  # noqa: E731
    plane = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit_decode_step(1)", 0, 100),
                             ev("jit_other(2)", 200, 100)]),
        line("XLA Ops", [ev("%fusion.1 = f32[4]", 0, 40),
                         ev("%fusion.1 = f32[4]", 20, 40),
                         ev("%fusion.2 = f32[4]", 70, 10),
                         ev("%copy.3 = f32[4]", 80, 20),
                         ev("%fusion.1 = f32[4]", 210, 50)])])
    host = SimpleNamespace(name="/host:CPU", lines=[])
    got = scope_seconds([host, plane], {"decode_step": text})
    d = got["decode_step"]
    assert d["calls"] == 1 and d["seconds"] == pytest.approx(100e-9)
    assert d["scopes"] == pytest.approx({"moe.experts": 60e-9,
                                         "moe.route": 10e-9,
                                         "moe.*": 70e-9})
