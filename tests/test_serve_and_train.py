"""End-to-end behaviours: continuous batching parity, gateway over tcp,
training loss decreases, checkpoint/restart determinism, elastic recovery
(membership epoch bump → restore from checkpoint and continue)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ParallelConfig
from repro.core.executor import Engine, RemoteError
from repro.core.types import Ret
from repro.models import Model, unzip
from repro.serve.engine import ServeEngine
from repro.services import (CheckpointClient, CheckpointServer,
                            MembershipClient, MembershipServer,
                            ServingGateway)
from repro.train import optim
from repro.train.step import init_state, make_train_step

CFG = configs.reduced("qwen1.5-0.5b").replace(compute_dtype="float32")


@pytest.fixture(scope="module")
def model_and_params():
    m = Model(CFG)
    params, _ = unzip(m.init(jax.random.PRNGKey(0)))
    return m, params


def test_continuous_batching_matches_isolated(model_and_params):
    """A request decoded among other (different) slot traffic must produce
    the same tokens as decoded alone."""
    m, params = model_and_params
    p_main = np.arange(1, 7)
    others = [np.arange(2, 10), np.arange(3, 6), np.arange(5, 17)]

    alone = ServeEngine(m, params, max_len=64, n_slots=1)
    want = alone.generate([p_main], max_new=6)[0]

    mixed = ServeEngine(m, params, max_len=64, n_slots=2)
    reqs = [mixed.submit(p, max_new=6) for p in [p_main] + others]
    mixed.drain()
    assert reqs[0].out_tokens == want


def test_gateway_tcp_end_to_end(model_and_params):
    m, params = model_and_params
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        gw = ServingGateway(srv, ServeEngine(m, params, max_len=64,
                                             n_slots=2))
        outs = []
        for i in range(3):
            outs.append(cli.call(srv.uri, "gen.generate",
                                 {"tokens": [1 + i, 2, 3], "max_new": 5},
                                 timeout=120.0))
        assert all(len(o["tokens"]) == 5 and o["done"] for o in outs)
        stats = cli.call(srv.uri, "gen.stats", {})
        assert stats["n_slots"] == 2
        gw.stop()


def test_gateway_sm_bulk_submit(model_and_params):
    """Gateway over the shared-memory tier: the prompt never rides the
    eager message — the gateway pulls it from the client's registered
    memory (gen.submit_bulk)."""
    import uuid
    m, params = model_and_params
    tag = uuid.uuid4().hex[:8]
    with Engine(f"sm://gw-{tag}") as srv, Engine(f"sm://gwc-{tag}") as cli:
        gw = ServingGateway(srv, ServeEngine(m, params, max_len=64,
                                             n_slots=2))
        tokens = np.asarray([1, 2, 3], np.int32)
        h = cli.expose([tokens])
        out = cli.call(srv.uri, "gen.submit_bulk",
                       {"desc": h.descriptor().to_bytes(), "count": 3,
                        "max_new": 4}, timeout=120.0)
        res = cli.call(srv.uri, "gen.result",
                       {"rid": out["rid"], "wait": True, "timeout": 60.0},
                       timeout=120.0)
        h.free()
        assert res["done"] and len(res["tokens"]) == 4
        stats = cli.call(srv.uri, "gen.stats", {})
        assert "sm://" in stats["uris"]
        gw.stop()


def test_gateway_step_fault_fails_requests(model_and_params):
    """A step that raises must not kill the gateway's loop silently:
    waiting gen.generate and gen.result callers get FAULT at once, the
    fault shows in gen.stats, and the gateway keeps serving."""
    m, params = model_and_params
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        serve = ServeEngine(m, params, max_len=64, n_slots=2)
        real_step = serve.step
        raised = threading.Event()

        def flaky_step():
            # fail the first step that has a request to serve
            if serve.pending() and not raised.is_set():
                raised.set()
                raise RuntimeError("injected step failure")
            return real_step()

        serve.step = flaky_step
        gw = ServingGateway(srv, serve)
        sub = cli.call(srv.uri, "gen.submit",
                       {"tokens": [4, 5, 6], "max_new": 3})
        t0 = time.monotonic()
        with pytest.raises(RemoteError) as ei:
            cli.call(srv.uri, "gen.result",
                     {"rid": sub["rid"], "wait": True, "timeout": 60.0},
                     timeout=60.0)
        assert ei.value.ret == Ret.FAULT
        assert "injected step failure" in str(ei.value)
        assert time.monotonic() - t0 < 30.0
        stats = cli.call(srv.uri, "gen.stats", {})
        assert stats["faults"] == 1
        assert "injected step failure" in stats["last_fault"]
        # the loop survived: the next request is served normally
        out = cli.call(srv.uri, "gen.generate",
                       {"tokens": [1, 2, 3], "max_new": 3}, timeout=120.0)
        assert out["done"] and len(out["tokens"]) == 3

        def broken_step():
            raise ValueError("broken step")

        serve.step = broken_step
        with pytest.raises(RemoteError) as ei:
            cli.call(srv.uri, "gen.generate",
                     {"tokens": [1, 2, 3], "max_new": 3}, timeout=60.0)
        assert ei.value.ret == Ret.FAULT and "broken step" in str(ei.value)
        time.sleep(0.2)        # an idle loop must not re-run the failing step
        assert cli.call(srv.uri, "gen.stats", {})["faults"] == 2
        gw.stop()


def make_batch(step):
    k = jax.random.PRNGKey(step)
    toks = jax.random.randint(k, (4, 33), 0, CFG.vocab)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_training_reduces_loss(model_and_params):
    m, _ = model_and_params
    ocfg = optim.OptConfig(lr=3e-3, warmup=2, decay_steps=40)
    state, _ = init_state(m, ocfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(m, ocfg,
                                   ParallelConfig(remat="none")))
    losses = []
    for i in range(15):
        state, metrics = step(state, make_batch(i % 3))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_checkpoint_restart_determinism(model_and_params):
    """Train 6 steps straight == train 3, save, restore, train 3 more."""
    m, _ = model_and_params
    ocfg = optim.OptConfig(lr=1e-3, warmup=0, decay_steps=100)
    step = jax.jit(make_train_step(m, ocfg, ParallelConfig(remat="none")))

    state, _ = init_state(m, ocfg, jax.random.PRNGKey(0))
    for i in range(6):
        state, _m = step(state, make_batch(i))
    direct = state

    with Engine(None) as e:
        CheckpointServer(e)
        cli = CheckpointClient(e, e.uri)
        state, _ = init_state(m, ocfg, jax.random.PRNGKey(0))
        for i in range(3):
            state, _m = step(state, make_batch(i))
        cli.save("t", 3, jax.tree_util.tree_map(np.asarray, state))

        fresh, _ = init_state(m, ocfg, jax.random.PRNGKey(42))  # wrong init
        restored, at = cli.restore("t", fresh)
        assert at == 3
        restored = jax.tree_util.tree_map(jnp.asarray, restored)
        for i in range(3, 6):
            restored, _m = step(restored, make_batch(i))

    for a, b in zip(jax.tree_util.tree_leaves(direct["params"]),
                    jax.tree_util.tree_leaves(restored["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_elastic_recovery_on_membership_change(model_and_params):
    """Simulated node failure: epoch bump triggers restore-from-checkpoint
    and training continues to lower loss."""
    m, _ = model_and_params
    ocfg = optim.OptConfig(lr=3e-3, warmup=0, decay_steps=100)
    step = jax.jit(make_train_step(m, ocfg, ParallelConfig(remat="none")))

    with Engine("tcp://127.0.0.1:0") as coord_e, \
            Engine("tcp://127.0.0.1:0") as trainer_e, \
            Engine("tcp://127.0.0.1:0") as peer_e:
        ms = MembershipServer(coord_e, heartbeat_timeout=0.4,
                              sweep_interval=0.1)
        CheckpointServer(coord_e)
        ckpt = CheckpointClient(trainer_e, coord_e.uri)

        epoch_changed = threading.Event()
        me = MembershipClient(trainer_e, coord_e.uri, "trainer", 0.1,
                              on_change=lambda v: epoch_changed.set())
        me.join()
        peer = MembershipClient(peer_e, coord_e.uri, "peer", 0.1)
        peer.join()
        time.sleep(0.3)
        epoch_changed.clear()

        state, _ = init_state(m, ocfg, jax.random.PRNGKey(0))
        for i in range(3):
            state, metrics = step(state, make_batch(i))
        ckpt.save("elastic", 3, jax.tree_util.tree_map(np.asarray, state))
        loss_at_ckpt = float(metrics["loss"])

        peer._stop.set()                        # peer dies silently
        assert epoch_changed.wait(5.0), "failure must bump the epoch"

        # driver reaction: rebuild (here: same host), restore, continue
        fresh, _ = init_state(m, ocfg, jax.random.PRNGKey(9))
        state2, at = ckpt.restore("elastic", fresh)
        state2 = jax.tree_util.tree_map(jnp.asarray, state2)
        for i in range(at, at + 5):
            state2, metrics2 = step(state2, make_batch(i))
        assert float(metrics2["loss"]) < loss_at_ckpt + 0.5
        ms.stop()
        me.leave()
