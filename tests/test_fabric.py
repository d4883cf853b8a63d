"""Service fabric: registry lifecycle (register/resolve/epoch/TTL/member
expiry), ServicePool routing (rr / least-loaded / locality / weighted),
budgeted retries + deadlines + hedging, credit-based backpressure +
adaptive credits, deadline-aware admission control (Ret.OVERLOAD),
replica-death failover, registry-restart resync (epoch nonce),
sm→tcp tier failover with cached-view demotion, graceful close()
thread-join semantics, and the event-driven gen.result path."""
import queue
import threading
import time
import uuid

import numpy as np
import pytest

from conftest import poll_until
from repro.core.executor import Engine, RemoteError
from repro.core.types import Ret
from repro.fabric import (BudgetExhausted, CreditGate, EwmaWeighted,
                          RegistryClient, RegistryService, RetryPolicy,
                          ServiceInstance, ServicePool, SessionAffinity,
                          resolve_service_uris)
from repro.fabric.pool import Replica
from repro.serve.engine import Request
from repro.services import (AdmissionController, MembershipServer,
                            ServingGateway)


@pytest.fixture
def reg():
    """Registry on its own engine, fast sweeps for test-speed expiry."""
    with Engine("tcp://127.0.0.1:0") as e:
        svc = RegistryService(e, instance_ttl=0.6, sweep_interval=0.1)
        yield e, svc
        svc.close()


def _echo_engine(name):
    e = Engine("tcp://127.0.0.1:0")
    e.register("echo", lambda x, _n=name: (_n, x))
    return e


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_register_resolve_epoch(reg):
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as cli_e:
        cli = RegistryClient(cli_e, reg_e.uri)
        e0 = cli.epoch()
        iid = cli.register("svc", "tcp://127.0.0.1:1111", capacity=4)
        assert cli.epoch() == e0 + 1
        view = cli.resolve("svc")
        assert [i["iid"] for i in view["instances"]] == [iid]
        assert view["instances"][0]["capacity"] == 4
        # load reports must NOT bump the epoch (cached views stay valid)
        cli.report("svc", iid, load=7.5)
        assert cli.epoch() == e0 + 1
        assert cli.resolve("svc")["instances"][0]["load"] == 7.5
        assert cli.services() == ["svc"]
        assert cli.deregister("svc", iid)
        assert cli.epoch() == e0 + 2
        assert cli.resolve("svc")["instances"] == []
        from repro.core.types import MercuryError
        with pytest.raises(MercuryError):
            resolve_service_uris(cli_e, reg_e.uri, "svc")


def test_registry_ttl_expires_silent_instance(reg):
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as cli_e:
        cli = RegistryClient(cli_e, reg_e.uri)
        cli.register("svc", "tcp://127.0.0.1:1111")   # never reports again
        e1 = cli.epoch()
        poll_until(lambda: not cli.resolve("svc")["instances"],
                   timeout=5.0, interval=0.1, msg="silent instance reaped")
        assert cli.resolve("svc")["instances"] == []
        assert cli.epoch() > e1


def test_registry_reaps_instances_of_dead_members(reg):
    """An instance bound to a member_id dies with its member (via the
    MembershipServer.on_expire hook), even while it keeps reporting."""
    reg_e, reg_svc = reg
    ms = MembershipServer(reg_e, heartbeat_timeout=0.4, sweep_interval=0.1)
    ms.on_expire(reg_svc._members_expired)
    with Engine("tcp://127.0.0.1:0") as w:
        cli = RegistryClient(w, reg_e.uri)
        w.call(reg_e.uri, "mem.join", {"member_id": "w1", "uri": w.uri})
        iid = cli.register("svc", w.uri, member_id="w1")
        # member w1 never heartbeats; the instance DOES keep reporting,
        # so only the member-expiry path can remove it
        def _reaped():
            try:
                cli.report("svc", iid, load=0.0)
                return False
            except RemoteError:
                return True                    # NOENTRY: reaped
        poll_until(_reaped, timeout=5.0, interval=0.05,
                   msg="member-bound instance reaped")
        assert cli.resolve("svc")["instances"] == []
    ms.close()


# ---------------------------------------------------------------------------
# pool routing
# ---------------------------------------------------------------------------
def test_pool_round_robin_distributes(reg):
    reg_e, _ = reg
    a, b = _echo_engine("a"), _echo_engine("b")
    with a, b, Engine("tcp://127.0.0.1:0") as cli:
        ia = ServiceInstance(a, reg_e.uri, "svc", capacity=4,
                             report_interval=0.1)
        ib = ServiceInstance(b, reg_e.uri, "svc", capacity=4,
                             report_interval=0.1)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr")
        hits = [pool.call("echo", i, timeout=10.0)[0] for i in range(8)]
        assert hits.count("a") == 4 and hits.count("b") == 4
        ia.close(), ib.close()


def test_pool_least_loaded_prefers_idle(reg):
    reg_e, _ = reg
    a, b = _echo_engine("a"), _echo_engine("b")
    with a, b, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        ia = rc.register("svc", a.uri, capacity=4, load=9.0)  # busy
        ib = rc.register("svc", b.uri, capacity=4, load=0.0)  # idle
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="least")
        hits = {pool.call("echo", i, timeout=10.0)[0] for i in range(6)}
        assert hits == {"b"}
        rc.deregister("svc", ia), rc.deregister("svc", ib)


def test_pool_locality_prefers_cheap_tier(reg):
    """Replica advertising a self:// tier must win over a tcp-only one
    for a co-located (same-process) client."""
    reg_e, _ = reg
    tag = uuid.uuid4().hex[:6]
    near = Engine([f"self://near-{tag}", "tcp://127.0.0.1:0"])
    far = _echo_engine("far")
    near.register("echo", lambda x: ("near", x))
    with near, far, Engine([f"self://cli-{tag}",
                            "tcp://127.0.0.1:0"]) as cli:
        rc = RegistryClient(cli, reg_e.uri)
        i1 = rc.register("svc", near.uri, capacity=4)
        i2 = rc.register("svc", far.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="locality")
        tiers = sorted(r.stat()["tier"] for r in pool.replicas())
        assert tiers == ["self", "tcp"]
        hits = {pool.call("echo", i, timeout=10.0)[0] for i in range(6)}
        assert hits == {"near"}
        rc.deregister("svc", i1), rc.deregister("svc", i2)


# ---------------------------------------------------------------------------
# retries / deadlines / hedging / flow control
# ---------------------------------------------------------------------------
def test_pool_retries_around_dead_replica(reg):
    reg_e, _ = reg
    ok = _echo_engine("ok")
    with ok, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        dead = rc.register("svc", "tcp://127.0.0.1:1", capacity=4)
        live = rc.register("svc", ok.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr",
                           policy=RetryPolicy(attempts=3, rpc_timeout=2.0,
                                              backoff_base=0.01))
        # every call must succeed even when ranked onto the dead one first
        assert all(pool.call("echo", i, timeout=15.0)[0] == "ok"
                   for i in range(6))
        rc.deregister("svc", dead), rc.deregister("svc", live)


def test_pool_deadline_bounds_slow_service(reg):
    reg_e, _ = reg
    slow = Engine("tcp://127.0.0.1:0")
    slow.register("nap", lambda x: time.sleep(3.0) or "late")
    with slow, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        iid = rc.register("svc", slow.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc",
                           policy=RetryPolicy(attempts=2, rpc_timeout=0.3,
                                              backoff_base=0.01,
                                              jitter=0.0))
        t0 = time.monotonic()
        with pytest.raises(Exception):
            pool.call("nap", None, timeout=0.8)
        elapsed = time.monotonic() - t0
        # never exceeds the deadline by more than one rpc timeout
        assert elapsed < 0.8 + 0.3 + 0.3, elapsed
        rc.deregister("svc", iid)


def test_pool_hedged_request_beats_straggler(reg):
    reg_e, _ = reg
    slow = Engine("tcp://127.0.0.1:0")
    slow.register("work", lambda x: time.sleep(2.0) or "slow")
    fast = Engine("tcp://127.0.0.1:0")
    fast.register("work", lambda x: "fast")
    with slow, fast, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        i1 = rc.register("svc", slow.uri, capacity=4)
        i2 = rc.register("svc", fast.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr",
                           policy=RetryPolicy(attempts=3, rpc_timeout=5.0,
                                              hedge_after=0.1))
        t0 = time.monotonic()
        outs = [pool.call("work", i, timeout=10.0) for i in range(4)]
        dt = time.monotonic() - t0
        assert all(o == "fast" for o in outs)   # hedge wins every time
        assert dt < 2.0, dt                     # never waited for slow
        rc.deregister("svc", i1), rc.deregister("svc", i2)


def test_pool_credit_backpressure(reg):
    reg_e, _ = reg
    release = threading.Event()
    srv = Engine("tcp://127.0.0.1:0")
    srv.register("hold", lambda x: release.wait(10.0) and "held")
    with srv, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        iid = rc.register("svc", srv.uri, capacity=2)
        pool = ServicePool(cli, reg_e.uri, "svc", credits_per_target=2,
                           policy=RetryPolicy(attempts=1, rpc_timeout=15.0))
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(2) as tp:
            f1 = tp.submit(pool.call, "hold", 1, 12.0)
            f2 = tp.submit(pool.call, "hold", 2, 12.0)
            deadline = time.time() + 5
            while time.time() < deadline:
                if pool.stats()["replicas"][0]["inflight"] == 2:
                    break
                time.sleep(0.02)
            st = pool.stats()["replicas"][0]
            assert st["inflight"] == 2          # both credits consumed
            # third call: saturated -> bounded wait -> backpressure error
            with pytest.raises(BudgetExhausted):
                pool.call("hold", 3, timeout=0.4)
            st = pool.stats()["replicas"][0]
            assert st["backpressured"] >= 1 and st["rejected"] >= 1
            release.set()
            assert f1.result(15) == "held" and f2.result(15) == "held"
        # all credits returned after completion
        assert pool.stats()["replicas"][0]["inflight"] == 0
        rc.deregister("svc", iid)


def test_pool_failover_on_replica_death(reg):
    """Kill a replica abruptly mid-run: no client-visible failure, and
    the TTL sweep (epoch bump) eventually drops it from the view."""
    reg_e, _ = reg
    a, b = _echo_engine("a"), _echo_engine("b")
    ia = ServiceInstance(a, reg_e.uri, "svc", capacity=4,
                         report_interval=0.1)
    ib = ServiceInstance(b, reg_e.uri, "svc", capacity=4,
                         report_interval=0.1)
    with b, Engine("tcp://127.0.0.1:0") as cli:
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr",
                           refresh_interval=0.1,
                           policy=RetryPolicy(attempts=4, rpc_timeout=1.0,
                                              backoff_base=0.01))
        assert len(pool.replicas()) == 2
        ia.close(deregister=False)     # heartbeats stop: simulated crash
        a.shutdown()
        # every call still succeeds (retries absorb the dead replica)
        assert all(pool.call("echo", i, timeout=15.0)[0] == "b"
                   for i in range(8))
        poll_until(lambda: (pool.refresh(force=True) or
                            len(pool.replicas()) == 1),
                   timeout=5.0, interval=0.1, msg="dead replica pruned")
        assert len(pool.replicas()) == 1       # epoch bump pruned the dead
        ib.close()


def test_pool_affine_calls_pin_replica(reg):
    """call_routed reports the serving instance; call_on pins follow-ups
    to it (the gen.submit/gen.result pattern: rids are replica-local)."""
    reg_e, _ = reg
    a, b = _echo_engine("a"), _echo_engine("b")
    with a, b, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        ids = {rc.register("svc", e.uri, capacity=4): n
               for e, n in ((a, "a"), (b, "b"))}
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr")
        for i in range(6):
            out, iid = pool.call_routed("echo", i, timeout=10.0)
            assert out[0] == ids[iid]          # winner reported truthfully
            # pinned follow-ups always land on the same instance
            assert all(pool.call_on(iid, "echo", j, timeout=10.0)[0]
                       == ids[iid] for j in range(3))
        from repro.fabric import PoolError
        with pytest.raises(BudgetExhausted) as ei:
            pool.call_on("no-such-iid", "echo", 0, timeout=2.0,
                         policy=RetryPolicy(attempts=2, rpc_timeout=0.5,
                                            backoff_base=0.01))
        assert isinstance(ei.value.cause, PoolError)
        for iid in ids:
            rc.deregister("svc", iid)


def test_pool_recovers_replica_after_transient_outage(reg):
    """A replica that was down (marked down / undemotable) must come back
    once reachable again — demotions are soft state, not a tombstone."""
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        srv = _echo_engine("a")
        port_uri = srv.uri
        iid = rc.register("svc", port_uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", down_ttl=0.2,
                           policy=RetryPolicy(attempts=2, rpc_timeout=1.0,
                                              backoff_base=0.01))
        assert pool.call("echo", 1, timeout=10.0)[0] == "a"
        srv.shutdown()                 # transient outage begins
        with pytest.raises(Exception):
            pool.call("echo", 2, timeout=3.0)
        rep = pool.replicas()[0]
        assert not rep.is_up or rep.bad_schemes   # excluded right now
        # replica comes back on a NEW port; re-registers under same iid
        srv2 = _echo_engine("a2")
        rc.register("svc", srv2.uri, capacity=4, iid=iid)
        def _recovered():
            try:
                return pool.call("echo", 3, timeout=3.0)[0] == "a2"
            except Exception:
                return False
        poll_until(_recovered, timeout=5.0, interval=0.1,
                   msg="replica recovery (not tombstoned)")
        srv2.shutdown()
        rc.deregister("svc", iid)


# ---------------------------------------------------------------------------
# registry restart (epoch nonce), re-register epoch storms, replica locking
# ---------------------------------------------------------------------------
def test_reregister_same_uris_does_not_bump_epoch(reg):
    """The ServiceInstance report-loop recovery path re-registers under
    its old iid with unchanged uris; membership did not change, so the
    epoch must not move (a bump forces fab.resolve storms in every
    pool).  Changing the uris IS a membership change and must bump."""
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as cli_e:
        cli = RegistryClient(cli_e, reg_e.uri)
        iid = cli.register("svc", "tcp://127.0.0.1:1111", capacity=2)
        e1 = cli.epoch()
        for _ in range(5):     # recovery re-registers: same iid, same uris
            cli.register("svc", "tcp://127.0.0.1:1111", capacity=2,
                         iid=iid)
        assert cli.epoch() == e1
        # load/capacity still refreshed by the re-register
        cli.register("svc", "tcp://127.0.0.1:1111", capacity=2, iid=iid,
                     load=4.5)
        assert cli.resolve("svc")["instances"][0]["load"] == 4.5
        assert cli.epoch() == e1
        # moved to a new address: that IS membership
        cli.register("svc", "tcp://127.0.0.1:2222", capacity=2, iid=iid)
        assert cli.epoch() == e1 + 1
        cli.deregister("svc", iid)


@pytest.mark.slow
def test_pool_survives_registry_restart():
    """Acceptance: a pool keeps routing through a registry kill/restart
    (epoch resets to 0 under a fresh nonce) and converges to the fresh
    view within one refresh interval instead of treating the reset epoch
    as a stale race forever."""
    reg_e = Engine("tcp://127.0.0.1:0")
    reg_svc = RegistryService(reg_e)
    port = int(reg_e.uri.rsplit(":", 1)[1])
    srv = _echo_engine("a")
    inst = ServiceInstance(srv, reg_e.uri, "svc", capacity=4,
                           report_interval=0.1)
    with srv, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        # pad the old registry's epoch well past anything the restarted
        # (reset-to-0) registry will reach during the test
        for i in range(5):
            rc.register("pad", f"tcp://127.0.0.1:{2000 + i}")
        pool = ServicePool(cli, reg_e.uri, "svc", refresh_interval=0.1,
                           policy=RetryPolicy(attempts=3, rpc_timeout=2.0,
                                              backoff_base=0.01))
        old_epoch, old_nonce = pool.epoch, pool._view_nonce
        assert old_epoch >= 6 and old_nonce is not None
        assert pool.call("echo", 1, timeout=10.0)[0] == "a"

        reg_svc.close()
        reg_e.shutdown()               # registry dies
        # stale cached view keeps the data path alive
        assert pool.call("echo", 2, timeout=10.0)[0] == "a"

        # restart on the SAME port: empty state, epoch 0, fresh nonce
        reg_e2 = Engine(f"tcp://127.0.0.1:{port}")
        reg_svc2 = RegistryService(reg_e2)
        try:
            # the instance's report loop re-registers itself (NOENTRY ->
            # register); wait for the fresh registry to list it
            rc2 = RegistryClient(cli, reg_e2.uri)
            poll_until(lambda: rc2.resolve("svc")["instances"],
                       timeout=10.0, interval=0.05,
                       msg="instance re-registration on the fresh registry")
            # pool must converge onto the fresh view (new nonce, LOWER
            # epoch) within ~one refresh interval
            poll_until(lambda: (pool.refresh() or
                                pool._view_nonce != old_nonce),
                       timeout=5.0, msg="pool resync off the dead "
                                        "registry's view")
            assert pool.epoch < old_epoch          # reset accepted
            assert pool.call("echo", 3, timeout=10.0)[0] == "a"
        finally:
            reg_svc2.close()
            reg_e2.shutdown()
    inst.close(deregister=False)


@pytest.mark.slow
def test_replica_mutators_are_race_free():
    """demote / reresolve / mark_down / record hammered from many
    threads: every transition atomic (the PR-3 locking fix), no replica
    state torn, no exception escapes."""
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        srv.register("echo", lambda x: x)
        rep = Replica("r1", [srv.uri], 4, 0.0, CreditGate(4))
        assert rep.resolve(cli)
        stop = time.monotonic() + 1.5
        errors = []

        def hammer(which):
            try:
                while time.monotonic() < stop:
                    if which == 0:
                        rep.demote(cli)
                    elif which == 1:
                        rep.reresolve(cli)
                    elif which == 2:
                        rep.mark_down(0.01)
                        _ = rep.is_up
                    else:
                        rep.record(0.001, ok=True)
                        rep.record(None, ok=False)
            except Exception as e:     # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        threads = [threading.Thread(target=hammer, args=(i % 4,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        # post-storm state is coherent: recoverable and callable
        assert rep.reresolve(cli)
        assert rep.is_up


# ---------------------------------------------------------------------------
# weighted balancing + adaptive credits
# ---------------------------------------------------------------------------
def _fake_rep(iid, ema, inflight, load=0.0, capacity=1):
    rep = Replica(iid, [f"tcp://127.0.0.1:{9000 + hash(iid) % 100}"],
                  capacity, load, CreditGate(max(inflight, 1) + 1))
    rep.ema_latency = ema
    for _ in range(inflight):
        rep.gate.try_acquire()
    return rep


def test_weighted_balancer_ranks_by_expected_wait():
    b = EwmaWeighted()
    fast_idle = _fake_rep("fast", 0.01, 0)
    fast_busy = _fake_rep("busy", 0.01, 3)
    slow_idle = _fake_rep("slow", 0.10, 0)
    ranked = b.rank([slow_idle, fast_busy, fast_idle])
    assert ranked[0] is fast_idle
    # 10ms x 4 in flight beats 100ms idle: the busy-fast replica still
    # wins over the slow one (0.04 < 0.10 expected wait)
    assert ranked[1] is fast_busy and ranked[2] is slow_idle
    # capacity normalizes: same latency+occupancy, 4x capacity -> first
    big = _fake_rep("big", 0.10, 0, capacity=4)
    assert b.rank([slow_idle, big])[0] is big
    # piggybacked server load counts even with zero local in-flight
    loaded = _fake_rep("loaded", 0.01, 0, load=9.0)
    assert b.rank([loaded, fast_idle])[0] is fast_idle


def test_weighted_balancer_probes_unsampled_replicas():
    """A replica with no latency sample must rank with the best observed
    EWMA (occupancy-scaled), not sink to the bottom — otherwise a
    recovered replica is never probed and never gets a sample."""
    b = EwmaWeighted()
    sampled = _fake_rep("sampled", 0.05, 2)
    unsampled = _fake_rep("new", 0.0, 0)
    assert b.rank([sampled, unsampled])[0] is unsampled


def test_pool_adaptive_credits_grow_on_fast_replica(reg):
    """Default pool gates are adaptive: completions under the latency
    target grow the limit past the initial credits_per_target.  The
    target is pinned explicitly — every completion counts as fast — so
    the test exercises the record->gate->growth wiring, not the latency
    jitter of a loaded CI box (the control law itself is pinned by
    tests/test_fabric_flow.py)."""
    reg_e, _ = reg
    srv = _echo_engine("a")
    with srv, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        iid = rc.register("svc", srv.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", credits_per_target=2,
                           credit_max=16, credit_target_latency=30.0)
        for i in range(40):
            assert pool.call("echo", i, timeout=10.0)[0] == "a"
        st = pool.stats()["replicas"][0]
        assert st["limit"] > 2 and st["grown"] >= 1
        assert st["credits"] <= 16
        rc.deregister("svc", iid)


# ---------------------------------------------------------------------------
# deadline budget propagation + admission control (Ret.OVERLOAD)
# ---------------------------------------------------------------------------
def test_deadline_budget_rides_request_header():
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        seen = {}

        def probe(_x, handle):
            seen["budget"] = handle.remaining_budget()
            return "ok"
        srv.register("probe", probe, pass_handle=True)
        assert cli.call(srv.uri, "probe", None, timeout=5.0) == "ok"
        assert 4.0 < seen["budget"] <= 5.0
        # deadline= form propagates the *remaining* budget
        assert cli.call(srv.uri, "probe", None,
                        deadline=time.monotonic() + 2.0) == "ok"
        assert 1.0 < seen["budget"] <= 2.0
        # no timeout -> no budget -> admission never sheds
        fut = cli.call_async(srv.uri, "probe", None, timeout=None)
        assert fut.result(10.0) == "ok"
        assert seen["budget"] is None


def test_gateway_sheds_overload_fast(reg):
    """A gateway whose backlog x EWMA service time exceeds the caller's
    budget sheds with Ret.OVERLOAD in sub-RPC time instead of queueing
    doomed work; generous budgets are still admitted."""
    reg_e, _ = reg
    serve = FakeServe()
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        gw = ServingGateway(srv, serve)
        for _ in range(3):             # past min_samples: 500ms/request
            gw.admission.observe(0.5)
        t0 = time.monotonic()
        with pytest.raises(RemoteError) as ei:
            cli.call(srv.uri, "gen.submit", {"tokens": [1]}, timeout=0.2)
        assert ei.value.ret == Ret.OVERLOAD
        assert time.monotonic() - t0 < 0.19, "shed must be a fast-fail"
        # same request with headroom is admitted
        out = cli.call(srv.uri, "gen.submit", {"tokens": [1]}, timeout=5.0)
        assert "rid" in out
        st = cli.call(srv.uri, "gen.stats", {}, timeout=5.0)
        assert st["shed"] == 1 and st["admitted"] >= 1
        gw.close()


def test_pool_reroutes_overload_to_other_replica(reg):
    """OVERLOAD is retryable-on-another-replica with NO backoff: a pool
    facing one overloaded and one healthy gateway completes every call
    on the healthy one, within the original deadline."""
    reg_e, _ = reg
    slow_serve, fast_serve = FakeServe(), FakeServe()
    engines = [Engine("tcp://127.0.0.1:0") for _ in range(2)]
    gws = [ServingGateway(engines[0], slow_serve, registry=reg_e.uri,
                          service="gen", report_interval=0.1),
           ServingGateway(engines[1], fast_serve, registry=reg_e.uri,
                          service="gen", report_interval=0.1)]
    for _ in range(3):                 # replica 0 "takes 30s per request"
        gws[0].admission.observe(30.0)
    with Engine("tcp://127.0.0.1:0") as cli:
        pool = ServicePool(cli, reg_e.uri, "gen", balancer="rr",
                           refresh_interval=0.1,
                           policy=RetryPolicy(attempts=3, rpc_timeout=5.0,
                                              backoff_base=0.2))
        assert len(pool.replicas()) == 2
        t0 = time.monotonic()
        outs = [pool.call("gen.generate", {"tokens": [1], "max_new": 2},
                          timeout=5.0) for _ in range(6)]
        dt = time.monotonic() - t0
        assert all(o["done"] for o in outs)
        # rr alternates, so ~3 calls hit the overloaded replica first and
        # were shed + rerouted; fast_rets skips the 0.2s backoff, so the
        # whole batch finishes far inside the per-call deadline
        shed = cli.call(engines[0].uri, "gen.stats", {},
                        timeout=5.0)["shed"]
        assert shed >= 1
        assert dt < 5.0, dt
    for gw, e in zip(gws, engines):
        gw.close()
        e.shutdown()


def test_admission_tracks_pure_service_time():
    """The shedding estimate uses the pure-service EWMA; queue wait is
    priced only via the backlog term (feeding submit→done turnaround
    back into the EWMA would double-count queueing right after a burst
    and over-shed until the EWMA re-converged)."""
    adm = AdmissionController(min_samples=1)
    for _ in range(4):                 # 50ms of work behind a ~1s queue
        adm.observe(0.05, turnaround_s=1.0)
    st = adm.stats()
    assert 40 < st["ema_service_ms"] < 60
    assert st["ema_turnaround_ms"] > 500
    # 4 backlog / 2 slots -> 2 waves + own service: ~150ms, NOT ~3s —
    # a caller with a 500ms budget is admitted post-burst
    assert adm.estimate_wait(backlog=4, parallelism=2) < 0.2
    adm.admit(0.5, backlog=4, parallelism=2)   # must not raise


def test_gateway_admission_excludes_queue_wait():
    """Requests held in the gateway queue must not inflate the service
    EWMA: t_admit (slot entry) is the measurement origin, t_submit only
    feeds the separate turnaround EWMA."""
    gate = threading.Event()
    serve = FakeServe(auto=False, gate=gate)
    with Engine("tcp://127.0.0.1:0") as e:
        gw = ServingGateway(e, serve)
        try:
            with Engine("tcp://127.0.0.1:0") as cli:
                cli.call(e.uri, "gen.submit", {"tokens": [1]}, timeout=5.0)
            time.sleep(0.5)            # queue wait: gate still closed
            gate.set()                 # admit: slot occupancy starts
            deadline = time.time() + 5
            while time.time() < deadline and not serve.parked:
                time.sleep(0.01)
            assert serve.parked
            time.sleep(0.25)           # service time
            req = serve.parked[0]
            req.done_event.set()
            req._fire_done()
            st = gw.admission.stats()
            # service ~= 0.25s (plus step-loop poll slack), turnaround
            # additionally carries the ~0.5s queue wait
            assert st["admission_samples"] == 1
            assert st["ema_service_ms"] < 550
            assert st["ema_turnaround_ms"] > 650
            assert st["ema_turnaround_ms"] > st["ema_service_ms"] + 300
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# tier failover (na/multi + pool demotion)
# ---------------------------------------------------------------------------
def test_multi_lookup_falls_back_past_stale_sm():
    """An address set whose sm tier is unreachable must resolve tcp."""
    tag = uuid.uuid4().hex[:6]
    live = _echo_engine("live")
    with live, Engine([f"sm://mf-cli-{tag}", "tcp://127.0.0.1:0"]) as cli:
        addr = cli.lookup(f"sm://ghost-{tag};{live.uri}")
        assert addr.uri.startswith("tcp://")
        assert cli.call(addr, "echo", 1, timeout=10.0)[0] == "live"


def test_pool_demotes_tier_when_sm_dies_midrun(reg):
    """A replica resolved at the sm tier whose segment goes away must be
    demoted to tcp in the pool's cached view, transparently."""
    reg_e, _ = reg
    tag = uuid.uuid4().hex[:6]
    sm_half = Engine(f"sm://dm-{tag}")
    tcp_half = _echo_engine("tcp-half")
    sm_half.register("echo", lambda x: ("sm-half", x))
    with tcp_half, Engine([f"sm://dmc-{tag}",
                           "tcp://127.0.0.1:0"]) as cli:
        rc = RegistryClient(cli, reg_e.uri)
        iid = rc.register("svc", f"{sm_half.uri};{tcp_half.uri}",
                          capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="locality",
                           policy=RetryPolicy(attempts=3, rpc_timeout=2.0,
                                              backoff_base=0.01))
        rep = pool.replicas()[0]
        assert rep.stat()["tier"] == "sm"
        assert pool.call("echo", 1, timeout=10.0)[0] == "sm-half"
        sm_half.shutdown()             # sm segment vanishes mid-run
        out = pool.call("echo", 2, timeout=15.0)
        assert out[0] == "tcp-half"    # transparent fallback
        assert rep.stat()["tier"] == "tcp" and "sm" in rep.bad_schemes
        rc.deregister("svc", iid)


# ---------------------------------------------------------------------------
# graceful close semantics + event-driven gen.result
# ---------------------------------------------------------------------------
def test_membership_close_joins_sweeper():
    with Engine("tcp://127.0.0.1:0") as e:
        ms = MembershipServer(e, sweep_interval=0.1)
        assert ms._sweeper.is_alive()
        ms.close()
        assert not ms._sweeper.is_alive()
        ms.close()                     # idempotent


class FakeServe:
    """Minimal ServeEngine stand-in: completes each request with one
    token per step — lets gateway plumbing be tested without a model.
    Stamps ``t_submit``/``t_admit`` like the real engine (the admission
    EWMA's measurement origins); an optional ``gate`` event holds
    requests in the queue until set, creating real queue wait."""

    def __init__(self, n_slots=2, auto=True, gate=None):
        self.queue = queue.Queue()
        self.work = threading.Event()
        self.n_slots = n_slots
        self.auto = auto
        self.gate = gate               # None = admit immediately
        self.parked = []               # auto=False: admitted, not finished
        self._rid = 0
        self._lock = threading.Lock()

    def submit(self, tokens, max_new=32, temperature=0.0, eos_id=-1,
               frontend=None, on_token=None, session_id=None):
        with self._lock:
            self._rid += 1
            req = Request(self._rid, np.asarray(tokens, np.int32), max_new)
        req.t_submit = time.monotonic()
        self.queue.put(req)
        self.work.set()
        return req

    def pending(self):
        return self.queue.qsize()

    def step(self):
        if self.gate is not None and not self.gate.is_set():
            return 0
        n = 0
        while True:
            try:
                req = self.queue.get_nowait()
            except queue.Empty:
                return n
            req.t_admit = time.monotonic()
            if self.auto:
                req.out_tokens.append(7)
                req.done_event.set()
                req._fire_done()
                n += 1
            else:
                self.parked.append(req)   # test completes them by hand

    def stats(self):
        return {"active_slots": 0, "n_slots": self.n_slots,
                "queued": self.queue.qsize(), "max_len": 64,
                "occupancy": 0.0, "pinned_sessions": 0,
                "prefix_hits": 0, "prefix_misses": 0}


def test_gateway_close_joins_step_loop():
    with Engine("tcp://127.0.0.1:0") as e:
        gw = ServingGateway(e, FakeServe())
        assert gw._thread.is_alive()
        gw.close()
        assert not gw._thread.is_alive()
        gw.close()                     # idempotent


def test_gen_result_wait_is_event_driven():
    """Waiting gen.result handlers must not park handler-pool threads:
    with every pool thread's worth of waiters outstanding, an unrelated
    RPC still gets through, and completion wakes all waiters."""
    serve = FakeServe(auto=False)
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        gw = ServingGateway(srv, serve)
        rid = cli.call(srv.uri, "gen.submit", {"tokens": [1, 2]})["rid"]
        poll_until(lambda: serve.parked, timeout=5.0, interval=0.01,
                   msg="request admitted and parked")
        req = serve.parked[0]                  # admitted, unfinished
        cbs_before = len(req._done_cbs)
        waiters = [cli.call_async(srv.uri, "gen.result",
                                  {"rid": rid, "wait": True,
                                   "timeout": 20.0}, timeout=30.0)
                   for _ in range(4)]          # = srv handler_threads
        # each parked waiter registers a done callback; wait until all
        # four are event-parked (not thread-parked) before probing
        poll_until(lambda: len(req._done_cbs) >= cbs_before + 4,
                   timeout=5.0, interval=0.01, msg="waiters parked")
        # old busy/parked design: all 4 pool threads blocked -> this hangs
        stats = cli.call(srv.uri, "gen.stats", {}, timeout=2.0)
        assert stats["n_slots"] == 2
        req.out_tokens.append(9)
        req.done_event.set()
        req._fire_done()
        outs = [w.result(timeout=10) for w in waiters]
        assert all(o["done"] and o["tokens"] == [9] for o in outs)
        gw.close()


def test_gen_result_wait_times_out_with_partial_tokens():
    serve = FakeServe(auto=False)
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        gw = ServingGateway(srv, serve)
        rid = cli.call(srv.uri, "gen.submit", {"tokens": [1]})["rid"]
        t0 = time.monotonic()
        out = cli.call(srv.uri, "gen.result",
                       {"rid": rid, "wait": True, "timeout": 0.3},
                       timeout=10.0)
        assert not out["done"] and time.monotonic() - t0 < 5.0
        gw.close()


def test_gateway_self_registers_and_routes_through_pool(reg):
    reg_e, _ = reg
    serves = [FakeServe(), FakeServe()]
    engines = [Engine("tcp://127.0.0.1:0") for _ in serves]
    gws = [ServingGateway(e, s, registry=reg_e.uri, service="gen",
                          report_interval=0.1)
           for e, s in zip(engines, serves)]
    with Engine("tcp://127.0.0.1:0") as cli:
        pool = ServicePool(cli, reg_e.uri, "gen", balancer="rr",
                           refresh_interval=0.1,
                           policy=RetryPolicy(attempts=4, rpc_timeout=5.0,
                                              backoff_base=0.01))
        assert len(pool.replicas()) == 2
        outs = [pool.call("gen.generate", {"tokens": [1, 2], "max_new": 4},
                          timeout=15.0) for _ in range(4)]
        assert all(o["done"] for o in outs)
        # capacity was piggybacked from n_slots
        assert all(r.capacity == 2 for r in pool.replicas())
        # kill one replica: calls keep succeeding, view shrinks on expiry
        gws[0].instance.close(deregister=False)
        gws[0].stop()
        engines[0].shutdown()
        assert all(pool.call("gen.generate",
                             {"tokens": [3], "max_new": 2},
                             timeout=15.0)["done"] for _ in range(4))
    gws[1].close()
    engines[1].shutdown()


def test_session_affinity_rehomes_across_replica_kill(reg):
    """Multi-turn sessions over a routed pool: follow-ups return to
    their home gateway; once that replica is killed, every turn still
    completes and its sessions move to the survivor (a fresh-prefill
    route, never a lost request)."""
    reg_e, _ = reg
    serves = [FakeServe(), FakeServe()]
    engines = [Engine("tcp://127.0.0.1:0") for _ in serves]
    gws = [ServingGateway(e, s, registry=reg_e.uri, service="gen-sess",
                          report_interval=0.1)
           for e, s in zip(engines, serves)]
    sessions = [f"conv{i}" for i in range(4)]
    with Engine("tcp://127.0.0.1:0") as cli:
        pool = ServicePool(cli, reg_e.uri, "gen-sess", balancer="rr",
                           refresh_interval=0.1,
                           policy=RetryPolicy(attempts=4, rpc_timeout=5.0,
                                              backoff_base=0.01))
        assert len(pool.replicas()) == 2
        aff = SessionAffinity(pool)

        def turn(sid):
            res, iid = aff.call_routed(
                sid, "gen.generate",
                {"tokens": [1, 2], "max_new": 2, "session_id": sid},
                timeout=15.0)
            assert res["done"] and res["tokens"], (sid, res)
            return iid

        homes = {sid: turn(sid) for sid in sessions}
        assert all(turn(sid) == homes[sid] for sid in sessions)
        assert aff.hits == len(sessions)          # follow-ups went home
        dead = gws[0].instance.iid
        live = gws[1].instance.iid
        orphaned = [sid for sid in sessions if homes[sid] == dead]
        assert orphaned, homes                    # rr spread the first turn
        gws[0].instance.close(deregister=False)
        gws[0].stop()
        engines[0].shutdown()
        assert all(turn(sid) == live for sid in sessions)
        assert aff.moves == len(orphaned)
        assert all(aff.lookup(sid) == live for sid in sessions)
    gws[1].close()
    engines[1].shutdown()


# ---------------------------------------------------------------------------
# checkpoint / datafeed resolvable by name
# ---------------------------------------------------------------------------
def test_checkpoint_resolvable_by_name(reg):
    from repro.services import CheckpointClient, CheckpointServer
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli_e:
        cs = CheckpointServer(srv, registry=reg_e.uri)
        cli = CheckpointClient(cli_e, registry=reg_e.uri)
        tree = {"w": np.arange(100, dtype=np.float32)}
        assert cli.save("m", 1, tree)["ok"]
        out, step = cli.restore("m", {"w": np.zeros(100, np.float32)})
        assert step == 1
        np.testing.assert_array_equal(out["w"], tree["w"])
        cs.close()


def test_datafeed_resolvable_by_name(reg):
    from repro.data.pipeline import SyntheticSource
    from repro.services import DataFeedClient, DataFeedServer
    reg_e, _ = reg
    src = SyntheticSource(vocab=100, seq_len=16, batch_per_host=2)
    with Engine("tcp://127.0.0.1:0") as fe, \
            Engine("tcp://127.0.0.1:0") as tr:
        fs = DataFeedServer(fe, src, registry=reg_e.uri)
        cli = DataFeedClient(tr, registry=reg_e.uri)
        b = cli.get(3)
        np.testing.assert_array_equal(b["tokens"],
                                      src.batch_at(3)["tokens"])
        fs.close()


def test_services_read_is_token_cached_and_evicts_on_epoch_bump(reg):
    """``fab.services`` carries the authoritative ``(nonce, epoch)``
    token: the client caches it under that token (not merely TTL) and an
    epoch bump evicts — a long TTL must NOT serve the stale service
    list once the registry's token has advanced."""
    reg_e, _ = reg
    with Engine("tcp://127.0.0.1:0") as ce, \
            Engine("tcp://127.0.0.1:0") as we:
        cli = RegistryClient(ce, reg_e.uri, cache_ttl=30.0)
        writer = RegistryClient(we, reg_e.uri)
        writer.register("alpha", "tcp://127.0.0.1:1111")
        assert cli.services() == ["alpha"]
        tok = cli.cache.stats()["token"]
        # the token came from the fab.services response itself
        assert tok["nonce"] is not None and tok["epoch"] >= 0
        assert cli.cache.stats()["entries"] >= 1
        ev0 = cli.cache.stats()["evictions"]

        writer.register("beta", "tcp://127.0.0.1:2222")   # epoch bump
        # a cheap epoch poll reveals the bump and evicts the cached list
        # (the 30s TTL alone could never explain the refreshed read)
        cli.epoch(fresh=True)
        assert cli.cache.stats()["evictions"] > ev0
        assert cli.services() == ["alpha", "beta"]
