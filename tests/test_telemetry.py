"""Telemetry plane: the metrics registry (counters/gauges/log-bucket
histograms + the ``fab.metrics`` RPC) and wire-propagated distributed
tracing — header propagation, retry/hedge attempt spans, the quorum
write-proxy hop, the self-tier local-dispatch fast path, and
cross-process span-tree reassembly via ``dbg.trace``."""
import os
import subprocess
import sys
import time

import pytest

from repro.core.executor import Engine
from repro.core.types import MercuryError, Ret
from repro.fabric import (RegistryClient, RegistryService, RetryPolicy,
                          ServiceInstance, ServicePool)
from repro.telemetry import metrics, trace
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.phases import Phases

from conftest import poll_until

LEASE = 0.5
GOSSIP = 0.12


def _wait(pred, timeout=8.0, interval=0.03, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def traced():
    """Force 100% head sampling for the test, restore defaults after."""
    prev_sample, prev_enabled = trace.sample_rate(), trace.is_enabled()
    trace.configure(sample=1.0, enabled=True)
    trace.clear()
    yield
    trace.configure(sample=prev_sample, enabled=prev_enabled)
    trace.clear()


@pytest.fixture
def reg():
    with Engine("tcp://127.0.0.1:0") as e:
        svc = RegistryService(e, instance_ttl=5.0, sweep_interval=0.2)
        yield e, svc
        svc.close()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("reqs")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert r.counter("reqs") is c            # idempotent getter

    g = r.gauge("load")
    g.set(2.5)
    assert r.gauge("load").value == 2.5
    live = r.gauge("live", fn=lambda: 7)
    assert live.value == 7.0
    bad = r.gauge("bad", fn=lambda: 1 / 0)
    assert bad.value == 0.0                  # callback failure -> fallback

    h = r.histogram("lat_ms")
    for v in (0.5, 3.0, 3.5, 900.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["max"] == 900.0
    assert snap["buckets"]["le_1"] == 1      # 0.5
    assert snap["buckets"]["le_4"] == 2      # 3.0, 3.5
    assert snap["buckets"]["le_1024"] == 1   # 900
    assert h.quantile(0.5) == 4.0
    assert h.quantile(1.0) == 1024.0


def test_labels_and_snapshot_shape():
    r = MetricsRegistry()
    r.counter("hits", service="gen").inc(2)
    r.counter("hits", service="ckpt").inc(1)
    snap = r.snapshot()
    assert snap["counters"]["hits{service=gen}"] == 2
    assert snap["counters"]["hits{service=ckpt}"] == 1
    assert set(snap) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phases(family_ns, family_calls, names):
    return Phases({n: metrics.counter(family_ns, phase=n) for n in names},
                  {n: metrics.counter(family_calls, phase=n) for n in names})


def test_phases_accumulate_and_nest():
    ph = phases("test.phases.nest.phase_ns", "test.phases.nest.phase_calls",
                ("outer", "inner"))
    for _ in range(2):
        with ph("outer"):
            with ph("inner"):
                time.sleep(0.002)
            with ph("inner"):
                pass
    assert ph.calls == {"outer": 2, "inner": 4}
    assert ph.ns["outer"] >= ph.ns["inner"] >= 2 * 2_000_000
    counters = metrics.snapshot()["counters"]
    assert counters["test.phases.nest.phase_calls{phase=inner}"] == 4
    assert counters["test.phases.nest.phase_calls{phase=outer}"] == 2
    assert counters["test.phases.nest.phase_ns{phase=outer}"] == \
        ph.ns["outer"]


def test_phase_closes_when_its_body_raises():
    ph = phases("test.phases.raise.phase_ns",
                "test.phases.raise.phase_calls", ("p",))
    with pytest.raises(ValueError):
        with ph("p"):
            time.sleep(0.001)
            raise ValueError("boom")
    assert ph.calls["p"] == 1 and ph.ns["p"] >= 1_000_000
    with pytest.raises(KeyError):
        ph("unknown")
    assert metrics.snapshot()["counters"][
        "test.phases.raise.phase_calls{phase=p}"] == 1


def test_phase_lands_in_a_profiler_trace(tmp_path):
    """On the profiler's clock: the phase is a host-plane event of a
    trace captured while it ran."""
    import jax
    import jax.numpy as jnp
    ph = phases("test.phases.trace.phase_ns",
                "test.phases.trace.phase_calls", ("serve.probe",))
    jnp.ones(4).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ph("serve.probe"):
            (jnp.arange(8) * 2).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    found = [(plane.name, e.duration_ns) for plane in pd.planes
             for line in plane.lines for e in line.events
             if e.name == "serve.probe"]
    assert len(found) == 1
    plane, dur = found[0]
    assert plane.startswith("/host:") and dur > 0
    assert ph.calls["serve.probe"] == 1


def test_fab_metrics_rpc_served_by_every_engine():
    metrics.counter("test.telemetry.probe").inc(3)
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        out = cli.call(srv.uri, "fab.metrics", {})
        assert out["pid"] == os.getpid()
        assert out["metrics"]["counters"]["test.telemetry.probe"] >= 3


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------
def test_sampling_modes(traced):
    # sampled root records; its children record
    root = trace.start_trace("op")
    assert root.recorded and root.ctx.sampled
    child = trace.start_span("step", root.ctx)
    child.finish("OK")
    root.finish("OK")
    assert len(trace.spans_for(root.ctx.trace_hex)) == 2

    # unsampled root: context still propagates, nothing records
    trace.configure(sample=0.0)
    root = trace.start_trace("op")
    assert not root.recorded and root.ctx is not None
    child = trace.start_span("step", root.ctx)
    assert not child.recorded
    assert child.ctx.trace_id == root.ctx.trace_id
    child.finish("OK")
    root.finish("OK")
    assert trace.spans_for(root.ctx.trace_hex) == []

    # disabled: no context at all
    trace.configure(enabled=False)
    assert trace.start_trace("op") is trace.NULL_SPAN
    assert trace.start_span("step", None) is trace.NULL_SPAN


def test_ring_is_bounded(traced):
    trace.configure(ring=8)
    for _ in range(50):
        trace.start_trace("x").finish()
    assert len(trace.export()["spans"]) == 8
    trace.configure(ring=4096)


def test_build_tree_dedups_and_joins(traced):
    root = trace.start_trace("root")
    a = trace.start_span("a", root.ctx)
    b = trace.start_span("b", a.ctx)
    b.finish()
    a.finish()
    root.finish()
    spans = trace.spans_for(root.ctx.trace_hex)
    roots, children = trace.build_tree(spans + spans)   # union may dup
    assert len(roots) == 1 and roots[0]["name"] == "root"
    tree = trace.format_tree(spans)
    assert tree.splitlines()[0].startswith("root")
    assert "    b" in tree                              # depth 2 indent


# ---------------------------------------------------------------------------
# wire propagation
# ---------------------------------------------------------------------------
def test_server_span_rides_the_wire(traced):
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        srv.register("echo", lambda x: x)
        root = trace.start_trace("client.op")
        with trace.use(root.ctx):
            assert cli.call(srv.uri, "echo", 42) == 42
        root.finish("OK")
        spans = trace.spans_for(root.ctx.trace_hex)
        srv_spans = [s for s in spans if s["name"] == "rpc.echo"]
        assert len(srv_spans) == 1
        s = srv_spans[0]
        assert s["parent"] == f"{root.ctx.span_id:016x}"
        assert s["tags"]["engine"] == srv.uri
        assert s["tags"]["local"] is False
        assert s["status"] == "OK"
        roots, _ = trace.build_tree(spans)
        assert len(roots) == 1


def test_local_dispatch_span(traced):
    """The PR-6 self-tier fast path hands the context object across
    directly — the server span still appears, tagged local=True."""
    with Engine(None) as e:
        e.register("echo", lambda x: x + 1)
        root = trace.start_trace("client.op")
        with trace.use(root.ctx):
            assert e.call(e.uri, "echo", 1) == 2
        root.finish("OK")
        spans = trace.spans_for(root.ctx.trace_hex)
        srv = [s for s in spans if s["name"] == "rpc.echo"]
        assert len(srv) == 1 and srv[0]["tags"]["local"] is True
        roots, _ = trace.build_tree(spans)
        assert len(roots) == 1


def test_unsampled_requests_record_nothing(traced):
    trace.configure(sample=0.0)
    with Engine("tcp://127.0.0.1:0") as srv, \
            Engine("tcp://127.0.0.1:0") as cli:
        srv.register("echo", lambda x: x)
        root = trace.start_trace("client.op")
        with trace.use(root.ctx):
            cli.call(srv.uri, "echo", 1)
        root.finish("OK")
        assert trace.spans_for(root.ctx.trace_hex) == []


# ---------------------------------------------------------------------------
# pool: retry / hedge attempt spans
# ---------------------------------------------------------------------------
def test_retry_yields_one_connected_trace(traced, reg):
    """A replica that sheds the first call (AGAIN) forces a retry: the
    trace must show one root pool span with two attempt children, the
    first AGAIN and the second OK, each with its server span below."""
    reg_e, _ = reg
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MercuryError(Ret.AGAIN, "warming up")
        return x * 2

    srv = Engine("tcp://127.0.0.1:0")
    srv.register("work", flaky)
    with srv, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        rc.register("svc", srv.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc",
                           policy=RetryPolicy(attempts=3, rpc_timeout=5.0,
                                              backoff_base=0.01, jitter=0.0))
        assert pool.call("work", 21, timeout=10.0) == 42

        # span ring is fed from done-callbacks; settle briefly
        _wait(lambda: any(s["name"] == "pool.svc.work"
                          for s in trace.export()["spans"]),
              msg="pool root span")
        root_span = [s for s in trace.export()["spans"]
                     if s["name"] == "pool.svc.work"][0]
        # the server finishes its span after it has responded
        _wait(lambda: sum(s["name"] == "rpc.work"
                          for s in trace.spans_for(root_span["trace"])) >= 2,
              msg="both server spans")
        spans = trace.spans_for(root_span["trace"])
        attempts = sorted((s for s in spans if s["name"] == "attempt.work"),
                          key=lambda s: s["tags"]["n"])
        assert [a["status"] for a in attempts] == ["AGAIN", "OK"]
        servers = [s for s in spans if s["name"] == "rpc.work"]
        assert sorted(s["status"] for s in servers) == ["AGAIN", "OK"]
        assert root_span["status"] == "OK"
        assert root_span["tags"]["attempts"] == 2
        roots, _ = trace.build_tree(spans)
        assert len(roots) == 1 and roots[0]["span"] == root_span["span"]


def test_hedge_loser_span_closes_canceled(traced, reg):
    reg_e, _ = reg
    slow = Engine("tcp://127.0.0.1:0")
    slow.register("work", lambda x: time.sleep(2.0) or "slow")
    fast = Engine("tcp://127.0.0.1:0")
    fast.register("work", lambda x: "fast")
    with slow, fast, Engine("tcp://127.0.0.1:0") as cli:
        rc = RegistryClient(cli, reg_e.uri)
        rc.register("svc", slow.uri, capacity=4)
        rc.register("svc", fast.uri, capacity=4)
        pool = ServicePool(cli, reg_e.uri, "svc", balancer="rr",
                           policy=RetryPolicy(attempts=3, rpc_timeout=5.0,
                                              hedge_after=0.05))
        # rr alternates the primary: within two calls one of them hedges
        # from the slow replica to the fast one
        outs = [pool.call("work", i, timeout=10.0) for i in range(2)]
        assert all(o == "fast" for o in outs)
        _wait(lambda: any(s["status"] == "CANCELED"
                          for s in trace.export()["spans"]),
              msg="canceled hedge-loser span")
        hedged = [s for s in trace.export()["spans"]
                  if s["name"] == "attempt.work" and s["tags"]["hedge"]]
        assert hedged, "no hedge attempt span recorded"
        trace_id = hedged[0]["trace"]
        spans = trace.spans_for(trace_id)
        statuses = sorted(s["status"] for s in spans
                          if s["name"] == "attempt.work")
        assert statuses == ["CANCELED", "OK"]
        roots, _ = trace.build_tree(spans)
        assert len(roots) == 1


# ---------------------------------------------------------------------------
# quorum write-proxy hop
# ---------------------------------------------------------------------------
def test_write_proxy_hop_joins_the_trace(traced):
    """A write sent to a follower is proxied to the leaseholder; the
    trace shows client root -> follower server span -> proxy span ->
    leader server span, one connected tree."""
    engines = [Engine("tcp://127.0.0.1:0") for _ in range(3)]
    peers = [e.uri for e in engines]
    regs = [RegistryService(e, peers=peers, lease_ttl=LEASE,
                            gossip_interval=GOSSIP, sweep_interval=0.2,
                            instance_ttl=5.0)
            for e in engines]
    try:
        _wait(lambda: regs[0].is_leader, msg="leader election")
        with Engine("tcp://127.0.0.1:0") as cli:
            follower = RegistryClient(cli, peers[1])
            root = trace.start_trace("client.write")
            with trace.use(root.ctx):
                follower.register("svc", "tcp://127.0.0.1:1111", capacity=1)
            root.finish("OK")
            spans = trace.spans_for(root.ctx.trace_hex)
            names = [s["name"] for s in spans]
            assert names.count("rpc.fab.register") == 2   # follower+leader
            proxies = [s for s in spans
                       if s["name"] == "proxy.fab.register"]
            assert len(proxies) == 1
            assert proxies[0]["tags"]["leader"] == regs[0].self_uri
            roots, children = trace.build_tree(spans)
            assert len(roots) == 1 and roots[0]["name"] == "client.write"
            # leader's server span hangs below the proxy span
            below_proxy = children.get(proxies[0]["span"], [])
            assert [s["name"] for s in below_proxy] == ["rpc.fab.register"]
    finally:
        for r in regs:
            r.close()
        for e in engines:
            e.shutdown()


# ---------------------------------------------------------------------------
# cross-process reassembly via dbg.trace
# ---------------------------------------------------------------------------
_WORKER_SRC = r"""
import sys, time
from repro.core.executor import Engine
e = Engine("tcp://127.0.0.1:0")
e.register("work", lambda x: x * 2)
print(e.uri, flush=True)
sys.stdin.readline()
e.shutdown()
"""


def test_dbg_trace_reassembles_across_processes(traced, tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.Popen([sys.executable, "-c", _WORKER_SRC],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, env=env,
                            cwd=os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))))
    try:
        uri = proc.stdout.readline().strip()
        assert uri.startswith("tcp://"), uri
        with Engine("tcp://127.0.0.1:0") as cli:
            root = trace.start_trace("client.op")
            with trace.use(root.ctx):
                assert cli.call(uri, "work", 21, timeout=20.0) == 42
            root.finish("OK")

            def fetch():
                r = cli.call(uri, "dbg.trace",
                             {"trace_id": root.ctx.trace_hex}, timeout=20.0)
                return r if any(s["name"] == "rpc.work"
                                for s in r["spans"]) else None

            # the server span is recorded after the response is sent:
            # under load dbg.trace can arrive before it
            remote = poll_until(fetch, timeout=10.0,
                                msg="the worker's rpc.work span")
        assert remote["pid"] != os.getpid()
        spans = trace.spans_for(root.ctx.trace_hex) + remote["spans"]
        roots, _ = trace.build_tree(spans)
        assert len(roots) == 1 and roots[0]["name"] == "client.op"
        assert len({s["pid"] for s in spans}) == 2
        srv = [s for s in spans if s["name"] == "rpc.work"]
        assert len(srv) == 1 and srv[0]["pid"] == remote["pid"]
    finally:
        try:
            proc.stdin.close()
        except Exception:
            pass
        proc.wait(timeout=10.0)
