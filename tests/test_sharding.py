"""Sharded control plane (DESIGN.md §12): shard-map properties
(cross-process stability, balance, minimal movement under growth),
cross-shard routing and ``fab.services`` merge, per-shard
``(nonce, epoch)`` read-cache tokens, the launcher's co-hosted shard
mode, and the shard-isolation chaos test (leaseholder kill on shard 0
must be invisible to shard 1)."""
import os
import subprocess
import sys
import threading
import time

import pytest

from conftest import poll_until
from proptest import cases
from repro.core.executor import Engine
from repro.fabric import (RegistryClient, RegistryService, ServiceInstance,
                          ServicePool)
from repro.fabric.sharding import (ShardedRegistryClient, membership_home,
                                   parse_shard_spec, registry_client_for,
                                   shard_addr, shard_of)

LEASE = 0.5
GOSSIP = 0.12
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# shard map properties (pure)
# ---------------------------------------------------------------------------
def test_shard_of_stability_across_processes():
    """Same name -> same shard from a different interpreter: the map
    must not lean on anything process-local (PYTHONHASHSEED, import
    order, id())."""
    names = [f"svc-{i}" for i in range(40)] + ["a", "trainer/emb", "日本語"]
    local = [shard_of(n, 4) for n in names]
    env = dict(os.environ, PYTHONPATH=_SRC, PYTHONHASHSEED="12345")
    prog = ("import sys\n"
            "from repro.fabric.sharding import shard_of\n"
            "names = sys.stdin.read().splitlines()\n"
            "print(' '.join(str(shard_of(n, 4)) for n in names))\n")
    out = subprocess.run([sys.executable, "-c", prog],
                         input="\n".join(names), capture_output=True,
                         text=True, env=env, check=True).stdout
    assert [int(x) for x in out.split()] == local


@cases(n=5, seed=11)
def test_shard_of_balance(rng):
    """10k random names land within ±20% of uniform at M=4."""
    names = [bytes(rng.integers(97, 123, size=12)).decode()
             + str(int(rng.integers(0, 10**9))) for _ in range(10_000)]
    counts = [0, 0, 0, 0]
    for n in names:
        counts[shard_of(n, 4)] += 1
    for c in counts:
        assert abs(c - 2500) <= 500, f"imbalanced shards: {counts}"


@cases(n=5, seed=12)
def test_shard_of_minimal_movement(rng):
    """Growing the map M -> M+1 remaps ~1/(M+1) of names, and every
    remapped name moves TO the new shard (rendezvous monotonicity) —
    never between surviving shards."""
    names = [f"n{int(rng.integers(0, 10**12))}-{i}" for i in range(2000)]
    for m in (2, 3, 4, 7):
        before = [shard_of(n, m) for n in names]
        after = [shard_of(n, m + 1) for n in names]
        moved = [i for i in range(len(names)) if before[i] != after[i]]
        assert all(after[i] == m for i in moved), \
            f"M={m}: a name moved between surviving shards"
        frac = len(moved) / len(names)
        assert frac <= 1.0 / (m + 1) + 0.05, \
            f"M={m}: {frac:.1%} of names moved (expected ~{1/(m+1):.1%})"


def test_shard_of_single_shard_and_errors():
    assert shard_of("anything", 1) == 0
    assert shard_of("anything", ["tcp://a:1"]) == 0
    with pytest.raises(ValueError):
        shard_of("x", 0)


def test_parse_spec_membership_home_and_shard_addr():
    spec = "tcp://a:1,tcp://b:1 | tcp://a:2"
    assert parse_shard_spec(spec) == ["tcp://a:1,tcp://b:1", "tcp://a:2"]
    # membership rides shard 0; unsharded specs pass through untouched
    assert membership_home(spec) == "tcp://a:1,tcp://b:1"
    assert membership_home("tcp://a:1,tcp://b:1") == "tcp://a:1,tcp://b:1"
    assert membership_home(["tcp://a:1", "tcp://b:1"]) == \
        ["tcp://a:1", "tcp://b:1"]
    # co-hosting offset convention: port + k, name suffix for portless
    assert shard_addr("tcp://10.0.0.1:7700", 3) == "tcp://10.0.0.1:7703"
    assert shard_addr("tcp://h:7700;sm://ctrl", 1) == "tcp://h:7701;sm://ctrl-1"
    assert shard_addr("sm://ctrl", 0) == "sm://ctrl"
    with pytest.raises(ValueError):
        parse_shard_spec("|")


# ---------------------------------------------------------------------------
# sharded client over live shards
# ---------------------------------------------------------------------------
def _mk_shards(m, **kw):
    """m single-node registry shards (each its own ReplicationCore
    leaseholder) plus the '|'-joined client spec."""
    engines = [Engine("tcp://127.0.0.1:0") for _ in range(m)]
    regs = [RegistryService(e, sweep_interval=0.1, **kw) for e in engines]
    return engines, regs, "|".join(e.uri for e in engines)


def _owned_by(client, shard, prefix="own"):
    """A service name owned by ``shard`` under ``client``'s map."""
    for i in range(10_000):
        name = f"{prefix}-{i}"
        if client.shard_of(name) == shard:
            return name
    raise AssertionError(f"no name owned by shard {shard}?!")


def test_cross_shard_routing_and_services_merge():
    engines, regs, spec = _mk_shards(2, instance_ttl=30.0)
    cli = Engine("tcp://127.0.0.1:0")
    try:
        c = ShardedRegistryClient(cli, spec, timeout=5.0)
        names = [f"merge-{i}" for i in range(12)]
        for n in names:
            c.register(n, ["tcp://10.0.0.1:1"])
        # every name landed on exactly its owning shard
        per_shard = [RegistryClient(cli, e.uri, timeout=5.0)
                     for e in engines]
        for n in names:
            owner = c.shard_of(n)
            for k, direct in enumerate(per_shard):
                got = len(direct.resolve(n)["instances"])
                assert got == (1 if k == owner else 0), \
                    f"{n} visible on shard {k} (owner {owner})"
        # both shards actually own something (the map spreads names)
        owners = {c.shard_of(n) for n in names}
        assert owners == {0, 1}
        # fab.services: sorted union across shards, and a strict
        # superset of any single shard's slice
        merged = c.services()
        assert merged == sorted(names)
        for direct in per_shard:
            slice_ = direct.services()
            assert set(slice_) < set(merged)
        # per-shard epochs/nonces are independent authorities
        infos = c.epoch_info()
        assert len(infos) == 2 and infos[0][1] != infos[1][1]
        assert len(c.status()["shards"]) == 2
    finally:
        for r in regs:
            r.close()
        for e in engines + [cli]:
            e.shutdown()


@pytest.mark.parametrize("m", [1, 2])
def test_concurrent_writers_land_every_registration(m):
    """Writer threads sharing one client engine register instances
    across the shards, re-register them on new addresses and report
    their load: no call fails, and every instance resolves on its owning
    shard afterwards with its last address and load."""
    engines, regs, spec = _mk_shards(m, instance_ttl=30.0)
    cli = Engine("tcp://127.0.0.1:0")
    services = [f"scale-{i:02d}" for i in range(16)]
    n, n_threads = 200, 4
    errors, landed = [], [[] for _ in range(n_threads)]

    def writer(t):
        c = ShardedRegistryClient(cli, spec, timeout=5.0)
        for i in range(t, n, n_threads):
            svc = services[i % len(services)]
            try:
                iid = c.register(svc, [f"tcp://10.0.0.1:{i}"], capacity=4)
                c.register(svc, [f"tcp://10.0.0.2:{i}"], capacity=4,
                           iid=iid)
                c.report(svc, iid, 0.5)
                landed[t].append(iid)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

    try:
        threads = [threading.Thread(target=writer, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not errors, errors[:3]
        iids = [iid for mine in landed for iid in mine]
        assert len(set(iids)) == n
        c = ShardedRegistryClient(cli, spec, timeout=5.0)
        seen = [inst for svc in services
                for inst in c.resolve(svc, fresh=True)["instances"]]
        assert sorted(i["iid"] for i in seen) == sorted(iids)
        assert all(i["uris"][0].startswith("tcp://10.0.0.2:")
                   and i["load"] == 0.5 for i in seen)
        assert {c.shard_of(svc) for svc in services} == set(range(m))
    finally:
        for r in regs:
            r.close()
        for e in engines + [cli]:
            e.shutdown()


def test_pool_and_service_instance_route_through_sharded_spec():
    """ServicePool + ServiceInstance take the '|' spec unchanged: both
    bind to the owning shard and the data path works end to end."""
    engines, regs, spec = _mk_shards(2, instance_ttl=30.0)
    cli = Engine("tcp://127.0.0.1:0")
    worker = Engine("tcp://127.0.0.1:0", handler_threads=2)
    worker.register("echo", lambda x: x)
    inst = pool = None
    try:
        svc = _owned_by(ShardedRegistryClient(cli, spec), 1, "pooled")
        inst = ServiceInstance(worker, spec, svc, report_interval=0.1)
        # the reporter bound to the owning shard's quorum
        assert inst.client.uris == [engines[1].uri]
        pool = ServicePool(cli, spec, svc, refresh_interval=0.2)
        assert pool.registry.uris == [engines[1].uri]
        poll_until(lambda: pool.replicas(), msg="pool sees the instance")
        assert pool.call("echo", b"hi", timeout=5.0) == b"hi"
        # registry_client_for: plain client for unsharded specs, owner
        # binding with service=, fan-out client without
        assert isinstance(registry_client_for(cli, engines[0].uri),
                          RegistryClient)
        assert isinstance(registry_client_for(cli, spec),
                          ShardedRegistryClient)
        bound = registry_client_for(cli, spec, service=svc)
        assert isinstance(bound, RegistryClient)
        assert bound.uris == [engines[1].uri]
    finally:
        if pool:
            pool.close()
        if inst:
            inst.close()
        for r in regs:
            r.close()
        for e in engines + [cli, worker]:
            e.shutdown()


def _resolve_counter(engine):
    """Count server-side fab.resolve executions on ``engine``."""
    rec = engine.hg._by_name["fab.resolve"]
    inner = rec.handler
    hits = [0]

    def counting(arg):
        hits[0] += 1
        return inner(arg)

    rec.handler = counting
    return hits


def test_per_shard_tokens_restart_evicts_only_that_shard():
    """A restart (fresh nonce) on shard 1 must evict shard 1's cached
    reads only: shard 0 keeps serving from cache with zero round-trips,
    shard 1 refuses to serve the superseded epoch stream (§12 token
    rules: never compare epochs across shards)."""
    engines, regs, spec = _mk_shards(2, instance_ttl=30.0)
    cli = Engine("tcp://127.0.0.1:0")
    try:
        c = ShardedRegistryClient(cli, spec, timeout=5.0, cache_ttl=30.0)
        svc0, svc1 = _owned_by(c, 0, "tok"), _owned_by(c, 1, "tok")
        c.register(svc0, ["tcp://10.0.0.1:1"])
        c.register(svc1, ["tcp://10.0.0.1:2"])
        hits0 = _resolve_counter(engines[0])
        assert len(c.resolve(svc0)["instances"]) == 1    # fill shard-0 cache
        assert len(c.resolve(svc1)["instances"]) == 1    # fill shard-1 cache
        assert c.resolve(svc0)["instances"] and hits0[0] == 1
        tok0 = c.clients[0].cache.token()
        tok1 = c.clients[1].cache.token()
        assert tok0[0] != tok1[0]                        # independent nonces

        # shard 1 restarts cold on the same address: new core nonce,
        # empty table, epochs restart — the classic stale-token trap
        uri1 = engines[1].uri
        regs[1].close()
        engines[1].shutdown()
        engines[1] = Engine(uri1)
        regs[1] = RegistryService(engines[1], sweep_interval=0.1,
                                  instance_ttl=30.0)

        # an authoritative shard-1 read reconnects, sees the fresh
        # nonce and evicts — after which even plain (cache-eligible)
        # reads serve the new empty authority, never the cached ghost
        view1 = poll_until(
            lambda: _try_resolve(c, svc1, fresh=True), timeout=10.0,
            msg="shard-1 client reconnect")
        assert view1["instances"] == []
        assert c.resolve(svc1)["instances"] == []
        assert c.clients[1].cache.token()[0] != tok1[0]
        # shard 0 was untouched: same token, still cache-served
        assert c.clients[0].cache.token() == tok0
        assert len(c.resolve(svc0)["instances"]) == 1
        assert hits0[0] == 1, "shard-1 restart cross-evicted shard 0"
    finally:
        for r in regs:
            r.close()
        for e in engines + [cli]:
            e.shutdown()


def _try_resolve(client, service, fresh=False):
    try:
        return client.resolve(service, fresh=fresh)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# launcher: co-hosted shards
# ---------------------------------------------------------------------------
def test_launch_registry_cohosts_shards():
    import socket
    socks = []
    try:
        for _ in range(4):   # grab a base with base+1 free alongside
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        base = max(s.getsockname()[1] for s in socks) + 7
    finally:
        for s in socks:
            s.close()
    env = dict(os.environ, PYTHONPATH=_SRC)
    p = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.registry",
         "--listen", f"tcp://127.0.0.1:{base}", "--shards", "2",
         "--instance-ttl", "30"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    cli = Engine("tcp://127.0.0.1:0")
    try:
        spec = f"tcp://127.0.0.1:{base}|tcp://127.0.0.1:{base + 1}"
        c = ShardedRegistryClient(cli, spec, timeout=2.0)
        for shard in c.clients:
            poll_until(lambda s=shard: _reachable(s), timeout=15.0,
                       msg="co-hosted shard up")
        svc0, svc1 = _owned_by(c, 0, "co"), _owned_by(c, 1, "co")
        c.register(svc0, ["tcp://10.0.0.1:1"])
        c.register(svc1, ["tcp://10.0.0.1:2"])
        assert c.services() == sorted([svc0, svc1])
        infos = c.epoch_info(fresh=True)
        assert infos[0][1] != infos[1][1]
    finally:
        cli.shutdown()
        p.terminate()
        p.wait(timeout=10)


def _reachable(client):
    try:
        client.epoch(fresh=True)
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# chaos: shard-isolated failover
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_shard0_leaseholder_kill_is_invisible_to_shard1():
    """Kill shard 0's leaseholder under concurrent register/resolve
    load on both shards: shard 1 sees ZERO write or resolve errors and
    keeps making progress during the outage; shard 0 elects a new
    leaseholder within ~one lease TTL and heals (extends the PR-4/5
    failover tests to the sharded topology)."""
    shard_engines, shard_regs = [], []
    for _ in range(2):                       # two 3-replica quorums
        engines = [Engine("tcp://127.0.0.1:0") for _ in range(3)]
        peers = [e.uri for e in engines]
        regs = [RegistryService(e, peers=peers, lease_ttl=LEASE,
                                gossip_interval=GOSSIP, sweep_interval=0.1,
                                instance_ttl=30.0)
                for e in engines]
        shard_engines.append(engines)
        shard_regs.append(regs)
    spec = "|".join(",".join(e.uri for e in engines)
                    for engines in shard_engines)
    cli = Engine("tcp://127.0.0.1:0")
    stop = threading.Event()
    threads = []
    try:
        for regs in shard_regs:
            poll_until(lambda r=regs: r[0].is_leader,
                       msg="initial shard leadership")
        probe = ShardedRegistryClient(cli, spec, timeout=5.0)
        svc = [_owned_by(probe, k, "chaos") for k in range(2)]

        errors = {0: [], 1: []}
        progress = {0: [0], 1: [0]}
        lock = threading.Lock()

        def drive(shard):
            c = ShardedRegistryClient(cli, spec, timeout=5.0)
            i = 0
            while not stop.is_set():
                try:
                    c.register(svc[shard], [f"tcp://10.0.0.1:{i}"],
                               iid=f"i{shard}-{i % 8}")
                    c.resolve(svc[shard], fresh=True)
                    with lock:
                        progress[shard][0] += 1
                except Exception as e:  # noqa: BLE001 — the assertion
                    with lock:
                        errors[shard].append(repr(e))
                i += 1

        threads = [threading.Thread(target=drive, args=(k,), daemon=True)
                   for k in (0, 0, 1, 1)]
        for t in threads:
            t.start()
        poll_until(lambda: progress[0][0] > 5 and progress[1][0] > 5,
                   msg="drivers warmed up on both shards")

        # abrupt leaseholder kill on shard 0 (no deregistration: peers
        # learn via lease expiry only)
        regs0, engines0 = shard_regs[0], shard_engines[0]
        leader = next(i for i, r in enumerate(regs0) if r.is_leader)
        base1 = progress[1][0]
        regs0[leader].close()
        engines0[leader].shutdown()
        t_kill = time.monotonic()

        survivor = regs0[(leader + 1) % 3], regs0[(leader + 2) % 3]
        poll_until(lambda: any(r.is_leader for r in survivor),
                   timeout=LEASE + 2.0, msg="shard-0 lease takeover")
        takeover_s = time.monotonic() - t_kill
        # shard 1 kept working *during* the shard-0 outage
        poll_until(lambda: progress[1][0] > base1 + 5,
                   msg="shard-1 progress during shard-0 outage")
        # shard 0 heals: writes land on the new leaseholder
        poll_until(lambda: not errors[1] and _chaos_write_ok(cli, spec,
                                                             svc[0]),
                   timeout=LEASE + 3.0, msg="shard-0 post-takeover write")
        stop.set()
        for t in threads:
            t.join(timeout=10.0)

        assert errors[1] == [], \
            f"shard-0 kill leaked {len(errors[1])} errors into shard 1: " \
            f"{errors[1][:3]}"
        # "within one lease TTL" + scheduling slack (same bound as the
        # unsharded PR-4/5 failover tests use)
        assert takeover_s < LEASE + 2.0, \
            f"shard-0 takeover took {takeover_s:.2f}s"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
        for regs in shard_regs:
            for r in regs:
                r.close()
        for engines in shard_engines:
            for e in engines:
                try:
                    e.shutdown()
                except Exception:
                    pass
        cli.shutdown()


def _chaos_write_ok(cli, spec, service):
    try:
        c = ShardedRegistryClient(cli, spec, timeout=2.0)
        c.register(service, ["tcp://10.0.0.1:999"], iid="post-kill")
        return len(c.resolve(service, fresh=True)["instances"]) > 0
    except Exception:
        return False
