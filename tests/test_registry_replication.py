"""Replicated control plane (DESIGN.md §8): deterministic leader lease,
delta-gossip replication to followers (with full-snapshot fallback),
follower write proxying, client endpoint failover, leaseholder kill
mid-run (pools converge to a survivor within one refresh interval with
zero client-visible resolution errors), restart resync (a restarted
replica adopts the acting leader's snapshot before it may reclaim the
lease), and the membership plane folded into the registry quorum
(members survive leaseholder death; expiry reaps fire exactly once from
the new leader)."""
import threading
import time

import pytest

from conftest import poll_until
from repro.core.executor import Engine
from repro.core.types import MercuryError, Ret
from repro.fabric import (PeerTracker, RegistryClient, RegistryService,
                          ReplicatedTable, RetryPolicy, ServiceInstance,
                          ServicePool, parse_registry_uris)
from repro.services import MembershipClient, MembershipServer

LEASE = 0.5
GOSSIP = 0.12


def _wait(pred, timeout=8.0, interval=0.03, msg="condition"):
    poll_until(pred, timeout=timeout, interval=interval, msg=msg)


def _mk_cluster(n=3, instance_ttl=5.0):
    engines = [Engine("tcp://127.0.0.1:0") for _ in range(n)]
    peers = [e.uri for e in engines]
    regs = [RegistryService(e, peers=peers, lease_ttl=LEASE,
                            gossip_interval=GOSSIP, sweep_interval=0.1,
                            instance_ttl=instance_ttl)
            for e in engines]
    return engines, peers, regs


@pytest.fixture
def cluster():
    engines, peers, regs = _mk_cluster()
    # cold start: rank 0 self-elects after its boot grace (one lease)
    _wait(lambda: regs[0].is_leader, msg="rank-0 leadership")
    yield engines, peers, regs
    for r in regs:
        r.close()
    for e in engines:
        try:
            e.shutdown()
        except Exception:
            pass


def _echo_engine(name):
    e = Engine("tcp://127.0.0.1:0")
    e.register("echo", lambda x, _n=name: (_n, x))
    return e


# ---------------------------------------------------------------------------
# lease bookkeeping (pure)
# ---------------------------------------------------------------------------
def test_peer_tracker_lease_and_grace():
    t = [0.0]
    tr = PeerTracker(["a", "b", "c"], "b", lease_ttl=1.0,
                     clock=lambda: t[0])
    # boot grace: a (optimistically alive) leads; self is deferred
    assert tr.in_grace() and tr.leader_uri() == "a"
    t[0] = 1.5                      # grace over, a's lease expired
    assert not tr.in_grace()
    assert tr.leader_uri() == "b"   # we are the best live peer
    tr.note("a")                    # a came back
    assert tr.leader_uri() == "a"
    t[0] = 3.0                      # a silent past the lease again
    assert tr.leader_uri() == "b"
    stats = {p["uri"]: p for p in tr.peer_stats()}
    assert stats["b"]["self"] and not stats["a"]["alive"]


def test_peer_tracker_grace_with_all_peers_dead():
    t = [0.0]
    tr = PeerTracker(["a", "b"], "a", lease_ttl=1.0, clock=lambda: t[0])
    t[0] = 0.5
    # in grace, nobody heard, self deferred: leadership unknowable
    assert tr.leader_uri() == "b"   # b still within its optimistic lease
    tr.mark_synced()                # adopted a snapshot: grace over early
    assert tr.leader_uri() == "a"


def test_parse_registry_uris_rejects_empty():
    with pytest.raises(ValueError):
        parse_registry_uris("  , ,")
    assert parse_registry_uris("a;b,c") == ["a;b", "c"]


# ---------------------------------------------------------------------------
# gossip replication
# ---------------------------------------------------------------------------
def test_cluster_elects_lowest_rank_and_agrees(cluster):
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        for uri in peers:
            st = cli.call(uri, "fab.status", {}, timeout=5.0)
            assert st["leader"] == peers[0], st
        assert regs[0].is_leader
        assert not regs[1].is_leader and not regs[2].is_leader
        roles = [cli.call(u, "fab.status", {}, timeout=5.0)["role"]
                 for u in peers]
        assert roles == ["leader", "follower", "follower"]


def test_register_replicates_to_follower_reads(cluster):
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        lead = RegistryClient(cli, peers[0])
        iid = lead.register("svc", "tcp://127.0.0.1:1111", capacity=4)
        # followers serve the mirrored view (reads never proxy)
        for uri in peers[1:]:
            follower = RegistryClient(cli, uri)
            _wait(lambda f=follower: [i["iid"] for i in
                                      f.resolve("svc")["instances"]] == [iid],
                  msg="gossip replication to follower")
            e, n = follower.epoch_info()
            le, ln = lead.epoch_info()
            assert (e, n) == (le, ln)   # same stream: nonce + epoch match


def test_follower_proxies_writes_to_leaseholder(cluster):
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        fol = RegistryClient(cli, peers[2])      # follower endpoint only
        iid = fol.register("svc", "tcp://127.0.0.1:2222", capacity=1)
        # the write landed on the leader's authoritative table
        assert any(i["iid"] == iid for i in
                   RegistryClient(cli, peers[0]).resolve("svc")["instances"])
        # load reports proxy too, and application errors pass through:
        fol.report("svc", iid, load=3.0)
        with pytest.raises(MercuryError) as ei:
            fol.report("svc", "nonexistent-iid", load=0.0)
        assert ei.value.ret == Ret.NOENTRY
        assert fol.deregister("svc", iid)


def test_registry_client_rotates_past_dead_endpoint(cluster):
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        dead = "tcp://127.0.0.1:1"               # nothing listens there
        c = RegistryClient(cli, [dead] + peers, timeout=5.0)
        iid = c.register("svc", "tcp://127.0.0.1:3333")
        assert c.resolve("svc")["instances"][0]["iid"] == iid
        # sticky: after one failover the live endpoint is preferred
        assert c.registry != dead


def test_registration_during_cold_boot_succeeds():
    """A write racing the quorum's cold start (every replica still in
    boot grace → AGAIN everywhere) must succeed once the lease settles:
    RegistryClient re-probes within its timeout budget instead of
    surfacing the transient — real launchers can't spin on is_leader."""
    engines, peers, regs = _mk_cluster()
    try:
        with Engine("tcp://127.0.0.1:0") as cli:
            c = RegistryClient(cli, peers, timeout=8.0)
            iid = c.register("svc", "tcp://127.0.0.1:6666")   # no wait
            # the sticky client may read a FOLLOWER's mirror, which is
            # documented to lag the proxied write by ≤ one gossip round
            _wait(lambda: [i["iid"] for i in
                           c.resolve("svc")["instances"]] == [iid],
                  msg="registration visible after cold boot")
    finally:
        for r in regs:
            r.close()
        for e in engines:
            e.shutdown()


def test_follower_hosted_membership_reaps_via_leader(cluster):
    """A MembershipServer co-hosted on a FOLLOWER node: its expiries are
    resolved against the follower's mirror and forwarded to the
    leaseholder as deregisters — the member-bound instance dies with its
    member even though it keeps reporting."""
    engines, peers, regs = cluster
    ms = MembershipServer(engines[2], heartbeat_timeout=0.4,
                          sweep_interval=0.1)
    ms.on_expire(regs[2]._members_expired)
    with Engine("tcp://127.0.0.1:0") as w:
        cli = RegistryClient(w, peers)
        w.call(peers[2], "mem.join", {"member_id": "w1", "uri": w.uri})
        iid = cli.register("svc", w.uri, member_id="w1")
        # member w1 never heartbeats; the instance DOES keep reporting,
        # so only the (forwarded) member-expiry path can remove it
        def _reaped():
            try:
                cli.report("svc", iid, load=0.0)
                return False
            except MercuryError as e:
                return e.ret == Ret.NOENTRY
        _wait(_reaped, interval=0.05,
              msg="member-bound instance reaped with its member")
        assert cli.resolve("svc")["instances"] == []
    ms.close()


# ---------------------------------------------------------------------------
# leaseholder kill mid-run (the ISSUE acceptance scenario)
# ---------------------------------------------------------------------------
def test_leader_kill_pools_converge_with_zero_resolution_errors(cluster):
    """Kill the leaseholder under routed load: every pool call keeps
    succeeding (client endpoint failover + follower read-serving), the
    next-ranked replica takes the lease, and the pool's view resyncs
    onto the survivor's fresh stream within one refresh interval."""
    engines, peers, regs = cluster
    srv_a, srv_b = _echo_engine("a"), _echo_engine("b")
    with srv_a, srv_b, Engine("tcp://127.0.0.1:0") as cli:
        insts = [ServiceInstance(s, peers, "svc", capacity=4,
                                 report_interval=0.1)
                 for s in (srv_a, srv_b)]
        refresh = 0.2
        pool = ServicePool(cli, peers, "svc", refresh_interval=refresh,
                           policy=RetryPolicy(attempts=3, rpc_timeout=2.0,
                                              backoff_base=0.01))
        assert len(pool.replicas()) == 2
        errors, stop = [], threading.Event()
        ok = [0]

        def drive():
            i = 0
            while not stop.is_set():
                try:
                    pool.call("echo", i, timeout=5.0)
                    ok[0] += 1           # int += is GIL-atomic enough here
                except Exception as e:   # noqa: BLE001 — surfaced below
                    errors.append(repr(e))
                i += 1

        # daemons: a failed assertion above must not leave live driver
        # threads blocking interpreter exit (that reads as a CI hang)
        threads = [threading.Thread(target=drive, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        _wait(lambda: ok[0] >= 20, msg="drivers routing before the kill")

        regs[0].close()                  # kill the leaseholder abruptly
        engines[0].shutdown()
        t_kill = time.monotonic()

        # pools fail over to a surviving replica within ~one refresh
        # interval: the control plane answers again immediately
        _wait(lambda: pool.registry.epoch_info() is not None,
              timeout=refresh + 2.0, msg="client failover")
        # the lease moves to the next-ranked survivor...
        _wait(lambda: regs[1].is_leader, msg="rank-1 takeover")
        takeover_s = time.monotonic() - t_kill
        # ...and the pool resyncs onto the new stream (nonce change).
        # The survivor's nonce is read inside the predicate: a lease
        # flap around the kill can mint a transient stream that is
        # replaced by the post-kill takeover — comparing against a
        # one-shot capture would wait on a nonce that no longer exists.
        _wait(lambda: (pool.refresh(force=True) or
                       (regs[1].is_leader
                        and pool._view_nonce == regs[1].nonce)),
              msg="pool resync onto survivor stream")
        resynced = ok[0]                 # keep routing on the new stream
        _wait(lambda: ok[0] >= resynced + 10,
              msg="routed calls succeeding on the new stream")
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, f"client-visible failures: {errors[:3]}"
        assert takeover_s < LEASE + 2.0
        # registrations survived the failover (mirror promoted, not lost)
        assert len(pool.replicas()) == 2
        for inst in insts:
            inst.close()


# ---------------------------------------------------------------------------
# restart resync
# ---------------------------------------------------------------------------
def test_restarted_follower_resyncs_from_leader(cluster):
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        RegistryClient(cli, peers[0]).register("svc", "tcp://127.0.0.1:4444")
        port = int(peers[2].rsplit(":", 1)[1])
        regs[2].close()
        engines[2].shutdown()
        # restart rank 2 on the same configured uri: empty table, boot
        # grace, adopts the acting leader's snapshot
        engines[2] = Engine(f"tcp://127.0.0.1:{port}")
        regs[2] = RegistryService(engines[2], peers=peers, lease_ttl=LEASE,
                                  gossip_interval=GOSSIP,
                                  sweep_interval=0.1, instance_ttl=5.0)
        fol = RegistryClient(cli, peers[2])
        _wait(lambda: fol.resolve("svc")["instances"],
              msg="restarted follower resync")
        assert fol.epoch_info() == RegistryClient(cli,
                                                  peers[0]).epoch_info()
        assert not regs[2].is_leader


def test_restarted_leader_resyncs_before_reclaiming_lease(cluster):
    """Kill rank 0; rank 1 takes over and keeps accepting writes.  A
    restarted rank 0 must adopt rank 1's snapshot BEFORE reclaiming the
    lease — registrations written during its absence survive."""
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        port = int(peers[0].rsplit(":", 1)[1])
        regs[0].close()
        engines[0].shutdown()
        _wait(lambda: regs[1].is_leader, msg="rank-1 takeover")
        # a write accepted by the acting leader while rank 0 is down
        iid = RegistryClient(cli, peers[1:]).register(
            "svc", "tcp://127.0.0.1:5555", capacity=2)

        engines[0] = Engine(f"tcp://127.0.0.1:{port}")
        regs[0] = RegistryService(engines[0], peers=peers, lease_ttl=LEASE,
                                  gossip_interval=GOSSIP,
                                  sweep_interval=0.1, instance_ttl=5.0)
        # rank 0 resyncs, then reclaims the lease; rank 1 steps down
        _wait(lambda: regs[0].is_leader, msg="rank-0 reclaim")
        _wait(lambda: not regs[1].is_leader, msg="rank-1 step-down")
        view = RegistryClient(cli, peers[0]).resolve("svc")
        assert [i["iid"] for i in view["instances"]] == [iid], \
            "write during the leader's absence was lost"
        # all replicas converge onto the reclaimed stream
        for uri in peers:
            _wait(lambda u=uri: (RegistryClient(cli, u).epoch_info()
                                 == (regs[0].epoch, regs[0].nonce)),
                  msg="stream convergence after reclaim")


# ---------------------------------------------------------------------------
# client read cache vs the replicated control plane (DESIGN.md §9)
# ---------------------------------------------------------------------------
def test_read_cache_invalidation_across_failover(cluster):
    """Each invalidation trigger — epoch bump, TTL expiry, nonce change
    (leaseholder failover) — must evict the client read cache, and no
    read after the failover may be served from the dead leader's epoch
    stream."""
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli, \
            Engine("tcp://127.0.0.1:0") as cli2:
        # 60s TTL: within this test only *token* invalidation can evict
        cached = RegistryClient(cli, peers, cache_ttl=60.0)
        writer = RegistryClient(cli2, peers)   # its writes are invisible
                                               # to `cached`'s token
        iids = ["aaaaaaaaaaaa", "bbbbbbbbbbbb", "cccccccccccc"]
        writer.register("svc", "tcp://127.0.0.1:4441", iid=iids[0])
        _wait(lambda: cached.resolve("svc", fresh=True)["instances"],
              msg="initial view")
        assert len(cached.resolve("svc")["instances"]) == 1   # cached now

        def keepalive(known):
            # same-iid/same-uris re-register refreshes the instance TTL
            # stamp without bumping the epoch (see RegistryService)
            for i, iid in enumerate(iids[:known]):
                writer.register("svc", f"tcp://127.0.0.1:444{i + 1}",
                                iid=iid)

        # --- epoch bump (another client's write) evicts via the poll
        writer.register("svc", "tcp://127.0.0.1:4442", iid=iids[1])

        def sees_two():
            keepalive(2)
            cached.epoch_info(fresh=True)      # observe the authority
            return len(cached.resolve("svc")["instances"]) == 2

        _wait(sees_two, msg="epoch-bump eviction")

        # --- TTL expiry evicts with no token feed at all
        short = RegistryClient(cli, peers, cache_ttl=0.15)
        _wait(lambda: len(short.resolve("svc", fresh=True)["instances"]) == 2,
              msg="short-ttl warm view")
        writer.register("svc", "tcp://127.0.0.1:4443", iid=iids[2])

        def ttl_sees_three():
            keepalive(3)
            # no fresh=, no observe: only TTL lapse explains a refetch
            return len(short.resolve("svc")["instances"]) == 3
        _wait(ttl_sees_three, msg="TTL-expiry eviction")

        # --- leaseholder kill: nonce change must evict, and no read
        # may come from the dead leader's stream afterwards
        regs[0].close()
        engines[0].shutdown()
        _wait(lambda: regs[1].is_leader, msg="rank-1 takeover")

        def resynced():
            try:
                keepalive(3)
                _, nonce = cached.epoch_info(fresh=True)
            except MercuryError:
                return False                   # failing over between replicas
            if nonce != regs[1].nonce:
                return False                   # survivors still converging
            view = cached.resolve("svc")       # served under the new token
            return (view.get("nonce") == regs[1].nonce
                    and len(view["instances"]) == 3)

        _wait(resynced, msg="cache resync onto survivor stream")
        # the cache token itself moved onto the survivor's stream, and
        # every cached entry from the dead leader's nonce is gone
        assert cached.cache.stats()["token"]["nonce"] == regs[1].nonce
        assert cached.resolve("svc")["nonce"] == regs[1].nonce


# ---------------------------------------------------------------------------
# ReplicatedTable: version stamps, deltas, tombstone horizon (pure)
# ---------------------------------------------------------------------------
def test_replicated_table_delta_roundtrip():
    lock = threading.RLock()
    leader = ReplicatedTable("t", lock, tombstone_ttl=60.0)
    mirror = ReplicatedTable("t", threading.RLock(), tombstone_ttl=60.0)
    now = time.monotonic()
    leader.put("a", {"x": 1})
    leader.put("b", {"x": 2})
    mirror.install(leader.snapshot(now), now)
    assert (mirror.epoch, len(mirror)) == (2, 2)

    base = mirror.epoch
    leader.put("c", {"x": 3})
    leader.delete("a")
    # soft update: no epoch bump, rides the soft channel only
    assert leader.update("b", x=20)
    assert leader.epoch == 4
    d = leader.delta_since(base, now)
    assert [e["k"] for e in d["put"]] == ["c"]
    assert d["del"] == [["a", 4]]
    assert mirror.apply_delta(d, now)
    mirror.apply_soft(leader.take_soft(now), now)
    assert mirror.epoch == 4
    assert mirror.get("a") is None
    assert mirror.get("b")["x"] == 20 and mirror.get("c")["x"] == 3
    # idle: nothing to ship (heartbeats with unchanged values are free)
    assert leader.update("b", x=20) and leader.take_soft(now) == []
    d2 = leader.delta_since(mirror.epoch, now)
    assert d2["put"] == [] and d2["del"] == []


def test_replicated_table_horizon_forces_snapshot():
    t = ReplicatedTable("t", threading.RLock(), tombstone_ttl=0.05)
    t.put("a", {"x": 1})
    t.put("b", {"x": 2})
    base = t.epoch
    t.delete("a")
    now = time.monotonic()
    assert t.delta_since(base, now)["del"] == [["a", 3]]
    # tombstone GC'd once its TTL passes: the horizon moves and the
    # behind-horizon delta must force a snapshot
    poll_until(lambda: t.delta_since(base, time.monotonic()) is None,
               timeout=2.0, interval=0.01, msg="tombstone horizon move")
    now = time.monotonic()
    assert t.delta_since(t.epoch, now) is not None   # at-horizon is fine
    # a gapped delta (base past the mirror's epoch) is refused
    m = ReplicatedTable("t", threading.RLock())
    assert not m.apply_delta({"base": 7, "epoch": 9, "put": [], "del": []},
                             now)


# ---------------------------------------------------------------------------
# membership folded into the quorum
# ---------------------------------------------------------------------------
def _mk_member_cluster(n=3, heartbeat_timeout=0.6):
    engines = [Engine("tcp://127.0.0.1:0") for _ in range(n)]
    peers = [e.uri for e in engines]
    regs = [RegistryService(e, peers=peers, lease_ttl=LEASE,
                            gossip_interval=GOSSIP, sweep_interval=0.1,
                            instance_ttl=5.0, serve_membership=True,
                            heartbeat_timeout=heartbeat_timeout)
            for e in engines]
    return engines, peers, regs


@pytest.fixture
def member_cluster():
    engines, peers, regs = _mk_member_cluster()
    _wait(lambda: regs[0].is_leader, msg="rank-0 leadership")
    yield engines, peers, regs
    for r in regs:
        r.close()
    for e in engines:
        try:
            e.shutdown()
        except Exception:
            pass


def test_membership_served_by_quorum(member_cluster):
    """mem.* wire API against the quorum: joins land on the leader's
    replicated member table (proxied from a follower endpoint), views
    are served by followers from their mirror, and the member table
    shares the instance table's gossip stream (same nonce)."""
    engines, peers, regs = member_cluster
    with Engine("tcp://127.0.0.1:0") as w:
        # write via a FOLLOWER endpoint: proxied one hop to the leader
        view = w.call(peers[2], "mem.join",
                      {"member_id": "m1", "uri": w.uri,
                       "meta": {"role": "trainer"}}, timeout=5.0)
        assert view["members"] == ["m1"]
        assert regs[0].membership.table.get("m1")["meta"] == \
            {"role": "trainer"}
        # follower-served reads: the mirror carries the member
        for i in (1, 2):
            _wait(lambda i=i: (engines and regs[i].membership.table
                               .get("m1") is not None),
                  msg=f"member replication to follower {i}")
            v = w.call(peers[i], "mem.view", {}, timeout=5.0)
            assert v["members"] == ["m1"]
            assert v["nonce"] == regs[0].nonce
        # heartbeat via a follower refreshes the leader's stamp (retry
        # until the clock has visibly advanced past the join stamp)
        before = regs[0].membership.table.get("m1")["last"]

        def _refreshed():
            w.call(peers[1], "mem.heartbeat",
                   {"member_id": "m1", "uri": w.uri}, timeout=5.0)
            return regs[0].membership.table.get("m1")["last"] > before
        _wait(_refreshed, interval=0.02,
              msg="follower-proxied heartbeat refreshing leader stamp")


@pytest.mark.slow
def test_leaseholder_kill_members_survive_reaps_fire_once(member_cluster):
    """The ISSUE acceptance scenario: kill the leaseholder under active
    member heartbeats.  Heartbeating members are never mass-expired on
    takeover; a member that stopped heartbeating before the kill is
    expired by the NEW leader, its on_expire reap fires exactly once
    (and only there), and its bound instance is reaped from the
    replicated instance table."""
    engines, peers, regs = member_cluster
    fires = []
    for i, r in enumerate(regs):
        r.membership.on_expire(
            lambda dead, i=i: fires.append((i, sorted(dead))))
    with Engine("tcp://127.0.0.1:0") as w:
        cli = RegistryClient(w, peers)
        live = MembershipClient(w, peers, "live", 0.1)
        live.join({"zone": "a"})
        # "doomed" joins but never heartbeats; an instance is bound to it
        w.call(peers[0], "mem.join", {"member_id": "doomed",
                                      "uri": "tcp://x"}, timeout=5.0)
        iid = cli.register("svc", "tcp://127.0.0.1:7777",
                           member_id="doomed")
        _wait(lambda: regs[1].membership.table.get("doomed") is not None,
              msg="member replication before the kill")

        regs[0].close()                   # kill the leaseholder abruptly
        engines[0].shutdown()
        _wait(lambda: regs[1].is_leader, msg="rank-1 takeover")
        # doomed expires on the NEW leader (takeover refreshed liveness,
        # so expiry lands one heartbeat_timeout after takeover, not 0)
        _wait(lambda: any("doomed" in d for _, d in fires),
              msg="expiry reap from the new leader")
        # ...and its bound instance is reaped from the instance table
        _wait(lambda: cli.resolve("svc")["instances"] == [],
              msg="member-bound instance reap after failover")
        # observation window (not a wait): absence of duplicate fires
        # can only be asserted over elapsed sweep periods
        time.sleep(3 * 0.6)
        doomed_fires = [(i, d) for i, d in fires if "doomed" in d]
        assert len(doomed_fires) == 1, f"reap fired {doomed_fires}"
        assert doomed_fires[0][0] == 1, "reap must fire on the new leader"
        # the heartbeating member was never expired anywhere
        assert not any("live" in d for _, d in fires), \
            f"heartbeating member mass-expired: {fires}"
        assert "live" in live.current_view()["members"]
        live.leave()


def test_membership_nonce_resync_in_driver_path(member_cluster):
    """The training-driver path across a control-plane failover: the
    MembershipClient's on_change fires on the nonce change (epochs are
    only comparable within one stream), the view still carries every
    live member, and heartbeats keep landing via the survivors."""
    engines, peers, regs = member_cluster
    changes = []
    with Engine("tcp://127.0.0.1:0") as w:
        c = MembershipClient(w, peers, "trainer-0", 0.1,
                             on_change=lambda v: changes.append(dict(v)))
        first = c.join({"role": "trainer"})
        nonce0 = first["nonce"]
        assert nonce0 == regs[0].nonce
        regs[0].close()
        engines[0].shutdown()
        _wait(lambda: regs[1].is_leader, msg="rank-1 takeover")
        _wait(lambda: any(v.get("nonce") not in (None, nonce0)
                          for v in changes),
              msg="driver observes the nonce change")
        resynced = next(v for v in changes
                        if v.get("nonce") not in (None, nonce0))
        assert "trainer-0" in resynced["members"], \
            "member lost across failover"
        c.leave()


def test_behind_horizon_follower_resynced_by_snapshot():
    """A follower whose acked epoch predates the leader's tombstone
    horizon cannot be caught up by delta (deletions were GC'd): the
    leader must fall back to a full snapshot, after which the follower
    converges again."""
    # private cluster: a huge instance TTL so the reporter-less test
    # instance can never be expired while the assertions run
    engines, peers, regs = _mk_cluster(instance_ttl=3600.0)
    try:
        _wait(lambda: regs[0].is_leader, msg="rank-0 leadership")
        with Engine("tcp://127.0.0.1:0") as cli:
            lead = RegistryClient(cli, peers[0])
            iid = lead.register("svc", "tcp://127.0.0.1:8888")
            _wait(lambda: regs[1].epoch == regs[0].epoch,
                  msg="initial convergence")
            # churn through registrations whose tombstones are GC'd at
            # once (their deletion history is gone immediately)
            regs[0].table.tombstone_ttl = 0.0
            for i in range(3):
                tmp = lead.register("tmp", f"tcp://127.0.0.1:{9100 + i}")
                lead.deregister("tmp", tmp)
            with regs[0].core._lock:
                snaps_before = regs[0].core.stats["snapshot_pushes"]

            # force a pre-horizon ack for peer 1 until a leader tick
            # consumes it (the follower's own heartbeats race us and may
            # re-ack the true epoch in between): that tick must take the
            # snapshot path, not the delta path
            def forced_snapshot_pushed():
                with regs[0].core._lock:
                    if (regs[0].core.stats["snapshot_pushes"]
                            > snaps_before):
                        return True
                    regs[0].core._acks[peers[1]] = {
                        "nonce": regs[0].nonce,
                        "epochs": {"instances": 0}}
                    return False

            _wait(forced_snapshot_pushed, msg="snapshot fallback push")
            _wait(lambda: (regs[1].epoch, regs[1].nonce)
                  == (regs[0].epoch, regs[0].nonce),
                  msg="follower reconvergence after snapshot")
            view = RegistryClient(cli, peers[1]).resolve("svc")
            assert [i_["iid"] for i_ in view["instances"]] == [iid]
            assert RegistryClient(cli, peers[1]).resolve("tmp")[
                "instances"] == []
    finally:
        for r in regs:
            r.close()
        for e in engines:
            try:
                e.shutdown()
            except Exception:
                pass


@pytest.mark.slow
def test_idle_quorum_gossips_heartbeats_not_state(cluster):
    """Delta gossip's reason to exist: an idle quorum (registered
    instances, no churn) must exchange bare heartbeats — zero delta or
    snapshot pushes — instead of shipping the table every round."""
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        lead = RegistryClient(cli, peers[0])
        for i in range(10):
            lead.register("svc", f"tcp://127.0.0.1:{9300 + i}")
        _wait(lambda: all(r.epoch == regs[0].epoch for r in regs),
              msg="convergence")
        # measure over gossip ROUNDS, not wall time: wait out 3 rounds
        # to drain in-flight pushes, then observe a 10-round window
        drained = regs[0].core.stats["rounds"] + 3
        _wait(lambda: regs[0].core.stats["rounds"] >= drained,
              msg="in-flight gossip drained")
        s0 = dict(regs[0].core.stats)
        _wait(lambda: regs[0].core.stats["rounds"] >= s0["rounds"] + 10,
              msg="10-round idle window")
        s1 = dict(regs[0].core.stats)
        assert s1["rounds"] > s0["rounds"]
        assert s1["delta_pushes"] == s0["delta_pushes"]
        assert s1["snapshot_pushes"] == s0["snapshot_pushes"]
        assert s1["heartbeat_pushes"] > s0["heartbeat_pushes"]


def test_fab_status_reports_tables_gossip_and_acks(cluster):
    """fab.status (docs/OPERATIONS.md): per-table entry counts/epochs,
    delta-vs-snapshot gossip counters, and per-peer acked replication
    state."""
    engines, peers, regs = cluster
    with Engine("tcp://127.0.0.1:0") as cli:
        lead = RegistryClient(cli, peers[0])
        lead.register("svc", "tcp://127.0.0.1:9500")
        _wait(lambda: regs[1].epoch == regs[0].epoch, msg="convergence")
        st = lead.status()
        assert st["role"] == "leader"
        assert st["tables"]["instances"]["entries"] == 1
        assert st["tables"]["instances"]["epoch"] == regs[0].epoch
        g = st["gossip"]
        assert g["rounds"] > 0
        assert g["delta_pushes"] + g["snapshot_pushes"] \
            + g["pull_snapshots"] + g["pull_deltas"] > 0
        _wait(lambda: any(
            p.get("acked", {}).get("instances") == regs[0].epoch
            for p in lead.status()["peers"]),
            msg="peer acks visible in fab.status")
        acked = [p for p in lead.status()["peers"] if "acked" in p]
        assert acked and all("acked_nonce" in p for p in acked)
        # follower status: mirrored tables, role, and the same stream
        fst = RegistryClient(cli, peers[1]).status()
        assert fst["role"] == "follower"
        assert fst["nonce"] == st["nonce"]


def test_register_member_rebind_is_versioned():
    """A same-uris re-register that changes the member binding is a
    membership change: it must bump the epoch (ride the versioned,
    retransmitted stream), while a same-everything re-register (the
    report-loop recovery path) must not."""
    with Engine("tcp://127.0.0.1:0") as e, \
            Engine("tcp://127.0.0.1:0") as w:
        svc = RegistryService(e, sweep_interval=0.1, instance_ttl=5.0)
        cli = RegistryClient(w, e.uri)
        iid = cli.register("svc", w.uri, member_id="a")
        e1 = cli.epoch()
        cli.register("svc", w.uri, iid=iid, member_id="a")   # recovery
        assert cli.epoch() == e1, "same-everything re-register bumped"
        cli.register("svc", w.uri, iid=iid, member_id="b")   # rebind
        assert cli.epoch() == e1 + 1, "member rebind must be versioned"
        assert svc.table.get(f"svc\x1f{iid}")["member_id"] == "b"
        svc.close()
