"""NA conformance matrix — one suite, every plugin.

The paper's C1 claim is that the NA contract is plugin-agnostic: upper
layers cannot tell transports apart.  This suite pins that contract —
addressing, unexpected/expected messaging, one-sided RMA, cancellation,
and eager-limit enforcement — across ``self``, ``tcp`` and ``sm``, so a
new plugin is done exactly when this matrix passes (DESIGN.md §6).
"""
import subprocess
import sys
import time
import uuid

import numpy as np
import pytest

from repro.core.na import (NACap, SelfPlugin, SMPlugin, TCPPlugin,
                           initialize)
from repro.core.types import MercuryError, Ret

PLUGINS = ["self", "tcp", "sm"]


def make_plugin(kind: str):
    if kind == "self":
        return SelfPlugin()
    if kind == "tcp":
        return TCPPlugin(None, listen=True)
    return SMPlugin(f"sm://conf-{uuid.uuid4().hex[:10]}")


@pytest.fixture(params=PLUGINS)
def pair(request):
    a, b = make_plugin(request.param), make_plugin(request.param)
    yield a, b
    a.finalize()
    b.finalize()


def spin(plugins, cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        for p in plugins:
            p.progress(0.005)
    assert cond(), "condition not met within timeout"


# -- addressing ---------------------------------------------------------------
def test_addr_self_and_lookup(pair):
    a, b = pair
    uri = a.addr_self().uri
    assert uri.startswith(f"{a.name}://") or uri.startswith(f"{a.name}-")
    addr = b.addr_lookup(uri)
    assert addr.uri == uri
    assert addr == b.addr_lookup(uri)            # stable equality
    with pytest.raises(MercuryError):
        b.addr_lookup("bogus://nowhere")


# -- two-sided messaging ------------------------------------------------------
def test_unexpected_roundtrip(pair):
    a, b = pair
    got = {}
    b.msg_recv_unexpected(
        lambda ret, src, tag, data: got.update(ret=ret, src=src.uri, tag=tag,
                                               data=bytes(data)))
    sent = {}
    a.msg_send_unexpected(a.addr_lookup(b.addr_self().uri), b"payload-1", 17,
                          lambda ret: sent.update(ret=ret))
    spin(pair, lambda: "data" in got and "ret" in sent)
    assert got["ret"] == Ret.SUCCESS and sent["ret"] == Ret.SUCCESS
    assert got["tag"] == 17 and got["data"] == b"payload-1"
    assert got["src"] == a.addr_self().uri


def test_unexpected_vectored_send(pair):
    a, b = pair
    got = {}
    b.msg_recv_unexpected(
        lambda ret, src, tag, data: got.update(data=bytes(data)))
    a.msg_send_unexpected(a.addr_lookup(b.addr_self().uri),
                          (b"head|", b"body|", b"tail"), 3, lambda ret: None)
    spin(pair, lambda: "data" in got)
    assert got["data"] == b"head|body|tail"


def test_expected_tag_matching(pair):
    a, b = pair
    addr_a = b.addr_lookup(a.addr_self().uri)
    addr_b = a.addr_lookup(b.addr_self().uri)
    got = {}
    b.msg_recv_expected(addr_a, 1, lambda ret, data: got.update(one=bytes(data)))
    b.msg_recv_expected(addr_a, 2, lambda ret, data: got.update(two=bytes(data)))
    # out-of-order sends must still match by tag
    a.msg_send_expected(addr_b, b"TWO", 2, lambda ret: None)
    a.msg_send_expected(addr_b, b"ONE", 1, lambda ret: None)
    spin(pair, lambda: len(got) == 2)
    assert got == {"one": b"ONE", "two": b"TWO"}


def test_expected_waits_for_post(pair):
    """An expected message that arrives before its recv is posted must be
    queued, not dropped."""
    a, b = pair
    addr_b = a.addr_lookup(b.addr_self().uri)
    a.msg_send_expected(addr_b, b"early", 9, lambda ret: None)
    for p in pair:                       # let it land unmatched
        p.progress(0.01)
    got = {}
    b.msg_recv_expected(None, 9, lambda ret, data: got.update(data=bytes(data)))
    spin(pair, lambda: "data" in got)
    assert got["data"] == b"early"


# -- one-sided RMA ------------------------------------------------------------
def _rma(pair, fn, *args):
    """Issue put/get; normalize sync-raise vs async-error completion."""
    box = {}
    try:
        fn(*args, lambda ret: box.setdefault("ret", ret))
    except MercuryError as e:
        return e.ret
    spin(pair, lambda: "ret" in box)
    return box["ret"]


def test_rma_put_get(pair):
    a, b = pair
    addr_b = a.addr_lookup(b.addr_self().uri)
    remote_buf = np.zeros(64, np.uint8)
    mh_remote = b.mem_register(remote_buf)
    src = np.arange(64, dtype=np.uint8)
    mh_local = a.mem_register(src)

    assert _rma(pair, a.put, mh_local, 0, addr_b, mh_remote, 0, 64) == Ret.SUCCESS
    spin(pair, lambda: remote_buf[63] == 63)
    np.testing.assert_array_equal(remote_buf, src)

    back = np.zeros(32, np.uint8)
    mh_back = a.mem_register(back)
    assert _rma(pair, a.get, mh_back, 0, addr_b, mh_remote, 16, 32) == Ret.SUCCESS
    spin(pair, lambda: back[0] == 16)
    np.testing.assert_array_equal(back, src[16:48])

    b.mem_deregister(mh_remote)
    assert _rma(pair, a.get, mh_back, 0, addr_b, mh_remote, 0, 8) != Ret.SUCCESS


def test_rma_permission_enforced(pair):
    a, b = pair
    addr_b = a.addr_lookup(b.addr_self().uri)
    secret = np.arange(16, dtype=np.uint8)
    mh_ro = b.mem_register(secret, read=True, write=False)
    local = np.zeros(16, np.uint8)
    mh_local = a.mem_register(local)
    assert _rma(pair, a.put, mh_local, 0, addr_b, mh_ro, 0, 16) != Ret.SUCCESS
    # read side still works
    assert _rma(pair, a.get, mh_local, 0, addr_b, mh_ro, 0, 16) == Ret.SUCCESS
    spin(pair, lambda: local[15] == 15)


def test_rma_out_of_bounds(pair):
    a, b = pair
    addr_b = a.addr_lookup(b.addr_self().uri)
    mh_remote = b.mem_register(np.zeros(16, np.uint8))
    mh_local = a.mem_register(np.zeros(64, np.uint8))
    assert _rma(pair, a.put, mh_local, 0, addr_b, mh_remote, 8, 16) != Ret.SUCCESS


# -- cancellation -------------------------------------------------------------
def test_cancel_unexpected_recv(pair):
    a, b = pair
    fired = []
    op = b.msg_recv_unexpected(lambda *args: fired.append(args))
    b.cancel(op)
    a.msg_send_unexpected(a.addr_lookup(b.addr_self().uri), b"msg", 5,
                          lambda ret: None)
    for _ in range(20):
        for p in pair:
            p.progress(0.005)
    assert not fired and op.canceled
    # the message was not consumed by the canceled recv: a fresh post gets it
    got = {}
    b.msg_recv_unexpected(lambda ret, src, tag, data: got.update(d=bytes(data)))
    spin(pair, lambda: "d" in got)
    assert got["d"] == b"msg"


def test_cancel_expected_recv(pair):
    a, b = pair
    fired = []
    op = b.msg_recv_expected(None, 77, lambda *args: fired.append(args))
    b.cancel(op)
    for _ in range(5):
        b.progress(0.005)
    assert not fired and op.canceled and not op.done


# -- eager limits -------------------------------------------------------------
def test_oversized_unexpected_rejected(pair):
    a, b = pair
    addr_b = a.addr_lookup(b.addr_self().uri)
    too_big = b"x" * (a.max_unexpected_size + 1)
    with pytest.raises(MercuryError) as ei:
        a.msg_send_unexpected(addr_b, too_big, 0, lambda ret: None)
    assert ei.value.ret == Ret.MSGSIZE


def test_oversized_expected_rejected(pair):
    a, b = pair
    if a.max_expected_size > (1 << 26):
        pytest.skip("plugin has no practical expected limit")
    addr_b = a.addr_lookup(b.addr_self().uri)
    with pytest.raises(MercuryError) as ei:
        a.msg_send_expected(addr_b, b"x" * (a.max_expected_size + 1), 0,
                            lambda ret: None)
    assert ei.value.ret == Ret.MSGSIZE


# -- capability surface -------------------------------------------------------
def test_capability_flags(pair):
    a, _ = pair
    if a.name in ("self", "sm"):
        assert a.caps & NACap.NATIVE_RMA and a.caps & NACap.ZERO_COPY
    else:
        assert not a.caps & NACap.NATIVE_RMA
    assert a.max_unexpected_size > 0 and a.max_expected_size > 0


# -- locality-tiered routing --------------------------------------------------
def test_tiered_resolution_prefers_cheapest_reachable():
    """An address set resolves self > sm > tcp, skipping unreachable tiers."""
    tag = uuid.uuid4().hex[:8]
    srv = initialize(f"self://tier-{tag};sm://tier-{tag};tcp://127.0.0.1:0")
    cli = initialize(f"self://tcli-{tag};sm://tcli-{tag};tcp://127.0.0.1:0")
    try:
        srv_set = srv.addr_self().uri
        assert srv_set.count(";") == 2
        # same process: the self tier wins
        assert cli.addr_lookup(srv_set).uri == f"self://tier-{tag}"
        # self tier unreachable (no such in-process instance): sm wins
        ghost = f"self://ghost-{tag};sm://tier-{tag};tcp://127.0.0.1:1"
        assert cli.addr_lookup(ghost).uri == f"sm://tier-{tag}"
        # only tcp reachable
        tcp_uri = [u for u in srv_set.split(";") if u.startswith("tcp")][0]
        only_tcp = f"self://ghost-{tag};sm://ghost-{tag};{tcp_uri}"
        assert cli.addr_lookup(only_tcp).uri == tcp_uri
    finally:
        srv.finalize()
        cli.finalize()


def test_multi_transport_engine_end_to_end():
    """Engines listening on an address set: calls route over the cheapest
    tier, and bulk descriptors minted by a multi engine stay valid."""
    from repro.core.executor import Engine
    tag = uuid.uuid4().hex[:8]
    with Engine(f"self://ms-{tag};sm://ms-{tag};tcp://127.0.0.1:0") as srv, \
            Engine(f"self://mc-{tag};sm://mc-{tag};tcp://127.0.0.1:0") as cli:
        srv.register("echo", lambda x: x)
        assert cli.call(srv.uri, "echo", {"v": 7})["v"] == 7
        # shared-key registration: pull through the resolved tier
        src = np.arange(10_000, dtype=np.float32)
        h = srv.expose([src])
        dst = np.zeros_like(src)
        hd = cli.expose([dst])
        cli.pull(srv.uri, h.descriptor(), hd)
        np.testing.assert_array_equal(dst, src)


def test_multi_falls_back_when_tier_dies():
    """If the cheap tier's listener vanishes, a fresh lookup of the same
    address set lands on the next tier instead of failing."""
    tag = uuid.uuid4().hex[:8]
    a = SelfPlugin(f"self://dies-{tag}")
    b = SMPlugin(f"sm://dies-{tag}")
    cli = initialize([f"self://dcli-{tag}", f"sm://dcli-{tag}"])
    try:
        addr_set = f"self://dies-{tag};sm://dies-{tag}"
        assert cli.addr_lookup(addr_set).uri.startswith("self://")
        a.finalize()                     # self tier gone
        assert cli.addr_lookup(addr_set).uri.startswith("sm://")
    finally:
        b.finalize()
        cli.finalize()


# -- sm cross-process ---------------------------------------------------------
SM_CHILD = """
import sys, time
sys.path.insert(0, "src")
import numpy as np
from repro.core.na import SMPlugin

parent_uri, key, size = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
from repro.core.na.base import NAMemHandle
p = SMPlugin("sm://child-" + sys.argv[4])
addr = p.addr_lookup(parent_uri)

got = {}
p.msg_recv_expected(addr, 2, lambda ret, data: got.update(d=bytes(data)))
p.msg_send_unexpected(addr, b"hello-from-child", 1, lambda ret: None)
t0 = time.time()
while "d" not in got and time.time() - t0 < 15:
    p.progress(0.01)
assert got.get("d") == b"go", got

# one-sided put into the parent's shm-backed registration: the parent's
# progress loop is *not* serving this — pure initiator-side copy
local = np.arange(size, dtype=np.uint8)
mh_local = p.mem_register(local)
remote = NAMemHandle(key=key, size=size, owner_uri=parent_uri)
done = []
p.put(mh_local, 0, addr, remote, 0, size, lambda ret: done.append(ret))
t0 = time.time()
while not done and time.time() - t0 < 15:
    p.progress(0.01)
p.msg_send_unexpected(addr, b"put-done", 3, lambda ret: None)
t0 = time.time()
while time.time() - t0 < 1:
    p.progress(0.01)
p.finalize()
print("CHILD_OK", done[0].name)
"""


def test_sm_cross_process_messaging_and_rma():
    tag = uuid.uuid4().hex[:10]
    parent = SMPlugin(f"sm://parent-{tag}")
    try:
        target = parent.alloc_array((256,), np.uint8)
        target[:] = 0
        mh = parent.mem_register(target)

        child = subprocess.Popen(
            [sys.executable, "-c", SM_CHILD, parent.addr_self().uri,
             str(mh.key), "256", tag],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=".")

        events = {}

        def on_unexp(ret, src, tag_, data):
            parent.msg_recv_unexpected(on_unexp)
            events[bytes(data)] = src

        parent.msg_recv_unexpected(on_unexp)
        spin([parent], lambda: b"hello-from-child" in events, timeout=20)
        src = events[b"hello-from-child"]
        parent.msg_send_expected(src, b"go", 2, lambda ret: None)
        spin([parent], lambda: b"put-done" in events, timeout=20)
        np.testing.assert_array_equal(np.asarray(target),
                                      np.arange(256, dtype=np.uint8))
        out, err = child.communicate(timeout=20)
        assert "CHILD_OK SUCCESS" in out, out + err
    finally:
        parent.finalize()


@pytest.mark.parametrize("n_frames", [20, 200])
def test_sm_burst_coalesces_doorbells(n_frames):
    """A burst queued while the consumer does not progress rings the
    peer's doorbell O(1) times, not once per frame (the coalesced send
    path rings only on the ring's idle→busy transition and on ring-full
    liveness probes), and every frame still arrives once it drains."""
    tag = uuid.uuid4().hex[:8]
    a = SMPlugin(f"sm://burst-a-{tag}")
    b = SMPlugin(f"sm://burst-b-{tag}")
    try:
        got = []
        for _ in range(n_frames):
            b.msg_recv_unexpected(
                lambda ret, src, t, data: got.append(bytes(data)))
        dst = a.addr_lookup(b.addr_self().uri)
        for i in range(n_frames):
            a.msg_send_unexpected(dst, b"y" * 64, i, lambda ret: None)
        frames, bells = a.stat_frames, a.stat_bells
        spin([b, a], lambda: len(got) == n_frames, timeout=10.0)
        assert frames == n_frames
        assert bells <= max(4, n_frames // 10), \
            f"{bells} doorbell writes for {n_frames} queued frames"
        assert got == [b"y" * 64] * n_frames
    finally:
        a.finalize()
        b.finalize()
