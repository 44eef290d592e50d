"""Cross-host serving fleet: TCP transport, authenticated hellos, host
failure domains, and the network-chaos proxy.

The exactness bar is inherited from test_worker_isolation: a stream
migrated off a host that vanished mid-decode — here via a REAL network
partition injected by :class:`ChaosProxy`, not a signal — must finish
bit-identical to ``generate_cached(batch=1)``, greedy and sampled, with
zero re-emitted tokens. On top of that the cross-host plane adds its own
contracts: frames torn at every header byte boundary surface as loud
WireErrors naming the peer, an unauthenticated or version-mismatched
peer is refused before any engine state moves, a lost host is contained
as ONE batch that never lands a stream on a dying sibling, and a healed
host is re-admitted by dial probe. Everything outside the two slow tests
runs jax-free — the frontend-package contract.
"""

from __future__ import annotations

import dataclasses
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.serving.frontend.netchaos import ChaosProxy
from gpt_2_distributed_tpu.serving.frontend.rpc import (
    MAX_FRAME_BYTES,
    WIRE_VERSION,
    WireError,
    auth_mac,
    client_hello,
    create_listener,
    dial,
    listener_addr,
    load_auth_token,
    make_nonce,
    parse_addr,
    recv_msg,
    send_msg,
    server_hello,
)
from gpt_2_distributed_tpu.serving.frontend.worker import (
    RemoteSpawner,
    read_worker_pool,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tier1_runtime_budget(request):
    t0 = time.perf_counter()
    yield
    if request.node.get_closest_marker("slow") is None:
        elapsed = time.perf_counter() - t0
        assert elapsed < 90, (
            f"{request.node.name} took {elapsed:.1f}s — default-tier tests "
            "must stay under 90s; size the config down or mark it slow"
        )


# ------------------------------------------------------- TCP transport


def test_parse_addr_specs():
    assert parse_addr("/tmp/w.sock") == ("unix", "/tmp/w.sock")
    assert parse_addr("tcp://10.0.0.7:9000") == ("tcp", ("10.0.0.7", 9000))
    for bad in ("tcp://nohost", "tcp://:9000", "tcp://h:port",
                "tcp://h:70000"):
        with pytest.raises(ValueError, match="tcp://|port"):
            parse_addr(bad)


def test_tcp_listener_dial_roundtrip():
    """Frames survive a real TCP hop byte-for-byte, and a port-0 bind
    resolves through ``listener_addr`` to something dialable."""
    lsock = create_listener("tcp://127.0.0.1:0")
    try:
        spec = listener_addr(lsock)
        assert spec.startswith("tcp://127.0.0.1:")
        c = dial(spec, timeout=5)
        s, _ = lsock.accept()
        try:
            msg = {"op": "step", "toks": list(range(40)), "uni": "héllo"}
            send_msg(c, msg)
            assert recv_msg(s) == msg
            send_msg(s, {"ok": True})
            send_msg(s, {"ok": False, "n": 2})
            assert recv_msg(c) == {"ok": True}
            assert recv_msg(c) == {"ok": False, "n": 2}
        finally:
            c.close()
            s.close()
    finally:
        lsock.close()


@pytest.mark.parametrize("cut", [0, 1, 2, 3])
def test_torn_frame_at_every_header_byte_boundary(cut):
    """A connection severed ``cut`` bytes into the 4-byte length prefix —
    what ChaosProxy.tear produces mid-header — surfaces as a WireError
    naming the peer and the short read, never a hang or a misparse."""
    lsock = create_listener("tcp://127.0.0.1:0")
    try:
        c = dial(listener_addr(lsock), timeout=5)
        c.settimeout(10)
        s, _ = lsock.accept()
        header = struct.pack(">I", 5)
        s.sendall(header[:cut])
        s.close()
        with pytest.raises(WireError) as ei:
            recv_msg(c)
        text = str(ei.value)
        assert "127.0.0.1" in text            # names the peer
        if cut == 0:
            assert "EOF" in text
        else:
            assert f"{cut}/4 bytes" in text   # names the boundary
        c.close()
    finally:
        lsock.close()


def test_torn_frame_mid_payload_names_progress():
    lsock = create_listener("tcp://127.0.0.1:0")
    try:
        c = dial(listener_addr(lsock), timeout=5)
        c.settimeout(10)
        s, _ = lsock.accept()
        s.sendall(struct.pack(">I", 10) + b"{"  b"abc")   # 4 of 10 bytes
        s.close()
        with pytest.raises(WireError, match=r"4/10 bytes"):
            recv_msg(c)
        c.close()
    finally:
        lsock.close()


def test_oversize_frame_reports_declared_length_and_peer():
    """Satellite: a corrupt length prefix must be diagnosable from the
    log line alone — declared length AND peer, before any allocation."""
    a, b = socket.socketpair()
    try:
        declared = MAX_FRAME_BYTES + 7
        a.sendall(struct.pack(">I", declared))
        with pytest.raises(WireError) as ei:
            recv_msg(b, peer="tcp-host-7:9000")
        text = str(ei.value)
        assert str(declared) in text
        assert "tcp-host-7:9000" in text
        assert "declares length" in text
    finally:
        a.close()
        b.close()


def test_malformed_frame_reports_length_and_peer():
    a, b = socket.socketpair()
    try:
        raw = b"\xff\xfe not json"
        a.sendall(struct.pack(">I", len(raw)) + raw)
        with pytest.raises(WireError) as ei:
            recv_msg(b, peer="worker-3")
        assert f"malformed {len(raw)}-byte frame" in str(ei.value)
        assert "worker-3" in str(ei.value)
    finally:
        a.close()
        b.close()


# ------------------------------------------------- authenticated hello


def _hello_server(conn, token, payload):
    """Worker side of one hello exchange, run in a thread. ``out`` gets
    ``ok`` (server_hello verdict) and ``sent_engine`` iff engine state
    crossed the link."""
    out = {}

    def serve():
        try:
            msg = recv_msg(conn, peer="frontend")
            out["ok"] = server_hello(conn, msg, token, peer="frontend")
            if out["ok"]:
                send_msg(conn, payload, peer="frontend")
                out["sent_engine"] = True
        except WireError as e:
            out["error"] = str(e)
        finally:
            conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return t, out


def test_hello_mutual_auth_success():
    token = b"fleet-secret"
    payload = {"ok": True, "wire_version": WIRE_VERSION, "pid": 4242,
               "engine": "state"}
    a, b = socket.socketpair()
    t, out = _hello_server(b, token, payload)
    try:
        reply = client_hello(a, token, peer="worker")
        assert reply == payload
    finally:
        a.close()
        t.join(timeout=10)
    assert out.get("ok") is True and out.get("sent_engine") is True


def test_hello_wrong_token_refused_before_engine_state():
    """Token mismatch: the client detects the bad server proof (mutual
    auth) and refuses loudly; the worker never sends its engine payload."""
    a, b = socket.socketpair()
    t, out = _hello_server(b, b"right-token", {"ok": True})
    try:
        with pytest.raises(WireError, match="mutual authentication"):
            client_hello(a, b"wrong-token", peer="worker")
    finally:
        a.close()
        t.join(timeout=10)
    assert out.get("ok") is False
    assert "sent_engine" not in out


def test_hello_bad_client_mac_refused_loudly():
    """A peer that accepts the challenge but answers with a garbage MAC
    is refused with a loud error frame — and no engine state."""
    token = b"fleet-secret"
    a, b = socket.socketpair()
    t, out = _hello_server(b, token, {"ok": True})
    try:
        send_msg(a, {"op": "hello", "wire_version": WIRE_VERSION})
        challenge = recv_msg(a)
        assert challenge.get("auth") == "challenge"
        # No client nonce was sent, so the worker must not volunteer a
        # proof the client never asked to verify.
        assert "proof" not in challenge
        send_msg(a, {"op": "auth", "mac": "bogus"})
        refusal = recv_msg(a)
        assert refusal["ok"] is False
        assert "authentication failed" in refusal["error"]
    finally:
        a.close()
        t.join(timeout=10)
    assert out.get("ok") is False
    assert "sent_engine" not in out


def test_hello_unauthenticated_worker_refused_by_client():
    """--worker_auth_token_file set, but the worker never challenges:
    the frontend refuses to adopt it."""
    a, b = socket.socketpair()
    t, out = _hello_server(b, None, {"ok": True,
                                     "wire_version": WIRE_VERSION})
    try:
        with pytest.raises(WireError, match="refusing to adopt an "
                                            "unauthenticated worker"):
            client_hello(a, b"fleet-secret", peer="worker")
    finally:
        a.close()
        t.join(timeout=10)


def test_hello_auth_required_but_client_has_no_token():
    a, b = socket.socketpair()
    t, out = _hello_server(b, b"fleet-secret", {"ok": True})
    try:
        with pytest.raises(WireError, match="requires authentication"):
            client_hello(a, None, peer="worker")
    finally:
        a.close()
        t.join(timeout=10)
    assert out.get("ok") is False


def test_hello_stale_wire_version_refused_before_auth():
    """Version mismatch is checked before the auth challenge: a worker
    from another build refuses the peer without leaking a challenge."""
    a, b = socket.socketpair()
    t, out = _hello_server(b, b"fleet-secret", {"ok": True})
    try:
        send_msg(a, {"op": "hello", "wire_version": WIRE_VERSION + 1,
                     "nonce": make_nonce()})
        refusal = recv_msg(a)
        assert refusal["ok"] is False
        assert "auth" not in refusal
        assert "wire version mismatch" in refusal["error"]
    finally:
        a.close()
        t.join(timeout=10)
    assert out.get("ok") is False
    assert "sent_engine" not in out


def test_auth_mac_binds_role_and_nonce():
    """The role tag stops reflection (a challenger's own proof replayed
    back at it); the nonce stops replay across handshakes."""
    token, nonce = b"tok", make_nonce()
    assert auth_mac(token, "server", nonce) != auth_mac(token, "client",
                                                        nonce)
    assert auth_mac(token, "client", nonce) != auth_mac(token, "client",
                                                        make_nonce())
    assert auth_mac(token, "client", nonce) != auth_mac(b"tok2", "client",
                                                        nonce)


def test_load_auth_token_strips_and_rejects_empty(tmp_path):
    p = tmp_path / "tok"
    p.write_text("  s3cret\n")
    assert load_auth_token(str(p)) == b"s3cret"
    p.write_text(" \n\t")
    with pytest.raises(ValueError, match="empty"):
        load_auth_token(str(p))


# ------------------------------------------------------- chaos proxy


def _echo_upstream():
    """A TCP echo server for proxy tests; returns (addr, close_fn)."""
    lsock = create_listener("tcp://127.0.0.1:0")

    def accept_loop():
        while True:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return
            def pump(c=conn):
                while True:
                    try:
                        data = c.recv(65536)
                    except OSError:
                        break
                    if not data:
                        break
                    try:
                        c.sendall(data)
                    except OSError:
                        break
                c.close()
            threading.Thread(target=pump, daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    return listener_addr(lsock), lsock.close


def _recv_all(sock, n, timeout=10.0):
    sock.settimeout(timeout)
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            break
        buf += chunk
    return buf


def test_chaos_proxy_passthrough_and_tear():
    up, close_up = _echo_upstream()
    px = ChaosProxy(up)
    try:
        c = dial(px.addr, timeout=5)
        c.sendall(b"ABCDEFGH")
        assert _recv_all(c, 8) == b"ABCDEFGH"
        # Arm a 3-byte tear: exactly 3 more reply bytes arrive, then the
        # link dies mid-stream — a reply truncated inside a frame.
        px.tear(after_bytes=3)
        c.sendall(b"12345678")
        got = _recv_all(c, 8)
        assert got == b"123"
        c.close()
    finally:
        px.close()
        close_up()


def test_chaos_proxy_partition_then_heal_same_port():
    """Partition semantics the re-admission probe depends on: dials are
    REFUSED while partitioned (not accepted into a dead link), live
    connections are severed, and heal rebinds the very same port."""
    up, close_up = _echo_upstream()
    px = ChaosProxy(up)
    try:
        port = parse_addr(px.addr)[1][1]
        live = dial(px.addr, timeout=5)
        live.sendall(b"hi")
        assert _recv_all(live, 2) == b"hi"
        px.partition()
        with pytest.raises(OSError):
            dial(px.addr, timeout=1.0)
        # The live connection is severed, not left dangling.
        live.settimeout(5)
        assert live.recv(1) == b""
        live.close()
        px.heal()
        assert parse_addr(px.addr)[1][1] == port
        c2 = dial(px.addr, timeout=5)
        c2.sendall(b"back")
        assert _recv_all(c2, 4) == b"back"
        c2.close()
    finally:
        px.close()
        close_up()


def test_chaos_proxy_blackhole_is_one_way():
    """Down-direction blackhole: the sender sees a healthy connection,
    replies simply never arrive — until heal."""
    up, close_up = _echo_upstream()
    px = ChaosProxy(up)
    try:
        c = dial(px.addr, timeout=5)
        px.blackhole("down")
        c.sendall(b"lost")
        c.settimeout(0.3)
        with pytest.raises(socket.timeout):
            c.recv(1)
        px.heal()
        c.sendall(b"found")
        assert _recv_all(c, 5) == b"found"
        c.close()
    finally:
        px.close()
        close_up()


# ---------------------------------------------- worker pool / spawner


def test_read_worker_pool_parses_ledger(tmp_path):
    p = tmp_path / "pool"
    p.write_text(
        "# fleet ledger\n"
        "\n"
        "hostA tcp://127.0.0.1:9001\n"
        "hostB tcp://127.0.0.1:9002\n"
        "hostC tcp://127.0.0.1:9001\n"   # re-registration: same addr
    )
    entries = read_worker_pool(str(p))
    assert [(e["host_id"], e["addr"]) for e in entries] == [
        ("hostC", "tcp://127.0.0.1:9001"),   # last registration wins
        ("hostB", "tcp://127.0.0.1:9002"),
    ]
    assert all(e["handle"] is None for e in entries)


def test_read_worker_pool_rejects_malformed_and_empty(tmp_path):
    p = tmp_path / "pool"
    p.write_text("hostA tcp://1.2.3.4:5 extra\n")
    with pytest.raises(ValueError, match=":1:"):
        read_worker_pool(str(p))
    p.write_text("# only comments\n\n")
    with pytest.raises(ValueError, match="names no workers"):
        read_worker_pool(str(p))


def _pool(*pairs):
    return [{"host_id": h, "addr": a, "handle": None} for h, a in pairs]


def test_remote_spawner_quarantine_and_free_entries():
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = RemoteSpawner(
        _pool(("h0", "tcp://127.0.0.1:1"), ("h0", "tcp://127.0.0.1:2"),
              ("h1", "tcp://127.0.0.1:3")),
        serve,
    )
    assert sp.hosts_active == 2
    assert len(sp._free_entries()) == 3
    sp.mark_host_dead("h0")
    assert sp.hosts_active == 1
    assert [e["addr"] for e in sp._free_entries()] == ["tcp://127.0.0.1:3"]
    sp.readmit("h0")
    assert sp.hosts_active == 2 and len(sp._free_entries()) == 3

    # An entry with a LIVE handle is in use; a dead handle frees it.
    class H:
        _dead = None
    sp.pool[2]["handle"] = H()
    assert len(sp._free_entries()) == 2
    sp.pool[2]["handle"]._dead = "heartbeat lost"
    assert len(sp._free_entries()) == 3


def test_remote_spawner_respawn_budget_exhaustion():
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = RemoteSpawner(_pool(("h0", "tcp://127.0.0.1:1")), serve,
                       max_respawns=0, respawn_backoff_s=0.0)

    class FakeRouter:
        n_failed = 1

    sp.router = FakeRouter()
    with pytest.raises(RuntimeError, match="respawn budget"):
        sp()
    assert sp.spawns == 0 and sp.respawns == 0


def test_remote_spawner_every_host_quarantined_gives_up_loudly():
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = RemoteSpawner(_pool(("h0", "tcp://127.0.0.1:1"),
                             ("h1", "tcp://127.0.0.1:2")), serve)
    sp.mark_host_dead("h0")
    sp.mark_host_dead("h1")
    with pytest.raises(RuntimeError, match="no adoptable worker"):
        sp()


def test_remote_spawner_poll_hosts_readmits_on_dial(tmp_path):
    """The re-admission probe: a quarantined host stays dead while its
    worker is unreachable, and rejoins the moment a dial lands."""
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    lsock = create_listener("tcp://127.0.0.1:0")
    addr = listener_addr(lsock)
    lsock.close()                       # host down: dials refused
    sp = RemoteSpawner(_pool(("h9", addr)), serve)
    sp.mark_host_dead("h9")
    assert sp.poll_hosts() == []
    assert sp.dead_hosts == {"h9"}
    # Rebind the same port (SO_REUSEADDR): the host is back.
    lsock = create_listener(addr)
    try:
        assert sp.poll_hosts() == ["h9"]
        assert sp.dead_hosts == set()
        assert sp.hosts_active == 1
    finally:
        lsock.close()


def _fake_worker(serve, token, refuse=False):
    """A jax-free stand-in for ``gpt2-tpu-worker``: real listener, real
    hello protocol, fake engine payload. Returns (addr, close_fn)."""
    lsock = create_listener("tcp://127.0.0.1:0")
    payload = {
        "ok": True, "wire_version": WIRE_VERSION,
        "serve": dataclasses.asdict(serve),
        "kv_pool_bytes_per_device": 0, "pid": 4242, "stats": None,
    }

    def accept_loop():
        while True:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return
            try:
                msg = recv_msg(conn, peer="frontend")
                if server_hello(conn, msg, token, peer="frontend"):
                    send_msg(conn, payload, peer="frontend")
                    recv_msg(conn, peer="frontend")   # park until close
            except WireError:
                pass
            finally:
                conn.close()

    threading.Thread(target=accept_loop, daemon=True).start()
    return listener_addr(lsock), lsock.close


def test_remote_spawner_adopts_authenticated_worker():
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    addr, close_fn = _fake_worker(serve, b"fleet-secret")
    try:
        sp = RemoteSpawner(_pool(("hA", addr)), serve,
                           connect_timeout_s=10.0,
                           auth_token=b"fleet-secret")
        h = sp()
        assert h.host_id == "hA" and h.pid == 4242 and h.proc is None
        assert h.peer == addr
        assert sp.pool[0]["handle"] is h and sp.spawns == 1
        h.close()           # remote: disconnect only, never a kill
        with pytest.raises(RuntimeError, match="remote"):
            h.kill()
    finally:
        close_fn()


def test_remote_spawner_refuses_wrong_token_worker():
    """The wrong-token path end-to-end through the spawner: adoption
    fails loudly with the auth refusal in the error, not a hang and not
    a half-adopted handle."""
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    addr, close_fn = _fake_worker(serve, b"worker-token")
    try:
        sp = RemoteSpawner(_pool(("hA", addr)), serve,
                           connect_timeout_s=10.0,
                           auth_token=b"frontend-token")
        with pytest.raises(RuntimeError, match="mutual authentication"):
            sp()
        assert sp.pool[0]["handle"] is None and sp.spawns == 0
    finally:
        close_fn()


def test_remote_spawner_rejects_serve_config_mismatch():
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    other = ServeConfig(max_batch=4, block_size=8, num_blocks=8)
    addr, close_fn = _fake_worker(other, None)
    try:
        sp = RemoteSpawner(_pool(("hA", addr)), serve,
                           connect_timeout_s=10.0)
        with pytest.raises(RuntimeError, match="different ServeConfig"):
            sp()
    finally:
        close_fn()


# ------------------------------------------- host failure domains (fast)


class _FakeReq:
    def __init__(self, rid):
        self.id = rid
        self.generated = [1, 2, 3]
        self.replica = None
        self.finish_reason = None

    def _finish(self, reason):
        self.finish_reason = reason


class _FakeEngine:
    def __init__(self, host_id, serve):
        self.host_id = host_id
        self.serve = serve
        self.inflight = []
        self.adopted = []
        self.queue_depth = 0

    @property
    def occupancy(self):
        return len(self.inflight)

    def extract_inflight(self):
        out, self.inflight = self.inflight, []
        return out

    def adopt(self, req):
        self.adopted.append(req)
        self.inflight.append(req)


class _FakeHostSpawner:
    """make_engine with the host-quarantine surface RemoteSpawner has."""

    def __init__(self, hosts, serve):
        self.hosts = list(hosts)
        self.serve = serve
        self.dead_hosts = set()
        self.marked = []
        self.polled = 0

    def __call__(self):
        host = self.hosts.pop(0) if self.hosts else "spare"
        return _FakeEngine(host, self.serve)

    def mark_host_dead(self, host_id):
        self.marked.append(host_id)
        self.dead_hosts.add(host_id)

    def poll_hosts(self):
        self.polled += 1
        rejoined = sorted(self.dead_hosts)
        self.dead_hosts.clear()
        return rejoined

    @property
    def hosts_active(self):
        return 2 - len(self.dead_hosts)


def test_fail_host_contains_domain_as_one_batch():
    """Every replica on the lost host is marked FAILED *before* the one
    adopt wave — so no stream can land on a dying sibling — and the
    spawner is quarantined first, so growth avoids the dead host."""
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = _FakeHostSpawner(["h0", "h0", "h1", "h1"], serve)
    router = ReplicaRouter(sp, replicas=4, policy="round_robin")
    reqs = [_FakeReq(1), _FakeReq(2), _FakeReq(3)]
    router.engines[0].inflight.extend(reqs[:2])
    router.engines[1].inflight.append(reqs[2])

    moved = router.fail_host("h0")

    assert moved == 3
    assert router.host_failures == 1
    assert router.replica_failures == 2
    assert sp.marked == ["h0"]
    assert router.active_indices() == [2, 3]
    for r in reqs:
        assert r.finish_reason is None       # migrated, not abandoned
        assert r.replica in (2, 3)
    # The batch contract: NOTHING landed on the dying siblings.
    assert router.engines[0].adopted == []
    assert router.engines[1].adopted == []
    assert router.migrated == 3
    # Idempotent; unknown hosts are a no-op, not a failure event.
    assert router.fail_host("h0") == 0
    assert router.fail_host("h7") == 0
    assert router.host_failures == 1


def test_fail_host_last_resort_growth_lands_on_survivor():
    """When the lost host held EVERY active replica, the adopt wave's
    last-resort grow must place the replacement on a surviving host —
    the spawner was quarantined before placement ran."""
    from gpt_2_distributed_tpu.serving.frontend.router import ReplicaRouter

    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = _FakeHostSpawner(["h0", "h0", "h1"], serve)
    router = ReplicaRouter(sp, replicas=2, max_replicas=3,
                           policy="round_robin")
    reqs = [_FakeReq(1), _FakeReq(2)]
    router.engines[0].inflight.append(reqs[0])
    router.engines[1].inflight.append(reqs[1])

    moved = router.fail_host("h0")

    assert moved == 2
    assert len(router.engines) == 3
    assert router.engines[2].host_id == "h1"     # not the dead host
    assert all(r.replica == 2 for r in reqs)
    assert router.engines[2].adopted == reqs
    # Re-admission delegates to the spawner's dial probe.
    assert router.poll_hosts() == ["h0"]
    assert sp.polled == 1
    assert router.poll_hosts() == []             # nothing quarantined now


# ------------------------------------------------- jax-free flag checks


def test_frontend_package_imports_jax_free(run_jax_free):
    """The whole serving/frontend package — rpc, worker, router, driver,
    autoscale, server, netchaos — imports with jax poisoned: the worker
    CLI must bind its socket and the frontends must validate flags
    before any jax import."""
    code = (
        "import importlib, pkgutil\n"
        "import gpt_2_distributed_tpu.serving.frontend as fe\n"
        "mods = sorted(m.name for m in pkgutil.iter_modules(\n"
        "    fe.__path__, fe.__name__ + '.'))\n"
        "for m in mods:\n"
        "    importlib.import_module(m)\n"
        "print('\\n'.join(mods))\n"
    )
    r = run_jax_free("-c", code)
    assert r.returncode == 0, r.stderr[-2000:]
    mods = r.stdout.split()
    for expected in ("netchaos", "rpc", "worker", "router", "driver",
                     "autoscale", "server"):
        assert any(m.endswith("." + expected) for m in mods), (expected,
                                                               mods)


@pytest.mark.parametrize("cli", ["serve", "frontend"])
@pytest.mark.parametrize("flags, named", [
    (("--placement", "subprocess", "--worker_heartbeat_timeout_s", "0"),
     "--worker_heartbeat_timeout_s"),
    (("--placement", "subprocess", "--worker_heartbeat_timeout_s", "-2"),
     "--worker_heartbeat_timeout_s"),
    (("--placement", "subprocess", "--worker_auth_token_file", "{missing}"),
     "--worker_auth_token_file"),
    (("--placement", "subprocess", "--worker_auth_token_file", "{empty}"),
     "--worker_auth_token_file"),
    (("--placement", "remote"), "--worker_pool"),
    (("--placement", "remote", "--worker_pool", "{missing}"),
     "--worker_pool"),
    (("--placement", "subprocess", "--worker_pool", "{pool}"),
     "--worker_pool"),
], ids=" ".join)
def test_fleet_flags_rejected_jax_free(run_cli_jax_free, tmp_path, cli,
                                       flags, named):
    """Every cross-host flag is validated before the jax import, in both
    CLIs that share validate_worker_flags."""
    empty = tmp_path / "empty_token"
    empty.write_text(" \n")
    pool = tmp_path / "pool"
    pool.write_text("h0 tcp://127.0.0.1:9000\n")
    files = {"missing": tmp_path / "nonexistent", "empty": empty,
             "pool": pool}
    r = run_cli_jax_free(cli, *(f.format(**files) for f in flags))
    assert r.returncode != 0
    assert named in r.stderr, r.stderr[-300:]


def test_worker_cli_rejects_bad_socket_spec_jax_free(run_cli_jax_free):
    r = run_cli_jax_free("worker", "--socket", "tcp://nohost")
    assert r.returncode != 0
    assert "tcp://" in r.stderr


# ----------------------------------- real fleet over TCP + chaos (slow)


def _worker_args(extra=()):
    from gpt_2_distributed_tpu.serving.serve import build_argparser

    p = build_argparser()
    return p.parse_args([
        "--init_random", "--model", "124M", "--n_layer", "2",
        "--n_embd", "32", "--n_head", "2", "--vocab_size", "257",
        "--seq_len", "64", "--max_batch", "4", "--block_size", "8",
        "--num_blocks", "32", "--attn_impl", "xla", "--device", "cpu",
        "--requests", "-", *extra,
    ])


def _model_and_serve(args):
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.serving.serve import (
        build_serve_config,
        model_config_from_args,
    )

    config = model_config_from_args(args)
    serve = build_serve_config(args, config)
    return config, gpt2.init_params(config), serve


def _oneshot(params, config, prompt, rng, new, **kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gpt_2_distributed_tpu.models.decode import generate_cached

    key = rng if hasattr(rng, "dtype") else jax.random.PRNGKey(rng)
    out = generate_cached(
        params, config, jnp.asarray([prompt], jnp.int32), key,
        max_new_tokens=new, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def _spawn_fleet_workers(tmp_path, temperature, hosts):
    """Start one real gpt2-tpu-worker per (host_id) entry, all on
    tcp://127.0.0.1:0 with --advertise into a shared ledger. Returns
    (procs, ledger_path, token_path)."""
    ledger = str(tmp_path / "advertised")
    token_path = str(tmp_path / "token")
    with open(token_path, "w") as f:
        f.write("fleet-test-secret\n")
    argv_base = [
        sys.executable, "-m",
        "gpt_2_distributed_tpu.serving.frontend.worker",
        "--init_random", "--model", "124M", "--n_layer", "2",
        "--n_embd", "32", "--n_head", "2", "--vocab_size", "257",
        "--seq_len", "64", "--max_batch", "4", "--block_size", "8",
        "--num_blocks", "32", "--attn_impl", "xla", "--device", "cpu",
        "--temperature", str(temperature),
        "--socket", "tcp://127.0.0.1:0", "--advertise", ledger,
        "--auth_token_file", token_path,
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(argv_base + ["--host_id", h], cwd=REPO, env=env,
                         stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
        for h in hosts
    ]
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        try:
            if len(read_worker_pool(ledger)) == len(hosts):
                break
        except (OSError, ValueError):
            pass
        for p in procs:
            assert p.poll() is None, "worker died during startup"
        time.sleep(0.2)
    else:
        raise AssertionError("fleet never finished advertising")
    return procs, ledger, token_path


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 1.0],
                         ids=["greedy", "sampled"])
def test_host_partition_migration_bit_exact(temperature):
    """A REAL network partition (ChaosProxy) takes down both replicas of
    host "a" mid-decode. The driver's health sweep classifies the loss as
    a host death, contains it as ONE batch, replacements land on host
    "b", and every stream still finishes bit-identical to
    ``generate_cached(batch=1)`` with zero re-emitted tokens. Healing the
    proxies re-admits the host via dial probe."""
    import jax

    from gpt_2_distributed_tpu.serving.frontend import (
        Autoscaler,
        EngineDriver,
        ReplicaRouter,
    )

    import pathlib
    import tempfile

    with tempfile.TemporaryDirectory(prefix="gpt2tpu-fleet-") as td:
        tmp_path = pathlib.Path(td)
        args = _worker_args(["--temperature", str(temperature)])
        config, params, serve = _model_and_serve(args)
        procs, ledger, token_path = _spawn_fleet_workers(
            tmp_path, temperature, hosts=["a", "a", "b", "b"])
        proxies = []
        try:
            raw = sorted(read_worker_pool(ledger),
                         key=lambda e: (e["host_id"], e["addr"]))
            pool_lines = []
            for e in raw:
                if e["host_id"] == "a":
                    px = ChaosProxy(e["addr"])
                    proxies.append(px)
                    pool_lines.append(f'a {px.addr}')
                else:
                    pool_lines.append(f'b {e["addr"]}')
            pool_path = tmp_path / "pool"
            # "a" entries first: both initial replicas adopt on host a.
            pool_path.write_text("\n".join(pool_lines) + "\n")

            spawner = RemoteSpawner(
                read_worker_pool(str(pool_path)), serve,
                initial_replicas=2, max_respawns=3,
                respawn_backoff_s=0.1, heartbeat_s=0.05,
                heartbeat_timeout_s=1.0, connect_timeout_s=120.0,
                auth_token=load_auth_token(token_path),
            )
            router = ReplicaRouter(spawner, replicas=2, max_replicas=4,
                                   policy="round_robin")
            spawner.router = router
            assert [h.host_id for h in router.engines] == ["a", "a"]
            scaler = Autoscaler(router, min_replicas=2, max_replicas=4)
            driver = EngineDriver(router, autoscaler=scaler,
                                  autoscale_every=10)

            reqs = [([5, 6, 7], 8), ([9, 10], 10), ([1, 2, 3, 4], 8),
                    ([11, 12], 12)]
            counts = {}
            handles = [
                driver.submit(prompt, new, rng=jax.random.PRNGKey(100 + i),
                              on_token=lambda rh, _t: counts.__setitem__(
                                  rh.id, counts.get(rh.id, 0) + 1))
                for i, (prompt, new) in enumerate(reqs)
            ]
            fired = False
            while driver.has_work():
                if not fired and driver.steps >= 4:
                    for px in proxies:
                        px.partition()
                    fired = True
                    time.sleep(0.2)   # let the heartbeat window lapse
                driver.step()
            driver.close()

            assert fired
            assert router.host_failures == 1      # ONE batch, not two
            assert router.replica_failures == 2
            assert router.migrated >= 1
            assert spawner.respawns >= 1
            # Replacements landed on the surviving host only.
            replacements = router.engines[2:]
            assert replacements
            assert all(h.host_id == "b" for h in replacements)
            for i, ((prompt, new), h) in enumerate(zip(reqs, handles)):
                assert h.done and h.finish_reason == "length", i
                want = _oneshot(params, config, prompt,
                                jax.random.PRNGKey(100 + i), new,
                                temperature=temperature)
                assert h.generated == want, (
                    f"request {i} diverged across the partition")
                assert counts[h.id] == len(h.generated), i

            # Partition-then-heal: the dial probe re-admits host a.
            assert spawner.dead_hosts == {"a"}
            for px in proxies:
                px.heal()
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and spawner.dead_hosts:
                router.poll_hosts()
                time.sleep(0.2)
            assert spawner.dead_hosts == set()
            for h in router.engines:
                h.close()
        finally:
            for px in proxies:
                px.close()
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
