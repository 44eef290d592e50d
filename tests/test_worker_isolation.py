"""Process-isolated serving replicas: the worker RPC plane, the request
wire form, and bit-exact migration across a real process boundary.

The exactness bar is unchanged from test_serving/test_fault_tolerance:
a stream served by a subprocess worker — or migrated off one killed with
a REAL signal mid-decode — must stay bit-identical to
``generate_cached(batch=1)``, greedy and sampled, with zero re-emitted
tokens. The RPC plane adds its own contracts on top: frames survive the
socket byte-for-byte, version tags are rejected loudly, flag validation
never touches jax, and the respawn budget gives up like supervise.sh.
"""

from __future__ import annotations

import json
import signal
import socket
import struct
import sys
import time

import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig, validate_worker_flags
from gpt_2_distributed_tpu.serving.frontend.rpc import (
    MAX_FRAME_BYTES,
    WireError,
    recv_msg,
    send_msg,
)
from gpt_2_distributed_tpu.serving.frontend.worker import (
    WorkerSpawner,
    spawner_from_args,
)


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


# --------------------------------------------------------------- framing


def test_rpc_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        msg = {"op": "step", "nested": {"rid": 7, "toks": [1, 2, 3]},
               "f": 1.5, "none": None, "uni": "héllo"}
        send_msg(a, msg)
        assert recv_msg(b) == msg
        # Both directions, back to back — framing must not desync.
        send_msg(b, {"ok": True})
        send_msg(b, {"ok": False, "n": 2})
        assert recv_msg(a) == {"ok": True}
        assert recv_msg(a) == {"ok": False, "n": 2}
    finally:
        a.close()
        b.close()


def test_rpc_rejects_garbage_and_eof():
    a, b = socket.socketpair()
    try:
        # Malformed JSON inside a well-formed frame.
        raw = b"{not json"
        a.sendall(struct.pack(">I", len(raw)) + raw)
        with pytest.raises(WireError, match="malformed"):
            recv_msg(b)
        # A frame claiming to be larger than the cap is refused before
        # any allocation.
        a.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1))
        with pytest.raises(WireError, match="frame"):
            recv_msg(b)
        # Top-level non-dict payloads are protocol violations.
        raw = json.dumps([1, 2, 3]).encode()
        a.sendall(struct.pack(">I", len(raw)) + raw)
        with pytest.raises(WireError, match="expected object"):
            recv_msg(b)
        # Peer death mid-conversation surfaces as WireError, not a hang.
        a.close()
        with pytest.raises(WireError, match="EOF|closed"):
            recv_msg(b)
    finally:
        b.close()


# ------------------------------------------------------ request wire form


def _wire_handle():
    from gpt_2_distributed_tpu.serving.engine import RequestHandle

    h = RequestHandle(41, [5, 6, 7], 12)
    h.generated = [9, 8, 7]
    h._key = np.asarray([123456789, 987654321], np.uint32)
    h._pending_token = 7
    h.deadline = 12345.6
    h.submit_time = 12000.0
    h.first_token_time = 12000.5
    h.queue_wait_ms = 3.25
    h.preemptions = 1
    h.resumes = 1
    h.prefix_cached_tokens = 8
    return h


def test_request_wire_roundtrip_is_stable():
    from gpt_2_distributed_tpu.serving.engine import (
        REQUEST_WIRE_VERSION,
        RequestHandle,
    )

    h = _wire_handle()
    w = h.to_wire()
    assert w["v"] == REQUEST_WIRE_VERSION
    # The wire form must survive actual JSON serialization — it IS what
    # crosses the socket on extract/adopt.
    w2 = json.loads(json.dumps(w))
    r = RequestHandle.from_wire(w2)
    assert (r.id, r.prompt, r.max_new_tokens) == (41, [5, 6, 7], 12)
    assert r.generated == [9, 8, 7]
    assert r._pending_token == 7
    assert r._key.dtype == np.uint32
    assert [int(k) for k in r._key] == [123456789, 987654321]
    assert r.deadline == 12345.6
    assert (r.preemptions, r.resumes, r.prefix_cached_tokens) == (1, 1, 8)
    # Round-trip stability: re-serializing the rebuilt handle yields the
    # identical wire dict (nothing drifts through a double migration).
    assert r.to_wire() == w


def test_request_wire_none_key_roundtrip():
    from gpt_2_distributed_tpu.serving.engine import RequestHandle

    h = RequestHandle(1, [2, 3], 4)   # queued: no key captured yet
    r = RequestHandle.from_wire(json.loads(json.dumps(h.to_wire())))
    assert r._key is None and r.generated == [] and r._pending_token is None


def test_request_wire_version_rejected():
    from gpt_2_distributed_tpu.serving.engine import RequestHandle

    w = _wire_handle().to_wire()
    w["v"] = 99
    with pytest.raises(ValueError, match="wire version"):
        RequestHandle.from_wire(w)


# ------------------------------------------------- jax-free flag checks


@pytest.mark.parametrize("cli", ["serve", "frontend"])
@pytest.mark.parametrize("flags, named", [
    (("--placement", "bogus"), "--placement"),
    (("--placement", "subprocess", "--worker_max_respawns", "-1"),
     "--worker_max_respawns"),
    (("--placement", "subprocess", "--worker_respawn_backoff_s", "-1"),
     "--worker_respawn_backoff_s"),
    (("--placement", "subprocess", "--worker_rpc_timeout_s", "0"),
     "--worker_rpc_timeout_s"),
    (("--placement", "subprocess", "--worker_heartbeat_s", "0"),
     "--worker_heartbeat_s"),
    (("--placement", "subprocess", "--worker_connect_timeout_s", "0"),
     "--worker_connect_timeout_s"),
], ids=" ".join)
def test_worker_flags_rejected_jax_free(run_cli_jax_free, cli, flags, named):
    """Both CLIs refuse bad placement/worker flags at parse time, with a
    poisoned jax on PYTHONPATH proving validation never pays the jax
    import."""
    r = run_cli_jax_free(cli, *flags)
    assert r.returncode != 0
    assert named in r.stderr, r.stderr[-300:]


def test_validate_worker_flags_accepts_defaults():
    import argparse

    p = argparse.ArgumentParser()
    ns = argparse.Namespace(
        placement="subprocess", worker_max_respawns=3,
        worker_respawn_backoff_s=2.0, worker_rpc_timeout_s=300.0,
        worker_heartbeat_s=1.0, worker_connect_timeout_s=120.0,
    )
    validate_worker_flags(p, ns)   # must not raise


# ----------------------------------------------------- respawn budget


def test_spawner_respawn_budget_exhaustion():
    """A spawner whose budget is spent raises BEFORE spawning anything —
    supervise.sh's give-up-loudly semantics, and the RuntimeError the
    router/autoscaler containment paths are tested to absorb."""
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = WorkerSpawner(
        [sys.executable, "-c", "raise SystemExit('never spawned')"],
        serve, initial_replicas=1, max_respawns=0, respawn_backoff_s=0.0,
    )

    class FakeRouter:
        n_failed = 1

    sp.router = FakeRouter()
    with pytest.raises(RuntimeError, match="respawn budget"):
        sp()
    assert sp.spawns == 0 and sp.respawns == 0


def test_spawner_counts_initial_spawns_without_router():
    """Before a router is attached (or with none at all), the first
    ``initial_replicas`` calls are initial spawns, later ones respawns."""
    serve = ServeConfig(max_batch=2, block_size=8, num_blocks=8)
    sp = WorkerSpawner([sys.executable], serve, initial_replicas=2,
                       max_respawns=1, respawn_backoff_s=0.0)
    assert not sp._is_respawn()
    sp.spawns = 1
    assert not sp._is_respawn()
    sp.spawns = 2
    assert sp._is_respawn()


# ------------------------------------------- real workers on CPU (jax)


def _worker_args(extra=()):
    """Parsed gpt2-tpu-serve args for the tiny config — the same flag
    namespace all three CLIs hand to spawner_from_args."""
    from gpt_2_distributed_tpu.serving.serve import build_argparser

    p = build_argparser()
    return p.parse_args([
        "--init_random", "--model", "124M", "--n_layer", "2",
        "--n_embd", "32", "--n_head", "2", "--vocab_size", "257",
        "--seq_len", "64", "--max_batch", "4", "--block_size", "8",
        "--num_blocks", "32", "--attn_impl", "xla", "--device", "cpu",
        "--placement", "subprocess", "--requests", "-", *extra,
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

    from gpt_2_distributed_tpu.models.decode import generate_cached

    key = rng if hasattr(rng, "dtype") else jax.random.PRNGKey(rng)
    out = generate_cached(
        params, config, jnp.asarray([prompt], jnp.int32), key,
        max_new_tokens=new, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def test_worker_round_trip_and_extract_adopt():
    """One real worker process: submitted streams match
    ``generate_cached(batch=1)`` token-for-token, and a request extracted
    mid-flight crosses the wire and finishes bit-identically in an
    in-process engine — the single-worker core of migration."""
    from gpt_2_distributed_tpu.serving import ServingEngine

    args = _worker_args(["--temperature", "0"])
    config, params, serve = _model_and_serve(args)
    spawner = spawner_from_args(args, serve, initial_replicas=1)
    h = spawner()
    try:
        streams = {}
        for i, (prompt, new) in enumerate([([5, 6, 7], 6), ([9, 10], 8)]):
            toks = []
            streams[i] = (prompt, new, toks)
            h.submit(prompt, new, rng=i, rid=i,
                     on_token=lambda _h, t, acc=toks: acc.append(t))
        while h.has_work():
            h.step()
        for i, (prompt, new, toks) in streams.items():
            assert toks == _oneshot(params, config, prompt, i, new,
                                    temperature=0.0), i

        # Mid-flight extraction: step a few, pull the wire form, adopt
        # into an IN-PROCESS engine, finish, compare to a clean replay.
        toks = []
        mirror = h.submit([2, 3, 4], 8, rng=7, rid=50,
                          on_token=lambda _h, t: toks.append(t))
        h.step()
        h.step()
        got = h.extract_inflight()          # terminal: worker shuts down
        assert [r.id for r in got] == [50]
        assert got[0] is mirror and not got[0].done
        eng = ServingEngine(params, config, serve, temperature=0.0)
        eng.adopt(got[0])
        eng.run_until_idle()
        assert mirror.done and mirror.finish_reason == "length"
        assert toks == _oneshot(params, config, [2, 3, 4], 7, 8,
                                temperature=0.0)
    finally:
        h.close()


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 1.0],
                         ids=["greedy", "sampled"])
def test_sigkill_migration_bit_exact(temperature):
    """Real SIGKILL mid-decode on a subprocess fleet: the driver contains
    the corpse, migrates its streams off the host-side mirrors, the
    autoscaler respawns a replacement — and every stream still finishes
    bit-identical to ``generate_cached(batch=1)``."""
    import jax

    from gpt_2_distributed_tpu.resilience import FaultInjector
    from gpt_2_distributed_tpu.serving.frontend import (
        Autoscaler,
        EngineDriver,
        ReplicaRouter,
    )

    args = _worker_args(["--temperature", str(temperature),
                         "--worker_respawn_backoff_s", "0.1"])
    config, params, serve = _model_and_serve(args)
    spawner = spawner_from_args(args, serve, initial_replicas=2)
    router = ReplicaRouter(spawner, replicas=2, max_replicas=3,
                           policy="round_robin")
    spawner.router = router
    scaler = Autoscaler(router, min_replicas=2, max_replicas=3)
    injector = FaultInjector(
        kill_at=(4, 0),
        kill_fn=lambda r: router.engines[r].kill(signal.SIGKILL),
    )
    driver = EngineDriver(router, autoscaler=scaler, autoscale_every=10,
                          injector=injector)
    reqs = [([5, 6, 7], 8), ([9, 10], 10), ([1, 2, 3, 4], 8),
            ([11, 12], 12)]
    counts: dict[int, int] = {}
    handles = [
        driver.submit(prompt, new, rng=jax.random.PRNGKey(100 + i),
                      on_token=lambda rh, _t: counts.__setitem__(
                          rh.id, counts.get(rh.id, 0) + 1))
        for i, (prompt, new) in enumerate(reqs)
    ]
    while driver.has_work():
        driver.step()
    driver.close()
    assert injector.kill_fired
    assert router.replica_failures == 1
    assert router.migrated >= 1
    assert spawner.respawns == 1        # below-min replacement happened
    for i, ((prompt, new), h) in enumerate(zip(reqs, handles)):
        assert h.done and h.finish_reason == "length"
        want = _oneshot(params, config, prompt, jax.random.PRNGKey(100 + i),
                        new, temperature=temperature)
        assert h.generated == want, f"request {i} diverged after SIGKILL"
        # zero re-emission: exactly one on_token per generated token
        assert counts[h.id] == len(h.generated), i


@pytest.mark.slow
def test_sharded_worker_mesh_parity():
    """A ``data:2`` worker mesh behind the RPC plane streams the same
    tokens as an in-process engine on the identical sharded config — the
    process boundary composes with PR 17 mesh sharding untouched."""
    from gpt_2_distributed_tpu.serving import ServingEngine

    args = _worker_args(["--temperature", "0", "--serve_mesh", "data:2",
                         "--max_batch", "4"])
    config, params, serve = _model_and_serve(args)
    assert serve.mesh == "data:2" and serve.mesh_devices == 2
    spawner = spawner_from_args(args, serve, initial_replicas=1)
    h = spawner()
    try:
        ref = ServingEngine(params, config, serve, temperature=0.0)
        reqs = [([5, 6, 7], 6), ([9, 10], 8), ([1, 2, 3, 4], 6)]
        got, want = {}, {}
        for i, (prompt, new) in enumerate(reqs):
            tw, tr = [], []
            got[i], want[i] = tw, tr
            h.submit(prompt, new, rng=i, rid=i,
                     on_token=lambda _h, t, acc=tw: acc.append(t))
            ref.submit(prompt, new, rng=i, rid=i,
                       on_token=lambda _h, t, acc=tr: acc.append(t))
        while h.has_work():
            h.step()
        ref.run_until_idle()
        assert got == want
    finally:
        h.close()
