"""Serving fault tolerance: replica failure containment, bit-exact
request migration, deadlines, and the step watchdog.

The exactness bar is the same one test_serving and test_frontend enforce:
a replica failure may cost TIME, never TOKENS. Streams migrated off a
killed replica must stay bit-identical to ``generate_cached(batch=1)`` —
greedy and sampled — with zero re-emitted tokens, while the driver loop
keeps the rest of the fleet stepping.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.models.decode import generate_cached
from gpt_2_distributed_tpu.resilience import (
    FaultInjector,
    InjectedFault,
    PreemptionHandler,
    parse_fault_spec,
)
from gpt_2_distributed_tpu.serving import ServingEngine
from gpt_2_distributed_tpu.serving.frontend import (
    Autoscaler,
    EngineDriver,
    ReplicaRouter,
    StepWatchdog,
)
from gpt_2_distributed_tpu.serving.frontend.server import FrontendServer


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


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


def _serve(**kw):
    base = dict(max_batch=4, block_size=8, num_blocks=32, attn_impl="xla")
    base.update(kw)
    return ServeConfig(**base)


def _oneshot(params, config, prompt, key, new, **kw):
    out = generate_cached(
        params, config, jnp.asarray([prompt], jnp.int32), key,
        max_new_tokens=new, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def _fleet(params, config, *, replicas=2, serve=None, temperature=0.0,
           top_k=None, **router_kw):
    serve = serve or _serve(prefix_cache=True, prefill_chunk=8)
    return ReplicaRouter(
        lambda: ServingEngine(params, config, serve,
                              temperature=temperature, top_k=top_k),
        replicas=replicas, **router_kw,
    )


def _http(port, method, path, payload=None, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    body = json.dumps(payload) if payload is not None else None
    c.request(method, path, body,
              {"Content-Type": "application/json"} if body else {})
    r = c.getresponse()
    raw = r.read()
    headers = dict(r.getheaders())
    c.close()
    return r.status, (json.loads(raw) if raw else None), headers


def _sse(port, payload, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/completions",
              json.dumps({**payload, "stream": True}),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    status = r.status
    chunks, saw_done = [], False
    for raw_line in r:
        line = raw_line.decode().rstrip("\r\n")
        if line == "data: [DONE]":
            saw_done = True
        elif line.startswith("data: "):
            chunks.append(json.loads(line[len("data: "):]))
    c.close()
    return status, chunks, saw_done


class _Server:
    """FrontendServer over a caller-built driver, run()ning off-thread —
    unlike test_frontend's helper, the driver (and so the injector,
    watchdog and autoscaler) is fully under test control."""

    def __init__(self, driver, **kw):
        self.driver = driver
        self.srv = FrontendServer(driver, port=0, model_name="tiny",
                                  default_new=8, **kw)
        self.thread = threading.Thread(target=self.srv.run, daemon=True)

    def __enter__(self):
        self.thread.start()
        assert self.srv.ready.wait(60), "server never bound"
        return self

    @property
    def port(self):
        return self.srv.port

    def __exit__(self, *exc):
        if self.thread.is_alive():
            self.srv.shutdown()
            self.thread.join(60)
        assert not self.thread.is_alive(), "server thread leaked"


# ------------------------------------------------------- injector units


def test_parse_fault_spec():
    assert parse_fault_spec("20", "--f") == (20, None)
    assert parse_fault_spec("20:1", "--f") == (20, 1)
    for bad in ("0", "a", "1:2:3", "5:-1", ""):
        with pytest.raises(ValueError, match="--f"):
            parse_fault_spec(bad, "--f")


def test_fault_injector_fires_once_per_fault():
    inj = FaultInjector(fail_at=(3, 0))
    inj.tick(2, 0)            # before the trigger step
    inj.tick(3, 1)            # wrong replica
    with pytest.raises(InjectedFault):
        inj.tick(5, 0)        # >= semantics: a late replica can't dodge
    inj.tick(6, 0)            # fired once, never again

    inj = FaultInjector(exception_at=2)
    with pytest.raises(InjectedFault):
        inj.tick(2, 7)        # replica-agnostic
    inj.tick(3, 7)


def test_fault_injector_hang_released_and_expired():
    inj = FaultInjector(hang_at=(1, 0), hang_max_s=30.0)
    inj.release_hangs()       # what the watchdog trip does
    t0 = time.monotonic()
    with pytest.raises(InjectedFault, match="released"):
        inj.tick(1, 0)
    assert time.monotonic() - t0 < 5

    inj = FaultInjector(hang_at=(1, 0), hang_max_s=0.05)
    with pytest.raises(InjectedFault, match="expired"):
        inj.tick(1, 0)


def test_step_watchdog_unit():
    with pytest.raises(ValueError):
        StepWatchdog(0, lambda r: None)
    fired = []
    wd = StepWatchdog(0.08, fired.append).start()
    try:
        wd.arm(3)
        deadline = time.monotonic() + 5
        while not fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fired == [3] and wd.trips == 1
        time.sleep(0.25)              # one trip per arm: no refire
        assert fired == [3]
        wd.arm(1)
        wd.disarm()                   # disarmed in time: never fires
        time.sleep(0.25)
        assert fired == [3]
    finally:
        wd.stop()


class _ReplaceFake:
    """Minimal router surface for the autoscaler replacement path."""

    def __init__(self):
        self.n_active = 1             # one below the floor of 2
        self.max_batch = 4
        self.max_replicas = 3
        self.shed_count = 0
        self.slo_violations = 0
        self.replica_failures = 1

    def total_queue_depth(self):
        return 0

    def total_occupancy(self):
        return 0

    def grow(self):
        self.n_active += 1
        return self.n_active - 1      # the revived/new replica index


def test_autoscaler_replaces_below_floor_bypassing_hysteresis():
    r = _ReplaceFake()
    a = Autoscaler(r, min_replicas=2, max_replicas=3, grow_after=3,
                   cooldown=5)
    assert a.tick() == "replace"      # no streak, no cooldown wait
    assert r.n_active == 2 and a.replacements == 1 and a.scale_ups == 1
    assert a.tick() is None           # back at the floor: normal hysteresis


# --------------------------------------------- chaos: replica kill mid-run


def _run_chaos_fleet(params, config, *, temperature=0.0, top_k=None,
                     fail_step=4):
    """Kill replica 0 mid-decode under shared prefixes + chunked prefill;
    return (handles, refs, token counts, router, driver)."""
    router = _fleet(params, config, temperature=temperature, top_k=top_k)
    driver = EngineDriver(router, injector=FaultInjector(fail_at=(fail_step, 0)))
    shared = [11] * 8                       # one full block: prefix traffic
    prompts = ([shared + [50 + i] for i in range(4)]
               + [[1, 2, 3], [9, 8, 7, 6]])
    news = [10, 12, 9, 11, 8, 10]
    counts: dict[int, int] = {}

    def on_token(req, _tok, _c=counts):
        _c[req.id] = _c.get(req.id, 0) + 1

    handles = [driver.submit(p, n, rng=i, on_token=on_token)
               for i, (p, n) in enumerate(zip(prompts, news))]
    placed = {h.id: h.replica for h in handles}
    driver.drain()
    driver.close()
    refs = [_oneshot(params, config, p, jax.random.PRNGKey(i), n,
                     temperature=temperature, top_k=top_k)
            for i, (p, n) in enumerate(zip(prompts, news))]

    assert router.replica_failures == 1
    assert router.n_failed == 1 and router.n_active == 1
    migrated = [h for h in handles if h.replica != placed[h.id]]
    assert migrated and router.migrated == len(migrated)
    for h, ref, n in zip(handles, refs, news):
        assert h.done and h.finish_reason == "length"
        assert list(h.generated) == ref, f"request {h.id} diverged"
        assert counts[h.id] == n        # zero re-emitted tokens
    # The loop survived: the surviving replica keeps serving new work.
    h2 = driver.submit([7, 7, 7], 6, rng=99)
    driver.drain()
    assert list(h2.generated) == _oneshot(
        params, config, [7, 7, 7], jax.random.PRNGKey(99), 6,
        temperature=temperature, top_k=top_k,
    )


def test_chaos_replica_kill_greedy(tiny_params, tiny_config):
    _run_chaos_fleet(tiny_params, tiny_config)


def test_chaos_replica_kill_sampled(tiny_params, tiny_config):
    # Migration restores the saved per-slot PRNG chain head: sampled
    # streams must replay generate_cached's exact split order too.
    _run_chaos_fleet(tiny_params, tiny_config, temperature=0.9, top_k=40)


def test_watchdog_detects_hang_and_migrates(tiny_params, tiny_config):
    router = _fleet(tiny_params, tiny_config)
    # Warm every replica's prefill/decode compiles first: a cold XLA
    # compile inside step() can exceed the watchdog budget on CPU, and
    # the watchdog must only ever fire on the injected hang.
    for eng in router.engines:
        eng.submit([7] * 12, 2, rng=0)      # chunk + remainder widths
        eng.run_until_idle()
        eng.clear_prefix_cache()
    injector = FaultInjector(hang_at=(3, 0), hang_max_s=30.0)
    driver = EngineDriver(router, watchdog_timeout_s=1.0, injector=injector)
    prompts = [[1, 2, 3, i] for i in range(4)]
    handles = [driver.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
    driver.drain()
    driver.close()
    assert driver.watchdog_trips == 1
    assert router.replica_failures == 1 and router.n_active == 1
    for i, (h, p) in enumerate(zip(handles, prompts)):
        assert list(h.generated) == _oneshot(
            tiny_params, tiny_config, p, jax.random.PRNGKey(i), 8,
            temperature=0.0,
        )


# -------------------------------------------------------------- deadlines


def test_request_timeout_evicts_slotted_and_frees_blocks(
        tiny_params, tiny_config):
    eng = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0)
    eng.submit([9, 9, 9], 4, rng=0)         # compile warmup
    eng.run_until_idle()
    avail0 = eng.allocator.available

    h = eng.submit([1, 2, 3], 16, rng=1, timeout_s=30.0)
    while len(h.generated) < 2:             # admitted and decoding
        eng.step()
    h.deadline = time.monotonic() - 1.0     # force overdue, no sleeps
    eng.step()
    assert h.done and h.finish_reason == "timeout"
    assert 2 <= len(h.generated) < 16
    assert eng.allocator.available == avail0    # KV blocks freed
    assert eng.stats["timeouts"] == 1
    # The engine keeps serving after the eviction.
    h2 = eng.submit([4, 5, 6], 6, rng=2)
    eng.run_until_idle()
    assert list(h2.generated) == _oneshot(
        tiny_params, tiny_config, [4, 5, 6], jax.random.PRNGKey(2), 6,
        temperature=0.0,
    )


def test_request_timeout_evicts_queued_before_admission(
        tiny_params, tiny_config):
    eng = ServingEngine(tiny_params, tiny_config, _serve(max_batch=1),
                        temperature=0.0)
    eng.submit([9, 9, 9], 2, rng=0)
    eng.run_until_idle()
    a = eng.submit([1, 2, 3], 10, rng=1)        # occupies the only slot
    eng.step()
    b = eng.submit([4, 5, 6], 10, rng=2, timeout_s=0.0)
    eng.step()                                  # sweep runs before admit
    assert b.done and b.finish_reason == "timeout" and not b.generated
    eng.run_until_idle()
    assert a.done and len(a.generated) == 10    # A was never disturbed
    assert eng.stats["timeouts"] == 1

    with pytest.raises(ValueError):
        eng.submit([1], 2, rng=0, timeout_s=-1.0)


def test_http_timeout_maps_to_504(tiny_params, tiny_config):
    router = _fleet(tiny_params, tiny_config)
    with _Server(EngineDriver(router)) as s:
        status, body, _ = _http(
            s.port, "POST", "/v1/completions",
            {"prompt_ids": [1, 2, 3], "max_tokens": 8, "seed": 0,
             "timeout_s": 0},
        )
        assert status == 504
        assert body["error"]["type"] == "timeout"
        # Bad deadline is a 400, not a submit.
        status, body, _ = _http(
            s.port, "POST", "/v1/completions",
            {"prompt_ids": [1, 2], "max_tokens": 4, "timeout_s": -2},
        )
        assert status == 400
        # The fleet keeps serving afterwards.
        ref = _oneshot(tiny_params, tiny_config, [1, 2, 3],
                       jax.random.PRNGKey(0), 8, temperature=0.0)
        status, body, _ = _http(
            s.port, "POST", "/v1/completions",
            {"prompt_ids": [1, 2, 3], "max_tokens": 8, "seed": 0},
        )
        assert status == 200
        assert body["choices"][0]["token_ids"] == ref


# ------------------------------------------- healthz / autoscaler replace


def _concurrent_sse(port, payloads):
    results: dict[int, tuple] = {}
    threads = [
        threading.Thread(
            target=lambda i=i, pl=pl: results.__setitem__(i, _sse(port, pl))
        )
        for i, pl in enumerate(payloads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    return [results[i] for i in range(len(payloads))]


def test_healthz_degraded_after_replica_failure(tiny_params, tiny_config):
    router = _fleet(tiny_params, tiny_config)
    driver = EngineDriver(router, injector=FaultInjector(fail_at=(4, 0)))
    with _Server(driver) as s:
        status, body, _ = _http(s.port, "GET", "/healthz")
        assert status == 200 and body["status"] == "ok"
        prompts = [[1, 2, 3, i] for i in range(4)]
        outs = _concurrent_sse(
            s.port, [{"prompt_ids": p, "max_tokens": 12, "seed": i}
                     for i, p in enumerate(prompts)],
        )
        for i, (p, (st, chunks, done)) in enumerate(zip(prompts, outs)):
            assert st == 200 and done
            toks = [c["choices"][0]["token"] for c in chunks
                    if c["choices"][0]["token"] is not None]
            assert toks == _oneshot(tiny_params, tiny_config, p,
                                    jax.random.PRNGKey(i), 12,
                                    temperature=0.0), f"stream {i}"
        status, body, _ = _http(s.port, "GET", "/healthz")
        assert status == 200 and body["status"] == "degraded"
        assert body["failed_replicas"] == 1
        assert body["replicas"] == 1 and body["target_replicas"] == 2
        status, m, _ = _http(s.port, "GET", "/metrics")
        assert m["failed_replicas"] == 1
        assert m["replica_failures"] == 1.0
        assert m["requests_migrated"] >= 1.0


def test_autoscaler_replaces_failed_replica_healthz_recovers(
        tiny_params, tiny_config):
    router = _fleet(tiny_params, tiny_config, max_replicas=3)
    scaler = Autoscaler(router, min_replicas=2, max_replicas=3)
    driver = EngineDriver(router, autoscaler=scaler, autoscale_every=1,
                          injector=FaultInjector(fail_at=(4, 0)))
    with _Server(driver) as s:
        prompts = [[1, 2, 3, i] for i in range(4)]
        outs = _concurrent_sse(
            s.port, [{"prompt_ids": p, "max_tokens": 12, "seed": i}
                     for i, p in enumerate(prompts)],
        )
        for i, (p, (st, chunks, done)) in enumerate(zip(prompts, outs)):
            assert st == 200 and done
            toks = [c["choices"][0]["token"] for c in chunks
                    if c["choices"][0]["token"] is not None]
            assert toks == _oneshot(tiny_params, tiny_config, p,
                                    jax.random.PRNGKey(i), 12,
                                    temperature=0.0), f"stream {i}"
        # The autoscaler replaced the dead replica: back at target size.
        status, body, _ = _http(s.port, "GET", "/healthz")
        assert status == 200 and body["status"] == "ok", body
        status, m, _ = _http(s.port, "GET", "/metrics")
        assert m["serve_replicas"] == 2
        assert m["replica_failures"] == 1.0
        assert m["autoscale"]["replacements"] == 1


# ------------------------------------------------- drain/failure races


def test_replica_failure_during_drain_completes_streams(
        tiny_params, tiny_config):
    handler = PreemptionHandler(signals=())
    router = _fleet(tiny_params, tiny_config)
    driver = EngineDriver(router, preemption=handler,
                          injector=FaultInjector(fail_at=(4, 0)))
    prompts = [[1, 2, 3, i] for i in range(4)]
    handles = [driver.submit(p, 10, rng=i) for i, p in enumerate(prompts)]
    driver.step()
    handler.trigger("test SIGTERM")     # drain begins BEFORE the failure
    driver.step()
    assert driver.draining
    driver.drain()                      # replica 0 dies at step 4, mid-drain
    assert router.replica_failures == 1
    for i, (h, p) in enumerate(zip(handles, prompts)):
        assert h.done and h.finish_reason == "length"
        assert list(h.generated) == _oneshot(
            tiny_params, tiny_config, p, jax.random.PRNGKey(i), 10,
            temperature=0.0,
        ), f"stream {i} dropped tokens across the drain/failure race"


def test_sigterm_mid_migration_completes_streams(tiny_params, tiny_config):
    handler = PreemptionHandler(signals=())
    router = _fleet(tiny_params, tiny_config)
    driver = EngineDriver(router, preemption=handler,
                          injector=FaultInjector(fail_at=(3, 0)))
    prompts = [[1, 2, 3, i] for i in range(4)]
    handles = [driver.submit(p, 10, rng=i) for i, p in enumerate(prompts)]
    for _ in range(50):                 # step until the failure lands
        driver.step()
        if router.replica_failures:
            break
    assert router.replica_failures == 1
    handler.trigger("supervisor TERM")  # SIGTERM with migrations queued
    driver.drain()
    assert driver.draining
    for i, (h, p) in enumerate(zip(handles, prompts)):
        assert h.done and h.finish_reason == "length"
        assert list(h.generated) == _oneshot(
            tiny_params, tiny_config, p, jax.random.PRNGKey(i), 10,
            temperature=0.0,
        ), f"stream {i}"


# ---------------------------------------------- shutdown join abandonment


class _StubRouter:
    n_active = 1
    policy = "affinity"


class _StubDriver:
    router = _StubRouter()

    def stop(self):
        pass


class _WedgedServer(FrontendServer):
    """Reports drained but the driver thread never exits — the wedged
    case the join timeout exists for."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.release = threading.Event()

    def _drive(self, loop, drained):
        loop.call_soon_threadsafe(drained.set)
        self.release.wait(60)


def test_abandoned_driver_thread_is_loud_and_exits_nonzero(capsys):
    srv = _WedgedServer(_StubDriver(), port=0, join_timeout_s=0.2)
    t = threading.Thread(target=srv.run, daemon=True)
    t.start()
    t.join(30)
    try:
        assert not t.is_alive(), "run() never returned"
        assert srv.exit_code == 1
        err = capsys.readouterr().err
        assert "STILL ALIVE" in err and "--shutdown_join_s" in err
    finally:
        srv.release.set()


def test_clean_drain_exits_zero(tiny_params, tiny_config, capsys):
    router = _fleet(tiny_params, tiny_config)
    with _Server(EngineDriver(router)) as s:
        _http(s.port, "POST", "/v1/completions",
              {"prompt_ids": [1, 2, 3], "max_tokens": 4, "seed": 0})
    assert s.srv.exit_code == 0
    assert "drained, exiting 0" in capsys.readouterr().err


# ------------------------------------------------------------ fault flags


@pytest.mark.parametrize("cli", ["serve", "frontend"])
@pytest.mark.parametrize("flags, named", [
    (("--inject_replica_fail_at", "0"), "STEP must be >= 1"),
    (("--inject_replica_fail_at", "1:2:3"), "STEP or STEP:REPLICA"),
    (("--inject_replica_hang_at", "5"), "--watchdog_timeout_s"),
    (("--request_timeout_s", "-1"), "--request_timeout_s"),
], ids=" ".join)
def test_fault_flags_rejected_jax_free(run_cli_jax_free, cli, flags, named):
    # serve.make_injector (resilience.parse_fault_spec under it) runs with
    # the other parse-time checks, before jax loads.
    r = run_cli_jax_free(cli, *flags)
    assert r.returncode == 2, r.stderr[-300:]
    assert named in r.stderr, r.stderr[-300:]
