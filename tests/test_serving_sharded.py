"""Sharded multi-chip serving engine: bit-parity across mesh shapes.

The suite's conftest forces 8 virtual CPU devices, so ``data:4`` and
``data:2,tp:2`` engines run IN-PROCESS in the default tier — no subprocess,
no TPU. The bar is the engine's exactness contract extended over the mesh:
every request's stream bit-identical to ``generate_cached(batch=1)`` —
greedy AND sampled — for ANY mesh shape, through chunked/batched prefill,
prefix-cache hits, watermark preemption, and cross-mesh migration; plus
compile-once (one decode program per (ServeConfig, mesh shape)) and the
shard-aware allocator invariants.
"""

from __future__ import annotations

import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig, parse_serve_mesh
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.serving import (
    BlockAllocator,
    PrefixCache,
    ServingEngine,
)

import pipelined_cases
from test_serving import _oneshot, _serve

MESHES = ["data:4", "data:2,tp:2"]


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    return [
        list(map(int, rng.integers(1, 256, size=n)))
        for n in (5, 11, 17, 3, 9, 26, 7, 13)
    ]


@pytest.fixture(scope="module")
def refs(tiny_params, tiny_config, prompts):
    """One-shot references per (sampling mode, request) — shared across the
    mesh shapes so the jitted reference compiles once per prompt shape."""
    import jax

    out = {}
    for temperature, top_k in ((0.0, None), (0.9, 5)):
        out[(temperature, top_k)] = [
            _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i), 8,
                     temperature=temperature, top_k=top_k)
            for i, p in enumerate(prompts)
        ]
    return out


def _run(params, config, serve, prompts, *, temperature=0.0, top_k=None,
         new=8):
    eng = ServingEngine(params, config, serve,
                        temperature=temperature, top_k=top_k)
    hs = [eng.submit(p, new, rng=i) for i, p in enumerate(prompts)]
    eng.run_until_idle(max_steps=3000)
    return [h.generated for h in hs], eng


# ------------------------------------------------------------ config/spec


class TestMeshSpec:
    def test_parse_forms(self):
        assert parse_serve_mesh("") == (1, 1)
        assert parse_serve_mesh("data:4") == (4, 1)
        assert parse_serve_mesh("data=2,tp=2") == (2, 2)
        assert parse_serve_mesh("tp:2") == (1, 2)
        assert ServeConfig(mesh="data:2").mesh_devices == 2

    @pytest.mark.parametrize("bad", [
        "fsdp:2", "data:x", "data:0", "data:2,data:2",
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_serve_mesh(bad)

    def test_divisibility_enforced(self):
        with pytest.raises(ValueError, match="max_batch"):
            ServeConfig(max_batch=3, mesh="data:2")
        with pytest.raises(ValueError, match="num_blocks"):
            ServeConfig(max_batch=4, num_blocks=33, mesh="data:2")
        with pytest.raises(ValueError, match="prefill_batch"):
            ServeConfig(max_batch=4, prefill_batch=5)

    def test_mesh_wants_more_devices_than_visible(self, tiny_params,
                                                  tiny_config):
        with pytest.raises(ValueError, match="devices"):
            ServingEngine(tiny_params, tiny_config,
                          _serve(max_batch=16, mesh="data:16"))


# ------------------------------------------------- shard-aware allocator


class TestShardedAllocator:
    def test_per_shard_free_lists(self):
        a = BlockAllocator(16, num_shards=4)   # 4 blocks per shard
        assert a.blocks_per_shard == 4
        assert a.available_in(0) == 3          # shard 0 hosts null block 0
        assert all(a.available_in(s) == 4 for s in (1, 2, 3))
        ids = a.alloc(4, shard=2)
        assert ids is not None
        assert all(a.shard_of(i) == 2 for i in ids)
        assert a.alloc(1, shard=2) is None     # shard 2 empty; others full
        assert a.available_in(1) == 4
        a.release(ids)
        assert a.available_in(2) == 4

    def test_release_returns_to_owning_shard(self):
        a = BlockAllocator(8, num_shards=2)
        ids = a.alloc(2, shard=1)
        a.release(ids)
        assert a.available_in(1) == 4
        assert a.available_in(0) == 3

    def test_shard_count_must_divide(self):
        with pytest.raises(ValueError, match="num_shards"):
            BlockAllocator(10, num_shards=4)

    def test_prefix_evict_respects_shard(self):
        a = BlockAllocator(8, num_shards=2)
        cache = PrefixCache(block_size=2)
        [b0] = a.alloc(1, shard=0)
        [b1] = a.alloc(1, shard=1)
        cache.insert([1, 2], 0, b0, a)
        cache.insert([3, 4], 0, b1, a)
        a.release([b0])
        a.release([b1])                        # both now cache-only
        assert cache.evict_one(a, shard=1)
        assert a.available_in(1) == 4          # b1 went home
        assert cache.evict_one(a, shard=1) is False  # only b0 left: foreign
        assert cache.evict_one(a, shard=0)


# ----------------------------------------------------------- bit-parity


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.9, 5)])
def test_sharded_whole_prefill_bit_parity(tiny_params, tiny_config, prompts,
                                          refs, mesh, temperature, top_k):
    """Whole-prompt prefill engine over the mesh: every stream == the
    single-device one-shot reference, greedy and sampled."""
    got, eng = _run(
        tiny_params, tiny_config,
        _serve(max_batch=8, num_blocks=64, mesh=mesh),
        prompts, temperature=temperature, top_k=top_k,
    )
    assert got == refs[(temperature, top_k)]
    assert eng._decode_fn._cache_size() == 1


@pytest.mark.parametrize("mesh", MESHES)
def test_sharded_chunked_batched_prefill_bit_parity(tiny_params, tiny_config,
                                                    prompts, refs, mesh):
    """Chunked prefill with multi-row batched admission over the mesh:
    bit-parity, one decode AND one chunk compile, and the batched
    dispatches actually fold multiple rows."""
    got, eng = _run(
        tiny_params, tiny_config,
        _serve(max_batch=8, num_blocks=64, mesh=mesh,
               prefill_chunk=8, prefill_batch=4),
        prompts, temperature=0.9, top_k=5,
    )
    assert got == refs[(0.9, 5)]
    assert eng._decode_fn._cache_size() == 1
    assert eng._chunk_fn._cache_size() == 1
    assert eng.stats["prefill_batched"] > 0


def test_batched_admission_fewer_dispatches(tiny_params, tiny_config,
                                            prompts):
    """Same trace, same chunk width: prefill_batch=4 must finish prefill in
    fewer dispatches than one-row-per-step admission (the whole point of
    multi-row admission), with identical streams."""
    base = dict(max_batch=8, num_blocks=64, mesh="data:4", prefill_chunk=8)
    got1, e1 = _run(tiny_params, tiny_config,
                    _serve(prefill_batch=1, **base), prompts)
    got4, e4 = _run(tiny_params, tiny_config,
                    _serve(prefill_batch=4, **base), prompts)
    assert got1 == got4
    assert e4.stats["prefill_dispatches"] < e1.stats["prefill_dispatches"]
    assert e4.stats["prefill_batched"] > 0
    assert e1.stats["prefill_batched"] == 0


@pytest.mark.parametrize("mesh", MESHES)
def test_sharded_scheduler_churn_bit_parity(tiny_params, tiny_config,
                                            prompts, refs, mesh):
    """Prefix cache + watermark preemption + chunked prefill under a tight
    pool: shard-local hit truncation, per-shard watermark floors and
    shard-local preemption must all preserve bit-parity (sampled)."""
    shared = prompts[5]              # 26 tokens: 3 full 8-token blocks
    reqs = [shared + p for p in prompts[:4]]
    import jax

    expect = [
        _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i), 8,
                 temperature=0.9, top_k=5)
        for i, p in enumerate(reqs)
    ]
    got, eng = _run(
        tiny_params, tiny_config,
        _serve(max_batch=4, num_blocks=24, mesh="data:2" if mesh == "data:4"
               else mesh, prefill_chunk=8, prefill_batch=2,
               prefix_cache=True, admission="watermark",
               watermark_blocks=1),
        reqs, temperature=0.9, top_k=5,
    )
    assert got == expect
    assert eng._decode_fn._cache_size() == 1


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("case", ["sampled", "eos"])
def test_sharded_pipelined_loop_serves_what_a_collecting_loop_does(
        case, mesh, tiny_params, tiny_config, prompts):
    """The sharded engine runs the same one-deep decode pipeline (its
    read-back is the tokens' all-gather; the step's outputs feed the next
    step where they lie, row-sharded): the ids are those of the same engine
    made to collect after every dispatch."""
    def make_engine(temperature=0.0, **serve):
        return ServingEngine(
            tiny_params, tiny_config,
            _serve(mesh=mesh, prefill_chunk=8, prefill_batch=2, **serve),
            temperature=temperature)

    pipelined_cases.run(case, make_engine, prompts[:4], squeeze=None)


def test_migration_across_mesh_shapes(tiny_params, tiny_config, prompts,
                                      refs):
    """extract_inflight from a data:4 engine mid-decode, adopt into a
    data:2,tp:2 engine: every stream completes bit-identically with zero
    re-emitted tokens (the serving fault-tolerance contract, now across
    DIFFERENT mesh shapes)."""
    serve_a = _serve(max_batch=8, num_blocks=64, mesh="data:4")
    serve_b = _serve(max_batch=8, num_blocks=64, mesh="data:2,tp:2")
    eng_a = ServingEngine(tiny_params, tiny_config, serve_a,
                          temperature=0.9, top_k=5)
    streams: dict[int, list[int]] = {}

    def on_token(req, tok):
        streams.setdefault(req.id, []).append(tok)

    hs = [eng_a.submit(p, 8, rng=i, on_token=on_token)
          for i, p in enumerate(prompts)]
    for _ in range(3):
        eng_a.step()
    moved = eng_a.extract_inflight()
    assert len(moved) == len(hs)
    eng_b = ServingEngine(tiny_params, tiny_config, serve_b,
                          temperature=0.9, top_k=5)
    for req in moved:
        eng_b.adopt(req)
    eng_b.run_until_idle(max_steps=3000)
    for h, ref in zip(hs, refs[(0.9, 5)]):
        assert h.generated == ref
        assert streams[h.id] == h.generated  # no re-emits, no gaps


def test_chaos_replica_kill_sharded_fleet(tiny_params, tiny_config, prompts,
                                          refs):
    """test_fault_tolerance's chaos bar on SHARDED replicas: kill a data:2
    replica mid-decode under chunked prefill + prefix cache; every migrated
    stream completes on the surviving data:2 replica bit-identically
    (sampled — the saved PRNG chain head must survive the sharded extract)
    with zero re-emitted tokens."""
    from gpt_2_distributed_tpu.resilience import FaultInjector
    from gpt_2_distributed_tpu.serving.frontend import (
        EngineDriver,
        ReplicaRouter,
    )

    serve = _serve(max_batch=4, num_blocks=32, mesh="data:2",
                   prefix_cache=True, prefill_chunk=8)
    router = ReplicaRouter(
        lambda: ServingEngine(tiny_params, tiny_config, serve,
                              temperature=0.9, top_k=5),
        replicas=2,
    )
    driver = EngineDriver(router, injector=FaultInjector(fail_at=(4, 0)))
    counts: dict[int, int] = {}

    def on_token(req, _tok):
        counts[req.id] = counts.get(req.id, 0) + 1

    hs = [driver.submit(p, 8, rng=i, on_token=on_token)
          for i, p in enumerate(prompts)]
    placed = {h.id: h.replica for h in hs}
    driver.drain()
    driver.close()
    assert router.replica_failures == 1
    assert router.n_failed == 1 and router.n_active == 1
    migrated = [h for h in hs if h.replica != placed[h.id]]
    assert migrated and router.migrated == len(migrated)
    for h, ref in zip(hs, refs[(0.9, 5)]):
        assert h.done and h.finish_reason == "length"
        assert list(h.generated) == ref, f"request {h.id} diverged"
        assert counts[h.id] == 8  # zero re-emitted tokens


# -------------------------------------------------------------- plumbing


def test_kv_pool_bytes_and_snapshot_keys(tiny_params, tiny_config):
    eng1 = ServingEngine(tiny_params, tiny_config,
                         _serve(max_batch=8, num_blocks=64))
    eng4 = ServingEngine(tiny_params, tiny_config,
                         _serve(max_batch=8, num_blocks=64, mesh="data:4"))
    assert eng4.kv_pool_bytes_per_device * 4 == eng1.kv_pool_bytes_per_device
    snap = eng4.metrics_snapshot()
    assert snap["serve_mesh_devices"] == 4.0
    assert snap["kv_pool_bytes_per_device"] == float(
        eng4.kv_pool_bytes_per_device
    )
    assert "prefill_batched" in snap


@pytest.mark.parametrize("mesh", ["data:2", "tp:2", "data:2,tp:2"])
def test_placed_weights_are_cast_and_still_head_sharded(
        tiny_params, tiny_config, mesh):
    """The mesh places the tree the engine holds - cast, then put: every
    matmul and embedding leaf bfloat16 on every device of the mesh, the qkv
    leaves split over 'tp' on their head axis as ``serve_param_pspecs`` has
    it, the LayerNorm leaves float32; ``weight_bytes`` counts one device's
    share."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from test_serving import LAYER_NORMS, _leaves

    one = ServingEngine(tiny_params, tiny_config, _serve(max_batch=8, num_blocks=64))
    eng = ServingEngine(tiny_params, tiny_config,
                        _serve(max_batch=8, num_blocks=64, mesh=mesh))
    held = _leaves(eng.params)
    assert sorted(n for n, a in held.items()
                  if a.dtype == jnp.float32) == sorted(LAYER_NORMS)
    assert all(len(a.sharding.device_set) == eng.mesh.size for a in held.values())
    tp = eng.mesh.shape["tp"]
    heads = P(None, None, None, "tp", None) if tp > 1 else P()
    assert held["attn_qkv_w"].sharding.spec == heads
    assert held["attn_qkv_w"].dtype == jnp.bfloat16
    assert held["mlp_fc_w"].sharding.is_fully_replicated
    qkv = held["attn_qkv_w"].nbytes + held["attn_qkv_b"].nbytes
    assert eng.weight_bytes == one.weight_bytes - qkv + qkv // tp
    assert eng.metrics_snapshot()["weight_bytes"] == float(eng.weight_bytes)
    assert all(not a.is_deleted() for a in jax.tree_util.tree_leaves(tiny_params))


def test_submit_rejects_over_shard_capacity(tiny_params, tiny_config):
    # 32 blocks over 4 shards = 7 usable on the smallest shard; a request
    # needing 8 could never be admitted even with the pool idle.
    eng = ServingEngine(tiny_params, tiny_config,
                        _serve(max_batch=4, num_blocks=32, mesh="data:4"))
    with pytest.raises(ValueError, match="data shard"):
        eng.submit(list(range(1, 33)), 32)
    eng.submit(list(range(1, 17)), 8)  # 3 blocks: fits one shard
