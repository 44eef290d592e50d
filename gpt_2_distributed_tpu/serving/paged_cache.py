"""Paged KV-cache plumbing: the block pool, its allocator, and the device
writes that move K/V into pool blocks.

Layout: one preallocated buffer per K and V, ``[L, num_blocks, H,
block_size, D]`` — layer-stacked to mirror the parameter pytree,
block-paged on the second axis so sequences of different lengths share the
buffer through per-sequence block tables instead of per-shape contiguous
allocations.

A pool has ONE device layout, from ``ServingEngine.k_pool`` through every
program's entry, layer loop, K/V write, attention read and exit: row-major,
which is how the paged kernel's Mosaic call reads it. Three things keep it:

* the array is STORED with its block axis split, ``[L, N1, N2, H, bs, D]``
  (``pool_shape``; ``as_blocks`` is the free ``[L, N1*N2, H, bs, D]`` view
  every program works on). The TPU compiler stores an array whose minor
  dimension is D = 64 with another dimension minor-most if one longer than
  64 exists — ``[L, 257, H, bs, D]`` gets the BLOCK axis minor-most, and a
  program that wants it row-major re-lays out the pool on entry and on exit
  — and row-major when none does. (An explicit ``jax.experimental.layout``
  format would say so directly, but under jax 0.9.0 a program read back
  from the persistent compilation cache has lost it: the executable comes
  back compiled for the default layout. `PERF.md` §6, PR 26.)
* the step programs carry the whole pool through their layer loop and
  index the layer (``engine._paged_layers``), never slicing a layer out;
* every write here is a ``dynamic_update_slice`` of whole blocks, which XLA
  performs in place in the layout the buffer has (a ``scatter`` wants a
  third layout and drags the pool through it).

Block 0 is the null block: never allocated, it backs idle slots and the
padded tail of every block table, so device code can index the table
unconditionally — out-of-range entries fetch garbage that the per-sequence
length mask then drops (``ops/paged_attention.py``).

The allocator is host-side and deliberately dumb: a free list with O(1)
alloc/release and loud failure on double-free/foreign ids. All policy
(when to admit, how many blocks a request needs) lives in the engine.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
from typing import Iterable

import jax
import jax.numpy as jnp

from gpt_2_distributed_tpu.config import GPT2Config, ServeConfig


class BlockAllocator:
    """Refcounted free-list allocator over pool blocks ``1..num_blocks-1``
    (0 = null).

    ``alloc`` is all-or-nothing: the caller either gets every block it
    asked for, or None with the free list untouched. Blocks are refcounted
    so the prefix cache can pin a block (``retain``) while the request
    that wrote it still holds it — ``release`` decrements, and the block
    returns to the free list only at refcount zero. Double-free / foreign
    ids still fail loudly.

    With ``num_shards > 1`` (sharded engine, ``ServeConfig.mesh``) the pool
    splits into contiguous runs of ``num_blocks / num_shards`` blocks — run
    ``s`` lives on data-shard ``s`` of the device mesh — and each shard keeps
    its own free list. ``alloc(n, shard=s)`` then grants blocks from that
    shard only, so a slot row's KV never straddles data shards (block ids
    stay resolvable to one device without cross-shard gathers at decode).
    Shard 0 also hosts the reserved null block, so it has one fewer usable
    block than the others.
    """

    def __init__(self, num_blocks: int, num_shards: int = 1):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks={num_blocks} must be >= 2 (block 0 is reserved)"
            )
        if num_shards < 1 or num_blocks % num_shards != 0:
            raise ValueError(
                f"num_shards={num_shards} must be >= 1 and divide "
                f"num_blocks={num_blocks}"
            )
        self.num_blocks = num_blocks
        self.num_shards = num_shards
        self.blocks_per_shard = num_blocks // num_shards
        self._free: list[collections.deque[int]] = [
            collections.deque(
                range(max(1, s * self.blocks_per_shard),
                      (s + 1) * self.blocks_per_shard)
            )
            for s in range(num_shards)
        ]
        self._held: dict[int, int] = {}

    @property
    def available(self) -> int:
        return sum(len(f) for f in self._free)

    def available_in(self, shard: int) -> int:
        return len(self._free[shard])

    def shard_of(self, i: int) -> int:
        """Data shard owning pool block ``i``."""
        return i // self.blocks_per_shard

    def alloc(self, n: int, shard: int = 0) -> list[int] | None:
        """n blocks at refcount 1 from one shard's free list, or None
        (leaving the free list untouched) if that shard can't currently
        cover them."""
        if n < 1:
            raise ValueError(f"alloc({n}): need at least one block")
        free = self._free[shard]
        if n > len(free):
            return None
        ids = [free.popleft() for _ in range(n)]
        for i in ids:
            self._held[i] = 1
        return ids

    def retain(self, i: int) -> None:
        """Add a reference to an already-allocated block (prefix-cache
        sharing: the cache and each request using the block hold one
        reference each)."""
        if i not in self._held:
            raise ValueError(f"retain({i}): not an allocated block")
        self._held[i] += 1

    def refcount(self, i: int) -> int:
        """Current reference count (0 = free / never allocated)."""
        return self._held.get(i, 0)

    def release(self, ids: Iterable[int]) -> None:
        """Drop one reference per id; blocks reaching refcount zero return
        to the free list."""
        for i in ids:
            if i not in self._held:
                raise ValueError(
                    f"release({i}): not an allocated block (double free, the "
                    f"null block, or a foreign id)"
                )
            self._held[i] -= 1
            if self._held[i] == 0:
                del self._held[i]
                self._free[self.shard_of(i)].append(i)


class PrefixCache:
    """Hash-cons of full KV blocks by token-prefix (LRU).

    Key: the exact int32 token bytes of the prompt prefix a block
    completes — block ``j`` of a prompt is cached under
    ``tokens[:(j+1) * block_size]``. Content-addressing by prefix (not by
    (block j's tokens, j)) is what makes sharing safe: K/V at position i
    depends on every token ``<= i`` through attention, so two requests may
    share a cached block only when their *entire* prefix up to that block's
    end matches.

    The cache holds one allocator reference per entry (``retain`` at
    insert). Lookup returns the longest run of leading full-block hits —
    a miss at block j ends the run because block j+1's K/V would attend
    into the missed span. Eviction (LRU) only considers entries whose
    refcount is 1, i.e. blocks no live request still holds.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._entries: collections.OrderedDict[bytes, int] = (
            collections.OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _key(tokens, end: int) -> bytes:
        import numpy as np

        return np.asarray(tokens[:end], np.int32).tobytes()

    def peek_run(self, tokens) -> int:
        """Length (in blocks) of the leading full-block hit run, WITHOUT
        touching LRU order or the hit/miss counters. The replica router
        probes every replica's cache with this before choosing one
        (``serving/frontend/router.py``) — a probe is not a use, so it
        must not promote entries or skew the cache stats."""
        run = 0
        for j in range(len(tokens) // self.block_size):
            if self._key(tokens, (j + 1) * self.block_size) not in self._entries:
                break
            run += 1
        return run

    def lookup(self, tokens) -> list[int]:
        """Longest run of leading full-block hits for this token sequence;
        returns the cached block ids (caller must ``retain`` each before
        use). Hit entries move to MRU."""
        run: list[int] = []
        for j in range(len(tokens) // self.block_size):
            key = self._key(tokens, (j + 1) * self.block_size)
            bid = self._entries.get(key)
            if bid is None:
                self.misses += 1
                break
            self._entries.move_to_end(key)
            self.hits += 1
            run.append(bid)
        return run

    def insert(self, tokens, j: int, block_id: int,
               allocator: BlockAllocator) -> bool:
        """Register block ``block_id`` as holding block ``j`` of
        ``tokens``. First writer wins: if the prefix is already cached
        (another request registered its own copy) this is a no-op."""
        key = self._key(tokens, (j + 1) * self.block_size)
        if key in self._entries:
            return False
        allocator.retain(block_id)
        self._entries[key] = block_id
        return True

    def evict_one(self, allocator: BlockAllocator, shard: int | None = None) -> bool:
        """Drop the LRU entry whose block no live request holds
        (refcount 1 = cache-only). ``shard`` restricts eviction to blocks
        owned by that data shard (a sharded engine evicting to free shard-s
        capacity gains nothing from releasing a foreign shard's block).
        Returns False when every (matching) entry is still pinned by an
        in-flight request."""
        for key, bid in self._entries.items():
            if allocator.refcount(bid) == 1 and (
                shard is None or allocator.shard_of(bid) == shard
            ):
                del self._entries[key]
                allocator.release([bid])
                self.evictions += 1
                return True
        return False

    def clear(self, allocator: BlockAllocator) -> None:
        """Drop every unpinned entry (bench warmup isolation)."""
        while self.evict_one(allocator):
            pass


# The TPU's compact layout puts a dimension of more than this many elements
# minor-most rather than pad D = 64 to the 128 lanes.
_MAX_MAJOR_DIM = 64


def split_blocks(num_blocks: int) -> tuple[int, ...]:
    """The block axis as the fewest factors of at most 64 whose product is
    the least that holds ``num_blocks`` (257 -> (6, 43): one block of
    padding, which no table ever names)."""
    k = 1
    while _MAX_MAJOR_DIM ** k < num_blocks:
        k += 1
    splits = (
        (*lead, -(-num_blocks // math.prod(lead)))
        for lead in itertools.product(
            range(2, _MAX_MAJOR_DIM + 1), repeat=k - 1
        )
    )
    return min((f for f in splits if f[-1] <= _MAX_MAJOR_DIM), key=math.prod)


def pool_shape(
    config: GPT2Config, serve: ServeConfig, sharded: bool = False
) -> tuple[int, ...]:
    """The shape a pool is stored in: ``[L, *split_blocks(N), H, bs, D]``
    on one device, so that its default device layout is the row-major one
    the paged kernel reads; plain ``[L, N, H, bs, D]`` under a serving
    mesh, whose 'data' axis cuts the block axis into the allocator's
    per-shard runs (padding would shift them) and whose programs take the
    XLA gather path, not the kernel."""
    blocks = (serve.num_blocks,) if sharded else split_blocks(serve.num_blocks)
    return (
        config.n_layer,
        *blocks,
        config.n_head,
        serve.block_size,
        config.head_dim,
    )


def as_blocks(pool: jnp.ndarray) -> jnp.ndarray:
    """A stored pool as ``[L, N, H, bs, D]``: merging the split block axes
    of a row-major array moves nothing. (N may end in padding blocks.)"""
    return pool.reshape(pool.shape[0], -1, *pool.shape[-3:])


def init_pools(
    config: GPT2Config,
    serve: ServeConfig,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    sharding=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The preallocated K and V pools, zeros of ``pool_shape``.

    ``sharding`` (a NamedSharding; block axis over 'data', head axis over
    'tp') places each pool directly on the serving mesh so no device ever
    materializes the full buffer."""
    shape = pool_shape(config, serve, sharded=sharding is not None)
    if sharding is not None:
        zeros = jax.jit(
            lambda: jnp.zeros(shape, compute_dtype), out_shardings=sharding
        )
        return zeros(), zeros()
    return jnp.zeros(shape, compute_dtype), jnp.zeros(shape, compute_dtype)


def draft_serve_view(
    serve: ServeConfig,
    n_positions: int,
    block_size: int | None = None,
) -> ServeConfig:
    """ServeConfig describing the draft model's KV block pool.

    Same slot geometry as the target (the draft's slot tables are paired
    1:1 with the target's), same mesh, but an independent block size and
    a block count sized so every slot can hold a full-context draft
    sequence: ``data * (slots_per_shard * max_blocks_per_seq + 1)``
    blocks — the ``+1`` per shard covers the reserved null block on
    shard 0 and keeps the shards uniform. Draft KV is disposable
    (discarded on preemption/migration and re-drafted), so full
    per-slot capacity — rather than the target pool's oversubscribed
    paging — buys the engine a draft allocator that can never fail
    mid-round. ``spec`` is cleared: the draft never speculates.
    """
    bs = serve.block_size if block_size is None else block_size
    data, _ = serve.mesh_axes()
    m = -(-n_positions // bs)
    slots_per_shard = serve.max_batch // data
    return dataclasses.replace(
        serve,
        spec="",
        block_size=bs,
        num_blocks=data * (slots_per_shard * m + 1),
        prefix_cache=False,
    )


def pool_bytes(config: GPT2Config, serve: ServeConfig, itemsize: int = 2) -> int:
    """Bytes of K/V the two pools hold (the serving deployment's KV budget).
    On a TPU they occupy more: row-major pads D = 64 to the 128 lanes, twice
    this (2.53 GB for 1.5B at 257 blocks, against 1.89 GB block-minor)."""
    return (
        2 * config.n_layer * serve.num_blocks * config.n_head
        * serve.block_size * config.head_dim * itemsize
    )


def _write_blocks(
    k_pool: jnp.ndarray,   # [L, N, H, bs, D]
    v_pool: jnp.ndarray,
    layer,                 # scalar int32
    dst: jnp.ndarray,      # [J] int32 destination blocks
    keep: jnp.ndarray,     # [J, bs] bool — positions of block j to take
    k: jnp.ndarray,        # [J, H, bs, D], or [J, H, 1, D]: one row for all
    v: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``pool[layer, dst[j]] = where(keep[j], new[j], what the pool holds)``
    for j in order: each block is read, merged and put back with one
    ``dynamic_update_slice``, so the pool is updated in place whatever its
    layout, and a position that is not kept keeps its bits."""
    h, bs, d = k_pool.shape[2:]

    def one(j, pools):
        at = (layer, dst[j], 0, 0, 0)
        mask = jax.lax.dynamic_index_in_dim(keep, j, 0).reshape(1, 1, 1, bs, 1)
        return tuple(
            jax.lax.dynamic_update_slice(
                pool,
                jnp.where(
                    mask,
                    jax.lax.dynamic_index_in_dim(new, j, 0)[None]
                    .astype(pool.dtype),
                    jax.lax.dynamic_slice(pool, at, (1, 1, h, bs, d)),
                ),
                at,
            )
            for pool, new in zip(pools, (k, v))
        )

    return jax.lax.fori_loop(0, dst.shape[0], one, (k_pool, v_pool))


def write_rows(
    k_pool: jnp.ndarray,   # [L, N, H, bs, D]
    v_pool: jnp.ndarray,
    layer,                 # scalar int32
    blk: jnp.ndarray,      # [B] int32 destination block per row
    off: jnp.ndarray,      # [B] int32 position inside the block
    k: jnp.ndarray,        # [B, H, D] one position's K per row
    v: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The decode step's write: ``pool[layer, blk[b], :, off[b]] = new[b]``
    for every row, in row order (idle rows all land on the null block; the
    last one wins there, and nothing reads it)."""
    bs = k_pool.shape[3]
    keep = jax.lax.iota(jnp.int32, bs)[None] == off[:, None]      # [B, bs]
    return _write_blocks(
        k_pool, v_pool, layer, blk, keep, k[:, :, None], v[:, :, None]
    )


def write_chunk(
    k_pool: jnp.ndarray,   # [L, N, H, bs, D]
    v_pool: jnp.ndarray,
    layer,                 # scalar int32
    bt: jnp.ndarray,       # [R, M] int32 block-table rows
    start: jnp.ndarray,    # [R] int32 position of k[:, 0]
    valid: jnp.ndarray,    # [R, C] bool — False = padding, never written
    k: jnp.ndarray,        # [R, C, H, D] a run of consecutive positions
    v: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The chunk programs' write: position ``p = start[r] + i`` of row ``r``
    goes to ``pool[layer, bt[r, p // bs], :, p % bs]`` wherever
    ``valid[r, i]`` — the same pool contents, bit for bit, as a scatter
    with the invalid rows dropped.

    A run of C positions touches at most ``nb`` consecutive table slots,
    so each row's K/V is re-cut at its own block boundaries into
    ``[nb, H, bs, D]`` and written block by block: the partial first and
    last blocks, padding, and table slots past the row's end keep what
    the pool holds."""
    r, c, h, d = k.shape
    bs = k_pool.shape[3]
    m = bt.shape[1]
    nb = (c + 2 * bs - 2) // bs
    first = start // bs                                           # [R]
    # Chunk index of every position of those nb blocks.
    i = (first * bs - start)[:, None] + jax.lax.iota(jnp.int32, nb * bs)[None]
    ic = jnp.clip(i, 0, c - 1)
    slot = first[:, None] + jax.lax.iota(jnp.int32, nb)[None]     # [R, nb]
    keep = (i >= 0) & (i < c) & jnp.take_along_axis(valid, ic, axis=1)
    keep = keep.reshape(r, nb, bs) & (slot < m)[:, :, None]
    dst = jnp.take_along_axis(bt, jnp.minimum(slot, m - 1), axis=1)

    def blocks(x):           # [R, C, H, D] -> [R * nb, H, bs, D]
        x = jnp.take_along_axis(x, ic[:, :, None, None], axis=1)
        x = x.reshape(r, nb, bs, h, d).transpose(0, 1, 3, 2, 4)
        return x.reshape(r * nb, h, bs, d)

    return _write_blocks(
        k_pool, v_pool, layer, dst.reshape(r * nb),
        keep.reshape(r * nb, bs), blocks(k), blocks(v),
    )


def _scatter_prefill_impl(
    k_pool: jnp.ndarray,   # stored pool (`pool_shape`)
    v_pool: jnp.ndarray,
    k: jnp.ndarray,        # [L, H, Ppad, D] — prefill K, Ppad = nb * bs
    v: jnp.ndarray,
    block_ids: jnp.ndarray,  # [nb] int32 pool destinations
) -> tuple[jnp.ndarray, jnp.ndarray]:
    l, h, ppad, d = k.shape
    bs = k_pool.shape[-2]
    nb = ppad // bs
    pools = (as_blocks(k_pool), as_blocks(v_pool))
    news = tuple(
        x.reshape(l, h, nb, bs, d).transpose(0, 2, 1, 3, 4).astype(pool.dtype)
        for x, pool in zip((k, v), pools)
    )                                                    # [L, nb, H, bs, D]

    def one(j, pools):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                pool, jax.lax.dynamic_slice_in_dim(new, j, 1, axis=1),
                block_ids[j], axis=1,
            )
            for pool, new in zip(pools, news)
        )

    pools = jax.lax.fori_loop(0, nb, one, pools)
    return pools[0].reshape(k_pool.shape), pools[1].reshape(v_pool.shape)


# Scatter one sequence's prefill K/V into its allocated pool blocks.
#
# Compiles once per (Ppad, nb) bucket — the engine rounds prompt lengths
# up to block multiples precisely so this signature set stays small. The
# pools are donated: admission rewrites them in place rather than holding
# two copies of the serving deployment's largest buffer.
scatter_prefill = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_scatter_prefill_impl)


def _copy_block_impl(
    k_pool: jnp.ndarray,   # stored pool (`pool_shape`)
    v_pool: jnp.ndarray,
    src: jnp.ndarray,      # scalar int32 source block
    dst: jnp.ndarray,      # scalar int32 destination block
) -> tuple[jnp.ndarray, jnp.ndarray]:
    def copy(pool):
        blocks = as_blocks(pool)
        return jax.lax.dynamic_update_slice_in_dim(
            blocks, jax.lax.dynamic_slice_in_dim(blocks, src, 1, axis=1),
            dst, axis=1,
        ).reshape(pool.shape)

    return copy(k_pool), copy(v_pool)


# Copy-on-write: duplicate one pool block across all layers.
#
# Used when a prompt ends exactly on a cached block boundary — the
# request gets a private copy of the final cached block so its own
# tail writes (the last prompt position is recomputed to produce the
# first-token logits) can't corrupt the shared entry. src/dst are
# traced, so this compiles once per pool shape.
copy_block = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_copy_block_impl)


def make_pool_jits(pool_sharding):
    """Mesh-aware ``(scatter_prefill, copy_block)`` pair for a sharded
    engine: same programs, jitted with explicit ``out_shardings`` pinning
    the result pools to the input pools' placement — donation only elides
    the copy when input and output shardings match, and without the pin
    GSPMD is free to emit replicated outputs (silently un-sharding the
    pool on the first admission). The module-level jits stay as-is for the
    single-device engine and its tests."""
    out = (pool_sharding, pool_sharding)
    return (
        jax.jit(_scatter_prefill_impl, donate_argnums=(0, 1), out_shardings=out),
        jax.jit(_copy_block_impl, donate_argnums=(0, 1), out_shardings=out),
    )
