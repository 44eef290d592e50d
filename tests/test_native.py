"""Native (C) data-loader core: correctness vs the numpy path, error
contracts, and fallback behavior.

The C source compiles on demand with the host's C compiler
(``gpt_2_distributed_tpu/native``); these tests require it to be available
in CI (the build image ships gcc) so the native path never silently rots
into the fallback.
"""

import os

import numpy as np
import pytest

from gpt_2_distributed_tpu import native
from gpt_2_distributed_tpu.data.dataloader import TokenShardDataset, get_shard_paths


def test_native_builds_on_this_host():
    assert native.available(), (
        "native window gather failed to build — CI hosts ship a C compiler, "
        "so this signals a build regression, not a missing toolchain"
    )


def test_shared_object_is_named_after_its_source(tmp_path, monkeypatch):
    """The cached build carries a hash of the C source in its name, so a
    stale object lying in a copied tree (file times do not survive every
    copy) can never be loaded for a source it was not built from."""
    current = native.so_path()
    with open(native._SRC, "rb") as f:
        source = f.read()
    assert os.path.exists(current)          # built by the test above
    edited = tmp_path / "window_gather.c"
    edited.write_bytes(source + b"\n/* edited */\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert os.path.basename(native.so_path()) != os.path.basename(current)
    monkeypatch.setattr(native, "_SRC", str(tmp_path / "missing.c"))
    assert native.so_path() is None


def test_gather_matches_numpy():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50257, 10_000, dtype=np.uint16)
    offsets = np.asarray([0, 17, 128, 9000 - 65], dtype=np.int64)
    wins, max_id = native.gather_windows(tokens, offsets, 65)
    expect = np.stack([tokens[o : o + 65] for o in offsets])
    np.testing.assert_array_equal(wins, expect)
    assert max_id == int(expect.max())


def test_gather_rejects_out_of_range():
    tokens = np.zeros(100, dtype=np.uint16)
    with pytest.raises(IndexError):
        native.gather_windows(tokens, np.asarray([90], dtype=np.int64), 20)
    with pytest.raises(IndexError):
        native.gather_windows(tokens, np.asarray([-1], dtype=np.int64), 20)


def test_dataset_native_and_numpy_paths_identical(shard_dir, monkeypatch):
    """The loader's native fast path must yield byte-identical windows in
    the identical order as the pure-numpy path."""
    paths = get_shard_paths(shard_dir, "train")

    def windows(force_numpy: bool):
        if force_numpy:
            monkeypatch.setattr(native, "available", lambda: False)
        else:
            monkeypatch.undo()
        ds = TokenShardDataset(
            paths, seq_len=63, process_index=0, process_count=1, num_workers=1
        )
        ds.set_epoch(2)
        return [w.tobytes() for w in ds.iter_worker(0)]

    fast = windows(force_numpy=False)
    slow = windows(force_numpy=True)
    assert fast == slow
    assert len(fast) > 10


def test_dataset_native_corrupt_token_error(tmp_path):
    """The native path reports corrupt tokens with the numpy path's message
    contract (shard, token id, offset)."""
    tokens = np.zeros(4096, dtype="<u2")
    tokens[777] = 60_000  # out of the declared vocab
    p = tmp_path / "demo_train_000001.bin"
    tokens.tofile(p)
    ds = TokenShardDataset(
        [str(p)], seq_len=63, process_index=0, process_count=1,
        num_workers=1, vocab_size=50257,
    )
    ds.set_epoch(0)
    with pytest.raises(ValueError, match="token id 60000 >= vocab_size"):
        list(ds.iter_worker(0))
