"""One general generator for every traffic mix.

A mix is a data file under ``benchmark/traffic/``; this module reads its
parameters and makes, from ``--seed``, what the cell is fed: token shards
for a training cell, requests for a serving cell.

Every seed gets the same sizes in the same order - drawn once from the
mix's own ``base_seed`` - and its own token ids (and weights). A run's work
must not depend on the seed: in another order the same requests put another
number of prefill chunks into the window and other lengths side by side in
a step, and read up to 2 % apart, the same again on a second run (PERF.md,
PR 24) - a spread that follows the seeds drawn, which no bound can admit.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics

import numpy as np


def load_mix(name: str, traffic_dir: str) -> dict:
    """The mix's parameters. Its ``kind`` says which runner drives it
    (``harness.RUNNER_OF_KIND``, checked when the cell is loaded)."""
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        return json.load(f)


# --- training: token shards --------------------------------------------------


def write_shards(data_dir: str, mix: dict, vocab_size: int, seed: int) -> list[str]:
    """Write the mix's ``shards`` train shards of ``tokens_per_shard``
    uint16 tokens each, in the trainer's file format (flat little-endian
    uint16, ``<name>_train_<index>.bin``). Rows all differ: ascending runs
    of 64 tokens from random starts, so the loss can fall."""
    os.makedirs(data_dir, exist_ok=True)
    for old in os.listdir(data_dir):
        if old.endswith(".bin"):
            os.remove(os.path.join(data_dir, old))
    rng = np.random.default_rng(seed)
    n = int(mix["tokens_per_shard"])
    ramp = np.arange(n) % 64
    paths = []
    for i in range(1, int(mix["shards"]) + 1):
        starts = rng.integers(0, vocab_size, size=n // 64 + 1)
        tokens = (starts.repeat(64)[:n] + ramp) % vocab_size
        path = os.path.join(data_dir, f"bench_train_{i:06d}.bin")
        tokens.astype("<u2").tofile(path)
        paths.append(path)
    return paths


def rows_in_shards(paths: list[str], seq_len: int, rows_per_step: int,
                   batches: list) -> tuple[list, int]:
    """What the shard files themselves say of the rows that a loader fed.
    For each step's ``(inputs, labels)``, both [rows, seq_len], the pair as
    read from the files here, and the count of rows fed wrong: not a
    stride-aligned window of a shard with its labels one token on, a window
    fed before, or a row that a step is short of. In whatever order a
    loader visits the windows, it feeds each once an epoch."""
    unfed: dict[bytes, list[np.ndarray]] = {}
    for path in paths:
        tokens = np.fromfile(path, dtype="<u2").astype(np.int32)
        for k in range((len(tokens) - 1) // seq_len):
            window = tokens[k * seq_len:(k + 1) * seq_len + 1]
            unfed.setdefault(window[:-1].tobytes(), []).append(window)
    from_files, wrong = [], 0
    for x, y in batches:
        fx, fy = np.zeros_like(x), np.zeros_like(y)
        wrong += max(0, rows_per_step - len(x))
        for i, row in enumerate(x):
            found = unfed.get(np.ascontiguousarray(row, dtype=np.int32).tobytes())
            if not found:
                wrong += 1
                continue
            window = found.pop()
            fx[i], fy[i] = window[:-1], window[1:]
            wrong += int(not np.array_equal(y[i], window[1:]))
        from_files.append((fx, fy))
    return from_files, wrong


# --- serving: requests -------------------------------------------------------


@dataclasses.dataclass
class Request:
    index: int
    prompt: list[int]
    max_new_tokens: int


def _lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 0.5) / n: the same
    shape as ``n`` draws, with no luck in it."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(int)


def length_pool(mix: dict) -> list[tuple[int, int]]:
    """The mix's fixed round of (prompt length, output length) pairs: both
    lognormal by quantiles, paired and ordered at random from the mix's
    ``base_seed``. Keep the round short enough that a window goes through
    it several times: every window then holds the same requests, and one
    more or fewer at its close weighs little."""
    rng = np.random.default_rng(int(mix["base_seed"]))
    n = int(mix["pool"])
    prompts = _lognormal_lengths(mix["prompt"], n)
    outputs = rng.permutation(_lognormal_lengths(mix["output"], n))
    outputs = np.minimum(outputs, int(mix["max_total"]) - prompts)
    return [(int(prompts[i]), int(outputs[i])) for i in rng.permutation(n)]


def requests(mix: dict, vocab_size: int, seed: int):
    """Endless iterator of :class:`Request`: the mix's round, again and
    again, with this seed's token ids."""
    rng = np.random.default_rng(seed)
    pool = length_pool(mix)
    index = 0
    while True:
        for p, o in pool:
            yield Request(index, rng.integers(0, vocab_size, p).tolist(), o)
            index += 1
