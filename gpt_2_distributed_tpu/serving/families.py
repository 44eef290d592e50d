"""The family seam of ``ServingEngine``: what a model family that is not GPT-2
provides so that the one scheduler can serve it.

The engine's admission, slots, block tables, prefill tick, growth, emit loop,
``_Phase`` spans and ``stats`` counters are the same for every family. What
differs is gathered here, one ``Family`` a family, and the engine reads
nothing else of a family but its configuration's ``kv_pool_view`` (the shape
of its paged K/V: which layers hold any, in how many heads, and whether one
latent pool holds what keys and values are made of):

* ``flags``: its jax-free row in ``config.FAMILY_FLAGS`` - presets and
  ``refuse``: what the engine cannot do for it yet, by name, at construction
  as at parse time (one function);
* ``init_params``: its weights from a seed (``serve.load_model
  --init_random``); the engine holds them as given (a GPT-2 tree is cast once
  instead);
* ``init_state``: the cache it keeps beside the pools, by slot;
* ``chunk_impl`` / ``decode_impl``: its two step programs. Both take
  ``(params, k_pool, v_pool, state, ...)`` - donated - and return ``(tokens,
  keys, k_pool, v_pool, state)``; ``tokens`` is the sampled tokens followed
  by one int32 for each name in ``counters``, which the engine adds to the
  ``stats`` of those names: they reach the host in the token read-back;
* ``count_rows(stats, config, block_size, positions, decode)``: what a
  dispatch's rows count into the family's host-side counters, and the keys
  that queries at ``positions`` attend in one layer (None: every key up to
  their own, and nothing else counted).

GPT-2 has no entry: it is the engine's default branch (whole-prompt prefill,
the prefix cache's copies, speculation and the serving mesh are written for
its trees and pools). A family's model and programs are imported when an
engine of that family is first built, not with this module: a GPT-2 server's
start-up pays for no other family's imports (the grouped matmul's kernel
library alone is seconds).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import numpy as np

from gpt_2_distributed_tpu.config import (
    EXPERT_LAYER,
    FAMILY_FLAGS,
    MAMBA_LAYER,
    DeepseekV3Config,
    FamilyFlags,
    JambaConfig,
    NemotronHConfig,
    SalaConfig,
)


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    flags: FamilyFlags
    init_params: Callable
    init_state: Callable
    chunk_impl: Callable
    decode_impl: Callable
    counters: tuple[str, ...] = ()
    count_rows: Callable | None = None


def dense_attended(positions: np.ndarray) -> int:
    """Keys that queries at ``positions`` attend where each attends all up
    to its own."""
    return int(positions.sum()) + len(positions)


def _sala_rows(stats: dict, config: SalaConfig, block_size: int,
               positions: np.ndarray, decode: bool) -> int:
    """The keys of the selected blocks, which the selection's sizes fix
    without a look at the device (counted into ``sparse_...`` too)."""
    selected, visible = config.sparse.selected_blocks(positions)
    stats["sparse_rows"] += len(positions)
    stats["sparse_selected"] += int(selected.sum())
    stats["sparse_visible"] += int(visible.sum())
    # the query's own block is attended up to the query
    return int((selected * block_size - (block_size - 1 - positions % block_size)).sum())


def _nemotron_rows(stats: dict, config: NemotronHConfig, block_size: int,
                   positions: np.ndarray, decode: bool) -> int:
    """A dispatch with a live row asks every held expert of every expert
    layer (``moe_expert_slots``; how many got a row comes back from the
    device); a decode step's live rows each go through the state update of
    every Mamba layer (``ssm_rows``)."""
    if len(positions):
        stats["moe_expert_slots"] += config.n_held * len(config.layers_of(EXPERT_LAYER))
    if decode:
        stats["ssm_rows"] += len(positions) * len(config.layers_of(MAMBA_LAYER))
    return dense_attended(positions)


def _jamba_rows(stats: dict, config: JambaConfig, block_size: int,
                positions: np.ndarray, decode: bool) -> int:
    """Every real token of a chunk goes through the selective scan of every
    Mamba layer (``sscan_tokens``), every live row of a decode step through
    its one-token update (``sscan_rows``)."""
    stats["sscan_rows" if decode else "sscan_tokens"] += \
        len(positions) * len(config.layers_of(MAMBA_LAYER))
    return dense_attended(positions)


def _deepseek_v3_rows(stats: dict, config: DeepseekV3Config, block_size: int,
                      positions: np.ndarray, decode: bool) -> int:
    """A dispatch with a live row asks every held expert of every expert
    layer (``moe_expert_slots``); every layer attends all the keys up to a
    query's own."""
    if len(positions):
        stats["moe_expert_slots"] += config.n_held * len(config.layers_of(EXPERT_LAYER))
    return dense_attended(positions)


_FLAGS = {row.config_type: row for row in FAMILY_FLAGS}


def _sala() -> Family:
    from gpt_2_distributed_tpu.models import minicpm_sala
    from gpt_2_distributed_tpu.serving import sala_programs

    return Family(
        name="a SalaConfig", flags=_FLAGS[SalaConfig],
        init_params=minicpm_sala.init_params,
        init_state=sala_programs.init_state,
        chunk_impl=sala_programs.chunk_prefill_impl,
        decode_impl=sala_programs.decode_step_impl,
        count_rows=_sala_rows)


def _nemotron() -> Family:
    from gpt_2_distributed_tpu.models import nemotron_h
    from gpt_2_distributed_tpu.serving import nemotron_programs

    return Family(
        name="a NemotronHConfig", flags=_FLAGS[NemotronHConfig],
        init_params=nemotron_h.init_params,
        init_state=nemotron_programs.init_state,
        chunk_impl=nemotron_programs.chunk_prefill_impl,
        decode_impl=nemotron_programs.decode_step_impl,
        counters=nemotron_programs.COUNTERS,
        count_rows=_nemotron_rows)


def _jamba() -> Family:
    from gpt_2_distributed_tpu.models import jamba
    from gpt_2_distributed_tpu.serving import jamba_programs

    return Family(
        name="a JambaConfig", flags=_FLAGS[JambaConfig],
        init_params=jamba.init_params,
        init_state=jamba_programs.init_state,
        chunk_impl=jamba_programs.chunk_prefill_impl,
        decode_impl=jamba_programs.decode_step_impl,
        count_rows=_jamba_rows)


def _deepseek_v3() -> Family:
    from gpt_2_distributed_tpu.models import deepseek_v3
    from gpt_2_distributed_tpu.serving import deepseek_v3_programs

    return Family(
        name="a DeepseekV3Config", flags=_FLAGS[DeepseekV3Config],
        init_params=deepseek_v3.init_params,
        init_state=deepseek_v3_programs.init_state,
        chunk_impl=deepseek_v3_programs.chunk_prefill_impl,
        decode_impl=deepseek_v3_programs.decode_step_impl,
        counters=deepseek_v3_programs.COUNTERS,
        count_rows=_deepseek_v3_rows)


_BUILDERS: dict[type, Callable[[], Family]] = {
    SalaConfig: _sala, NemotronHConfig: _nemotron, JambaConfig: _jamba,
    DeepseekV3Config: _deepseek_v3}


@functools.cache
def _family(config_type: type) -> Family | None:
    build = _BUILDERS.get(config_type)
    return None if build is None else build()


def family_of(config) -> Family | None:
    """The family of a model configuration; None for GPT-2's."""
    return _family(type(config))
