"""What ``ServingEngine.step``'s own clocks and counters say per step: the
part of ``metrics_snapshot()`` that the engine and the router's fleet
aggregate share. Imports nothing, so the router stays importable without
jax, and takes ``stats`` dicts, so a worker's proxy serves as well as an
engine."""

from __future__ import annotations

from typing import Iterable, Mapping


def step_clocks(all_stats: Iterable[Mapping[str, float]]) -> dict[str, float]:
    """Means over every engine's steps. ``engine_host_ms``: a step's time
    that is neither a prefill dispatch nor the decode step, of which
    ``admit_ms``, ``grow_ms`` and ``emit_ms`` are the clocked parts.
    ``decode_dispatch_ms`` + ``decode_wait_ms``: a decode step less its
    draft pass, split where the decode program's call returns; the wait
    is for the read-back of the step BEFORE the one dispatched
    (``decode_overlapped``: the share of decode steps dispatched while the
    step before was still unread - how often the one-deep pipeline engaged).
    ``decode_rows``, ``decode_attended``: the rows a decode step advances
    and the keys they attend (of the selected blocks, where a layer
    selects). ``decode_blocks_live``, ``decode_blocks_table``: of those
    rows' block-table slots, the ones that hold keys and all of them
    (``1 - live / table`` is the share of slots the paged kernel skips).
    ``prefill_tokens``, ``prefill_attended``: the same of a chunked
    prefill dispatch. ``sparse_rows``, ``sparse_selected``,
    ``sparse_visible``: per step, the rows through a selecting attention
    (prefill and decode alike), the blocks they attend and the blocks they
    see. ``state_resets``: per step, requests started from a zero recurrent
    state. ``moe_rows``, ``moe_experts_touched``, ``moe_expert_slots``: per
    dispatch that ran expert layers (a prefill chunk or a decode step), the
    token-expert pairs that fell to the experts held here, the held experts
    that got a row and the held experts, each summed over the expert layers.
    ``ssm_rows``: per decode step, live rows through a state-space update,
    summed over those layers. ``sscan_tokens``: per prefill dispatch, real
    tokens through a selective scan; ``sscan_rows``: per decode step, live
    rows through its one-token update; both summed over those layers. A
    ``stats`` that predates a counter reads 0 there."""
    all_stats = list(all_stats)

    def total(key: str) -> float:
        return sum(stats.get(key, 0) for stats in all_stats)

    steps = max(total("steps"), 1)
    decodes = max(total("decode_steps"), 1)
    prefills = max(total("prefill_dispatches"), 1)
    dispatches = max(total("decode_steps") + total("prefill_dispatches"), 1)
    return {
        "engine_host_ms": (
            total("step_ms") - total("prefill_ms") - total("decode_ms")
        ) / steps,
        "admit_ms": total("admit_ms") / steps,
        "grow_ms": total("grow_ms") / steps,
        "emit_ms": total("emit_ms") / steps,
        "decode_dispatch_ms": total("decode_dispatch_ms") / decodes,
        "decode_wait_ms": (
            total("decode_ms") - total("draft_ms")
            - total("decode_dispatch_ms")
        ) / decodes,
        "decode_overlapped": total("decode_overlapped") / decodes,
        "decode_rows": total("decode_rows") / decodes,
        "decode_attended": total("decode_attended") / decodes,
        "decode_blocks_live": total("decode_blocks_live") / decodes,
        "decode_blocks_table": total("decode_blocks_table") / decodes,
        "prefill_tokens": total("prefill_tokens") / prefills,
        "prefill_attended": total("prefill_attended") / prefills,
        "sparse_rows": total("sparse_rows") / steps,
        "sparse_selected": total("sparse_selected") / steps,
        "sparse_visible": total("sparse_visible") / steps,
        "state_resets": total("state_resets") / steps,
        "moe_rows": total("moe_rows") / dispatches,
        "moe_experts_touched": total("moe_experts_touched") / dispatches,
        "moe_expert_slots": total("moe_expert_slots") / dispatches,
        "ssm_rows": total("ssm_rows") / decodes,
        "sscan_tokens": total("sscan_tokens") / prefills,
        "sscan_rows": total("sscan_rows") / decodes,
    }
