"""Single-process units for the multi-host control plane (coordination.py).

The real 2-process consensus/desync/hang paths run in tests/test_multihost.py;
here the protocol pieces are pinned in isolation: control-word encode/decode
round-trip and OR-reduce semantics, the ConsensusBus identity fast path (the
property that keeps single-host runs bit-identical), fingerprint determinism
and sensitivity, the mismatched-rank report, and the watchdog's full
fire/disarm/beat lifecycle with an injectable exit.
"""

from __future__ import annotations

import threading
import time

import jax
import pytest

from gpt_2_distributed_tpu.config import CoordinationPolicy
from gpt_2_distributed_tpu.coordination import (
    CTRL_PREEMPT,
    CTRL_ROLLBACK,
    CTRL_SAVE_NOW,
    CTRL_SKIP,
    CTRL_WORKER_ERROR,
    ConsensusBus,
    ControlWord,
    HangWatchdog,
    check_fingerprints,
    decode_control_word,
    encode_control_word,
    fingerprint_params,
    mismatched_ranks,
    or_reduce_words,
    perturb_params,
)
from gpt_2_distributed_tpu.resilience import (
    DATA_ABORT_EXIT_CODE,
    HANG_EXIT_CODE,
    PREEMPTED_EXIT_CODE,
)


# --- control word -----------------------------------------------------------


def test_control_word_roundtrip_every_combination():
    flags = ("preempt", "rollback", "skip", "worker_error", "save_now")
    for mask in range(32):
        kwargs = {f: bool(mask & (1 << i)) for i, f in enumerate(flags)}
        word = encode_control_word(**kwargs)
        assert decode_control_word(word) == ControlWord(**kwargs)


def test_control_word_bits_are_distinct():
    bits = [CTRL_PREEMPT, CTRL_ROLLBACK, CTRL_SKIP, CTRL_WORKER_ERROR,
            CTRL_SAVE_NOW]
    assert len(set(bits)) == 5
    for b in bits:
        assert b and (b & (b - 1)) == 0  # each a single bit


def test_or_reduce_any_host_raises_flag_for_pod():
    # One host preempted + one host rolling back -> the pod sees both.
    words = [
        encode_control_word(),
        encode_control_word(preempt=True),
        encode_control_word(rollback=True),
    ]
    agreed = decode_control_word(or_reduce_words(words))
    assert agreed.preempt and agreed.rollback
    assert not (agreed.skip or agreed.worker_error or agreed.save_now)
    assert or_reduce_words([]) == 0


def test_consensus_bus_identity_single_process():
    bus = ConsensusBus()
    assert bus.process_count == 1
    word = encode_control_word(rollback=True, save_now=True)
    # Identity: the agreed word IS the local word, no allgather dispatched.
    assert bus.exchange(word) == word
    assert bus.exchange(0) == 0


def test_consensus_bus_rejects_unknown_bits():
    # A word with bits outside the protocol means mismatched code versions
    # across the pod — the one failure the OR-reduce cannot paper over.
    bus = ConsensusBus()
    with pytest.raises(ValueError, match="unknown bits"):
        bus.exchange(1 << 7)
    bus.exchange(CTRL_PREEMPT | CTRL_SAVE_NOW)  # all known bits are fine


# --- desync detector --------------------------------------------------------


def test_fingerprint_deterministic_and_sensitive(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2

    params = gpt2.init_params(tiny_config)
    fp1 = fingerprint_params(params)
    fp2 = fingerprint_params(params)
    assert fp1 == fp2  # bit-identical across calls on identical params
    # The injection's own perturbation must move the fingerprint — otherwise
    # --inject_desync_at would test nothing.
    import numpy as np

    perturbed = perturb_params(params, np.float32(1.001))
    assert fingerprint_params(perturbed) != fp1
    # factor 1.0 is the identity (the non-chosen ranks' dispatch).
    same = perturb_params(params, np.float32(1.0))
    assert fingerprint_params(same) == fp1


def test_perturb_preserves_structure_and_dtype(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2
    import numpy as np

    params = gpt2.init_params(tiny_config)
    out = perturb_params(params, np.float32(1.001))
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(
        params
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(out)
    ):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_check_fingerprints_identity_single_process(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2

    # Single process: nothing to compare with, never a mismatch.
    assert check_fingerprints(fingerprint_params(gpt2.init_params(tiny_config))) == []


def test_mismatched_ranks():
    assert mismatched_ranks([]) == []
    assert mismatched_ranks([1.0, 1.0, 1.0]) == []
    assert mismatched_ranks([1.0, 2.0, 1.0, 1.0]) == [1]
    assert mismatched_ranks([1.0, 2.0, 2.0, 3.0]) == [0, 3]
    # 1v1 tie: blame the higher rank (the lower rank's value wins the mode).
    assert mismatched_ranks([1.0, 2.0]) == [1]


# --- hang watchdog ----------------------------------------------------------


def _watchdog(timeout_s: float, **kw) -> tuple[HangWatchdog, list[int]]:
    exits: list[int] = []
    wd = HangWatchdog(timeout_s, _exit=exits.append, **kw)
    return wd, exits


def test_watchdog_fires_with_hang_exit_code(capsys):
    ran = threading.Event()
    wd, exits = _watchdog(0.15, on_hang=ran.set)
    wd.start()
    wd.arm()
    deadline = time.monotonic() + 5.0
    while not wd.fired and time.monotonic() < deadline:
        time.sleep(0.02)
    wd.stop()
    assert wd.fired
    assert exits == [HANG_EXIT_CODE]
    assert ran.is_set()  # the emergency-save callback ran
    assert "no optimizer step completed in 0.15s" in capsys.readouterr().out


def test_watchdog_beat_extends_deadline_and_disarm_prevents_fire():
    wd, exits = _watchdog(0.3)
    wd.start()
    wd.arm()
    # Beat faster than the timeout: must never fire.
    for _ in range(5):
        time.sleep(0.1)
        wd.beat()
    assert not wd.fired and exits == []
    # Disarm, then wait past the timeout: still must not fire.
    wd.disarm()
    time.sleep(0.5)
    assert not wd.fired and exits == []
    wd.stop()


def test_watchdog_unarmed_never_fires():
    # start() without arm(): compilation / restore phases have no step
    # cadence and must not trip the watchdog.
    wd, exits = _watchdog(0.1)
    wd.start()
    time.sleep(0.4)
    wd.stop()
    assert not wd.fired and exits == []


def test_watchdog_abandons_hung_emergency_save(capsys):
    # An on_hang that itself hangs (a save stuck in a dead collective) is
    # abandoned after grace_s and the exit still happens.
    wd, exits = _watchdog(0.1, on_hang=lambda: time.sleep(60), grace_s=0.2)
    wd.start()
    wd.arm()
    deadline = time.monotonic() + 5.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.02)
    wd.stop()
    assert exits == [HANG_EXIT_CODE]
    assert "abandoning it" in capsys.readouterr().out


def test_watchdog_exit_survives_failing_emergency_save(capsys):
    def boom() -> None:
        raise RuntimeError("save exploded")

    wd, exits = _watchdog(0.1, on_hang=boom)
    wd.start()
    wd.arm()
    deadline = time.monotonic() + 5.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.02)
    wd.stop()
    assert exits == [HANG_EXIT_CODE]
    assert "emergency save failed" in capsys.readouterr().out


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError):
        HangWatchdog(0.0)
    with pytest.raises(ValueError):
        HangWatchdog(-1.0)


def test_watchdog_stop_is_idempotent_and_restartable():
    wd, exits = _watchdog(10.0)
    wd.start()
    wd.stop()
    wd.stop()
    wd.start()  # restart after stop must spin a fresh thread
    assert wd._thread is not None and wd._thread.is_alive()
    wd.stop()
    assert exits == []


# --- policy / exit codes ----------------------------------------------------


def test_coordination_policy_validation():
    CoordinationPolicy()  # defaults: fully off
    CoordinationPolicy(desync_check_every=50, hang_timeout_s=600.0)
    with pytest.raises(ValueError):
        CoordinationPolicy(desync_check_every=-1)
    with pytest.raises(ValueError):
        CoordinationPolicy(hang_timeout_s=-0.5)
    # consensus_every amortizes the exchange; 0 would mean "never agree".
    CoordinationPolicy(consensus_every=4)
    with pytest.raises(ValueError, match="consensus_every"):
        CoordinationPolicy(consensus_every=0)


def test_exit_codes_are_distinct():
    # supervise.sh dispatches on these: 143 restarts free, 170/171 burn an
    # attempt. A collision would silently change restart accounting.
    codes = {PREEMPTED_EXIT_CODE, HANG_EXIT_CODE, DATA_ABORT_EXIT_CODE}
    assert len(codes) == 3
    assert PREEMPTED_EXIT_CODE == 143
    assert HANG_EXIT_CODE == 170
    assert DATA_ABORT_EXIT_CODE == 171
