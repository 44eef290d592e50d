"""Multi-host control plane: pod-wide consensus on fault decisions.

SPMD's contract — every process dispatches the identical collective sequence
or the job deadlocks / silently diverges — is enforced for loop *bounds* by
``train._common_min`` but, before this module, not for fault *decisions*:
everything the resilience stack acts on (spike rollback, preemption flags,
data-worker errors) is host-local state, and one host of a pod rolling back
while the others step forward is exactly the divergence Mesh-TensorFlow
(PAPERS.md) names as the failure mode of single-program multi-host training.
Three parts, all identity / disarmed when ``jax.process_count() == 1`` so
single-host runs are bit-identical:

**1. Step-consensus bus** (:class:`ConsensusBus`). Each optimizer step every
process contributes a compact control word — preempt flag, spike-rollback
request, guard-skip observed, data-worker-error flag, save-now request — to a
``multihost_utils.process_allgather`` OR-reduce, so all hosts take the *same*
action on the *same* step: any-host preemption triggers the emergency save
everywhere, rollback is a pod-wide decision restoring the same verified
checkpoint and data cursor, and a data-worker failure on one host becomes a
coordinated abort (:data:`resilience.DATA_ABORT_EXIT_CODE`) instead of N-1
hosts deadlocked in a collective. The exchange happens BEFORE the step
dispatch (the batch fetch preceding it is host-local and can never block on a
peer), which is what makes the worker-failure case sound: the failing host
still reaches the exchange, so the pod agrees to abort before anyone enters
the train step's collectives.

**2. Desync detector** (:func:`fingerprint_params` + :func:`check_fingerprints`).
Every ``--desync_check_every`` steps a cheap device-side parameter fingerprint
— per-leaf sums reduced to one scalar — is computed per host, allgathered and
compared. In the healthy case the scalar is identical everywhere (same
program, same data); a mismatch names the offending ranks, increments the
``desync_detected`` metric and routes into the existing
rollback-to-last-verified path rather than letting corruption train onward.

**3. Hang watchdog** (:class:`HangWatchdog`). A daemon thread armed around the
step loop; if no step completes within ``--hang_timeout_s`` (collective
deadlock, peer host died), it dumps all-thread stacks via ``faulthandler``,
runs a bounded best-effort emergency-save callback, and exits with
:data:`resilience.HANG_EXIT_CODE` — which ``scripts/supervise.sh`` maps to
"restart the whole job" (burning a restart attempt, unlike preemption's
rc 143) — turning an infinite hang into a bounded restart.

Everything here is exercisable under ``JAX_PLATFORMS=cpu``: single-process
units in ``tests/test_coordination.py``, the real 2-process consensus paths in
``tests/test_multihost.py`` / ``tests/_multihost_worker.py``.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time
from collections import Counter
from typing import Any, Callable, NamedTuple

from gpt_2_distributed_tpu.obs.trace import get_tracer
from gpt_2_distributed_tpu.resilience import HANG_EXIT_CODE

# --- part 1: step-consensus control word -------------------------------------

# Control-word bits, OR-reduced across processes each step. Adding a bit is a
# protocol change: every process must run the same code version (the OR of
# words from mismatched versions would silently drop the new bit on old hosts).
CTRL_PREEMPT = 1 << 0        # this host saw SIGTERM / a cloud preemption notice
CTRL_ROLLBACK = 1 << 1       # this host's spike monitor requested a rollback
CTRL_SKIP = 1 << 2           # this host observed a guard-skipped step
CTRL_WORKER_ERROR = 1 << 3   # a data-worker thread died on this host
CTRL_SAVE_NOW = 1 << 4       # this host requests an immediate checkpoint

_ALL_BITS = (
    CTRL_PREEMPT | CTRL_ROLLBACK | CTRL_SKIP | CTRL_WORKER_ERROR | CTRL_SAVE_NOW
)


class ControlWord(NamedTuple):
    """Decoded control word — one bool per protocol bit."""

    preempt: bool = False
    rollback: bool = False
    skip: bool = False
    worker_error: bool = False
    save_now: bool = False


def encode_control_word(
    preempt: bool = False,
    rollback: bool = False,
    skip: bool = False,
    worker_error: bool = False,
    save_now: bool = False,
) -> int:
    """Pack the per-host fault flags into one OR-reducible integer."""
    return (
        (CTRL_PREEMPT if preempt else 0)
        | (CTRL_ROLLBACK if rollback else 0)
        | (CTRL_SKIP if skip else 0)
        | (CTRL_WORKER_ERROR if worker_error else 0)
        | (CTRL_SAVE_NOW if save_now else 0)
    )


def decode_control_word(word: int) -> ControlWord:
    return ControlWord(
        preempt=bool(word & CTRL_PREEMPT),
        rollback=bool(word & CTRL_ROLLBACK),
        skip=bool(word & CTRL_SKIP),
        worker_error=bool(word & CTRL_WORKER_ERROR),
        save_now=bool(word & CTRL_SAVE_NOW),
    )


def or_reduce_words(words: list[int] | Any) -> int:
    """The bus's reduction, exposed for unit tests: bitwise OR over per-host
    words (any host raising a flag raises it for the pod)."""
    out = 0
    for w in words:
        out |= int(w)
    return out


class ConsensusBus:
    """Per-step OR-reduce of host control words across all processes.

    ``exchange(word)`` returns the pod-agreed word. Identity fast path when
    ``process_count() == 1``: no allgather is dispatched at all, so
    single-host behavior (and the CLI e2e suite) is bit-identical with the
    bus in the loop. Each exchange is a ``consensus_exchange`` span, which
    is where its overhead is read.
    """

    def __init__(self) -> None:
        import jax

        self.process_count = jax.process_count()

    def exchange(self, word: int) -> int:
        # The span lives here (not at the call site) so every exchange — the
        # step loop's, the epoch boundary's — lands in the trace
        # under one name, parented by whatever span the caller has open.
        with get_tracer().span("consensus_exchange", word=int(word)):
            if word & ~_ALL_BITS:
                raise ValueError(f"control word {word:#x} has unknown bits set")
            if self.process_count == 1:
                agreed = int(word)
            else:
                import numpy as np
                from jax.experimental import multihost_utils

                gathered = multihost_utils.process_allgather(
                    np.asarray(word, np.int64)
                )
                agreed = or_reduce_words(np.ravel(gathered))
        return agreed


# --- part 2: cross-host desync detector --------------------------------------

_fingerprint_jit = None


def fingerprint_params(params: Any) -> float:
    """One fp32 scalar summarizing the parameter tree, computed device-side.

    Per-leaf sums (cast to fp32) tree-reduced to a single scalar — one tiny
    fused kernel per call, no host transfer of anything but the scalar. In a
    healthy pod the value every host reads back is identical: the reduction
    over each leaf's shards happens inside that host's replica group, on data
    that replication guarantees equal. A host whose replicated state drifted
    (the classic desync: divergent host inputs, a missed update, bit corruption
    on one VM) reads back a different scalar — which is exactly what
    :func:`check_fingerprints` compares.
    """
    global _fingerprint_jit
    if _fingerprint_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _fp(tree):
            total = jnp.zeros((), jnp.float32)
            for leaf in jax.tree_util.tree_leaves(tree):
                total = total + jnp.sum(leaf.astype(jnp.float32))
            return total

        _fingerprint_jit = _fp
    return float(_fingerprint_jit(params))


def check_fingerprints(fingerprint: float) -> list[int]:
    """Allgather this host's fingerprint and return the mismatched ranks
    (empty = pod in sync; always empty single-process — nothing to compare).

    "Mismatched" means differing from the modal (most common) value, so the
    report names the minority hosts — the ones that drifted — rather than
    everyone. Comparison is exact: identical programs over identical data
    produce bit-identical floats, so any difference is a real divergence.
    """
    import jax

    if jax.process_count() == 1:
        return []
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = np.ravel(
        multihost_utils.process_allgather(np.asarray(fingerprint, np.float64))
    )
    return mismatched_ranks([float(v) for v in gathered])


def assert_pod_agreement(name: str, value: float) -> None:
    """Startup barrier for elastic resume: every host allgathers ``value`` and
    the pod fails loudly if any rank disagrees, naming the minority ranks.

    After a world resize each host independently peeks the checkpoint's world
    record and re-derives the mesh / grad-accum rescale; a host reading a
    stale save_dir replica (or launched with drifted flags) would otherwise
    desync the pod on the first collective. No-op single-process; doubles as
    a rendezvous, so the new (smaller) world has barriered before any real
    collective runs.
    """
    import jax

    if jax.process_count() == 1:
        return
    import numpy as np
    from jax.experimental import multihost_utils

    with get_tracer().span("pod_barrier", barrier=name):
        gathered = np.ravel(
            multihost_utils.process_allgather(np.asarray(value, np.float64))
        )
    bad = mismatched_ranks([float(v) for v in gathered])
    if bad:
        raise RuntimeError(
            f"pod disagrees on {name} at startup: rank(s) "
            f"{', '.join(str(r) for r in bad)} differ "
            f"(gathered {[float(v) for v in gathered]}); all hosts must "
            f"observe the same checkpoint world record and launch flags"
        )


def mismatched_ranks(values: list[float]) -> list[int]:
    """Ranks whose value differs from the modal value (ties broken toward the
    lowest rank's value, so a 1v1 split blames the higher rank)."""
    if not values:
        return []
    counts = Counter(values)
    top = max(counts.values())
    modal = next(v for v in values if counts[v] == top)
    return [i for i, v in enumerate(values) if v != modal]


_perturb_jit = None


def perturb_params(params: Any, factor) -> Any:
    """Scale every parameter leaf by ``factor`` (dtype-preserving).

    Fault injection for the desync detector (--inject_desync_at): every rank
    dispatches this identically — SPMD-symmetric, so the injection cannot
    itself deadlock the collectives it is testing — and only the chosen
    rank's *value* of ``factor`` differs from 1.0. ``factor`` is a traced
    argument, so differing values never retrace or bake into the program.
    """
    global _perturb_jit
    if _perturb_jit is None:
        import jax

        @jax.jit
        def _p(tree, f):
            return jax.tree_util.tree_map(
                lambda x: (x * f).astype(x.dtype), tree
            )

        _perturb_jit = _p
    return _perturb_jit(params, factor)


# --- part 3: hang watchdog ----------------------------------------------------


class HangWatchdog:
    """Daemon thread that bounds how long the pod can sit in a dead collective.

    The driver calls :meth:`arm` when it enters the step loop and
    :meth:`beat` each time an optimizer step completes; if no beat arrives
    within ``timeout_s`` the watchdog fires: it dumps every thread's stack via
    ``faulthandler`` (the post-mortem for "which collective were we stuck
    in"), runs the ``on_hang`` callback — best-effort, on its own daemon
    thread, abandoned after ``grace_s`` (an emergency save attempted while
    collectives are dead may itself hang) — and hard-exits with
    ``exit_code`` (:data:`resilience.HANG_EXIT_CODE`). ``disarm`` around
    phases with no step cadence (restore, teardown/final save).

    ``_exit`` is injectable so unit tests observe the firing instead of dying.
    """

    def __init__(
        self,
        timeout_s: float,
        on_hang: Callable[[], None] | None = None,
        exit_code: int = HANG_EXIT_CODE,
        grace_s: float = 10.0,
        _exit: Callable[[int], None] = os._exit,
    ) -> None:
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.on_hang = on_hang
        self.exit_code = int(exit_code)
        self.grace_s = float(grace_s)
        self.fired = False
        self._exit = _exit
        self._armed = False
        self._deadline = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "HangWatchdog":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="hang-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def arm(self) -> None:
        with self._lock:
            self._armed = True
            self._deadline = time.monotonic() + self.timeout_s

    def beat(self) -> None:
        """A step completed — push the deadline out (no-op while disarmed)."""
        with self._lock:
            if self._armed:
                self._deadline = time.monotonic() + self.timeout_s

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _run(self) -> None:
        interval = min(self.timeout_s / 4.0, 0.5)
        while not self._stop.wait(interval):
            with self._lock:
                expired = self._armed and time.monotonic() > self._deadline
            if expired:
                self._fire()
                return

    def _fire(self) -> None:
        self.fired = True
        print(
            f"[watchdog] no optimizer step completed in {self.timeout_s:g}s "
            f"(collective deadlock or dead peer host?); dumping stacks and "
            f"exiting rc {self.exit_code} for a supervised full-job restart",
            flush=True,
        )
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:
            pass
        # Raw stacks name the *frame* the pod died in; the span stack names
        # the *phase* — "step > step_dispatch" vs "step > consensus_exchange"
        # is the first question a hang post-mortem asks.
        try:
            tracer = get_tracer()
            if tracer.enabled:
                msg = "[watchdog] " + tracer.format_open_spans()
                print(msg, flush=True)
                print(msg, file=sys.stderr, flush=True)
                tracer.event("hang_watchdog_fired", timeout_s=self.timeout_s)
        except Exception:
            pass
        if self.on_hang is not None:
            # Bounded best effort: the save runs on its own daemon thread and
            # is abandoned (not cancelled — the process is about to die
            # anyway) if it exceeds the grace window.
            t = threading.Thread(
                target=self._run_on_hang, name="watchdog-emergency", daemon=True
            )
            t.start()
            t.join(self.grace_s)
            if t.is_alive():
                print(
                    f"[watchdog] emergency save did not finish within "
                    f"{self.grace_s:g}s grace; abandoning it",
                    flush=True,
                )
        self._exit(self.exit_code)

    def _run_on_hang(self) -> None:
        try:
            self.on_hang()
        except BaseException as exc:  # the process is exiting; log only
            print(
                f"[watchdog] emergency save failed: "
                f"{type(exc).__name__}: {exc}",
                flush=True,
            )
