"""Resilience subsystem: four layers of defense for long training runs.

The reference codebase has no fault tolerance at all — its ``load_checkpoint``
is an empty stub (SURVEY.md C13) and a crash loses the run. Earlier rounds
rebuilt resume + supervised restart (``scripts/supervise.sh``,
``--inject_fail_at``); this module covers the failure classes a restart alone
cannot: a loss that blows up and poisons the params, a preemption that kills
the pod mid-step, and a newest checkpoint that is truncated on disk — the
skip/rollback and update-discipline playbook of large-scale pjit training
("Scalable Training of Language Models using JAX pjit and TPUv4", PAPERS.md).

Layer 1 — **in-step anomaly guard** (jit-side). ``make_train_step(guard=True)``
(``parallel/train_step.py``) carries a :class:`GuardState` through the step and
``lax.cond``-gates the optimizer update on ``isfinite(loss) &
isfinite(grad_norm)``: a non-finite step applies the *identity* update
(params/opt-state bit-unchanged), increments ``skipped_steps`` and records a
reason code — surfaced as registry metrics (``metrics/builtin.py``).

Layer 2 — **loss-spike rollback** (host-side). :class:`SpikeMonitor` keeps an
EMA mean/variance of the loss and flags spikes by z-score
(``--spike_sigma``); after ``--max_consecutive_skips`` consecutive
skipped/spiking steps the driver restores the last *verified* checkpoint and
fast-forwards the dataloader past the offending batches via the existing O(1)
arithmetic skip (``data/dataloader.py``).

Layer 3 — **checkpoint integrity**. :func:`write_manifest` records per-entry
sizes (every file) and CRC32C (files up to :data:`CRC_MAX_BYTES` — meta.json
and the orbax metadata/commit markers are always small enough) into
``manifest.json``, written last via tmp + ``os.replace`` so it doubles as the
atomic commit point. :func:`verify_checkpoint` validates it;
``checkpoint.restore_latest_verified`` falls back step by step to the newest
checkpoint that passes, logging what was discarded.

Layer 4 — **preemption-safe shutdown**. :class:`PreemptionHandler` turns
SIGTERM (the TPU preemption contract: the maintenance notice arrives as a
signal, then the VM dies) into a flag the driver checks at each optimizer-step
boundary; one emergency checkpoint lands in the normal ``step_*`` layout and
the process exits rc 143, which ``scripts/supervise.sh`` treats as resumable
without burning a restart attempt.

Everything here is exercisable under ``JAX_PLATFORMS=cpu``
(``tests/test_resilience.py``).
"""

from __future__ import annotations

import json
import math
import os
import re
import signal
import threading
import urllib.request
from typing import NamedTuple

# --- layer 1: guard state carried through the jitted train step -------------

# Reason codes for a skipped step (int32 on device; 0 = never skipped).
SKIP_NONE = 0
SKIP_NONFINITE_LOSS = 1
SKIP_NONFINITE_GRAD = 2
SKIP_REASON_NAMES = {
    SKIP_NONE: "none",
    SKIP_NONFINITE_LOSS: "nonfinite_loss",
    SKIP_NONFINITE_GRAD: "nonfinite_grad",
}


class GuardState(NamedTuple):
    """Anomaly-guard counters carried in train state (device scalars)."""

    skipped_steps: object   # int32 scalar — total updates skipped this run
    last_skip_reason: object  # int32 scalar — SKIP_* code of the latest skip
    clipped_steps: object   # int32 scalar — finite-but-huge grads clipped+applied


def init_guard_state() -> GuardState:
    import jax.numpy as jnp

    return GuardState(
        skipped_steps=jnp.zeros((), jnp.int32),
        last_skip_reason=jnp.zeros((), jnp.int32),
        clipped_steps=jnp.zeros((), jnp.int32),
    )


# --- layer 2: host-side loss-spike monitor ----------------------------------


class SpikeMonitor:
    """EMA z-score loss monitor driving the rollback policy.

    ``observe(loss, skipped)`` per optimizer step returns:

    * ``None`` — step looks healthy (and updated the EMA baseline),
    * ``"anomaly"`` — the step was skipped by the guard, its loss is
      non-finite, or its z-score against the EMA baseline exceeds ``sigma``,
    * ``"rollback"`` — the ``max_consecutive``-th consecutive anomaly: the
      driver should restore the last verified checkpoint and skip forward
      past the offending batches.

    Anomalous losses never update the EMA (a spike must not poison the
    baseline it is judged against), and z-scoring only engages after
    ``warmup`` healthy observations so the fresh-run loss cliff is not
    misread as a spike. Non-finite/skipped steps count as anomalies from
    step one — they need no baseline.
    """

    def __init__(
        self,
        sigma: float = 6.0,
        max_consecutive: int = 3,
        warmup: int = 20,
        ema_decay: float = 0.98,
    ) -> None:
        if sigma <= 0:
            raise ValueError(f"sigma must be > 0, got {sigma}")
        if max_consecutive < 1:
            raise ValueError(f"max_consecutive must be >= 1, got {max_consecutive}")
        self.sigma = float(sigma)
        self.max_consecutive = int(max_consecutive)
        self.warmup = int(warmup)
        self.ema_decay = float(ema_decay)
        self.reset()

    def reset(self) -> None:
        """Full reset (after a rollback the restored params live in an older
        loss regime, so the baseline restarts too)."""
        self.mean = 0.0
        self.var = 0.0
        self.n_healthy = 0
        self.consecutive = 0

    def state_dict(self) -> dict:
        """JSON-serializable EMA baseline for checkpoint ``meta.json``
        (ROADMAP resilience follow-up b): persisting mean/var/n_healthy lets
        a ``--resume`` relaunch keep its spike baseline instead of sitting
        through a fresh ``warmup`` window blind to spikes. ``consecutive`` is
        deliberately NOT saved — an anomaly streak must not survive a
        restart that may well have fixed its cause."""
        return {
            "mean": self.mean,
            "var": self.var,
            "n_healthy": self.n_healthy,
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a ``state_dict()`` baseline; resets the anomaly streak."""
        self.mean = float(state["mean"])
        self.var = float(state["var"])
        self.n_healthy = int(state["n_healthy"])
        self.consecutive = 0

    def _threshold(self) -> float:
        # Std floor: a converged, nearly-flat loss would otherwise turn
        # ordinary batch noise into huge z-scores.
        return self.sigma * max(math.sqrt(self.var), 1e-3 + 0.01 * abs(self.mean))

    def observe(self, loss: float, skipped: bool = False) -> str | None:
        loss = float(loss)
        anomaly = bool(skipped) or not math.isfinite(loss)
        if not anomaly and self.n_healthy >= self.warmup:
            # One-sided: only upward spikes are pathological.
            anomaly = (loss - self.mean) > self._threshold()
        if anomaly:
            self.consecutive += 1
            if self.consecutive >= self.max_consecutive:
                return "rollback"
            return "anomaly"
        self.consecutive = 0
        if self.n_healthy == 0:
            self.mean = loss
        else:
            delta = loss - self.mean
            self.mean += (1.0 - self.ema_decay) * delta
            self.var = self.ema_decay * (self.var + (1.0 - self.ema_decay) * delta * delta)
        self.n_healthy += 1
        return None


# --- layer 3: checkpoint manifest + verification ----------------------------

MANIFEST_NAME = "manifest.json"
# Files up to this size get a CRC32C in the manifest; larger files (sharded
# array data at real model sizes) are size-checked only — truncation, the
# on-disk failure mode this layer exists for, is caught by size alone, and a
# pure-python CRC over multi-GiB array files would stall every save/restore.
CRC_MAX_BYTES = 1024 * 1024

_CRC32C_TABLE: list[int] = []


def _crc32c_table() -> list[int]:
    if not _CRC32C_TABLE:
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
            _CRC32C_TABLE.append(c)
    return _CRC32C_TABLE


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC-32C (Castagnoli) — the checksum TFRecord/orbax ecosystems use.
    Pure python (no google-crc32c wheel in the image); ~0.2 s/MiB, bounded
    by CRC_MAX_BYTES above. Check value: crc32c(b"123456789") = 0xE3069283."""
    table = _crc32c_table()
    crc = ~crc & 0xFFFFFFFF
    for b in data:
        crc = (crc >> 8) ^ table[(crc ^ b) & 0xFF]
    return ~crc & 0xFFFFFFFF


def _file_crc32c(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(256 * 1024), b""):
            crc = crc32c(chunk, crc)
    return crc


def build_manifest(path: str, step: int) -> dict:
    """Inventory every file under a checkpoint dir: relative path + size for
    all, CRC32C for files <= CRC_MAX_BYTES (always includes meta.json and the
    orbax metadata/commit-marker files — they are tiny).

    The commit-protocol marker files (checkpoint.py: ``.INPROGRESS`` removed
    and ``COMMITTED`` created at commit, AFTER the manifest is written) are
    excluded — recording them would make the manifest stale the moment the
    commit completes.
    """
    entries = []
    for root, _dirs, files in os.walk(path):
        for name in sorted(files):
            fp = os.path.join(root, name)
            rel = os.path.relpath(fp, path)
            if rel in (
                MANIFEST_NAME, MANIFEST_NAME + ".tmp",
                ".INPROGRESS", "COMMITTED", "COMMITTED.tmp",
            ):
                continue
            size = os.path.getsize(fp)
            entry: dict = {"path": rel, "size": size}
            if size <= CRC_MAX_BYTES:
                entry["crc32c"] = format(_file_crc32c(fp), "08x")
            entries.append(entry)
    entries.sort(key=lambda e: e["path"])
    return {"format": 1, "step": int(step), "entries": entries}


def write_manifest(path: str, step: int) -> str:
    """Write ``manifest.json`` last, via tmp + atomic rename — the manifest's
    existence is the commit point: a checkpoint without one (crash mid-save)
    is at best legacy, never trusted as fully verified."""
    manifest = build_manifest(path, step)
    target = os.path.join(path, MANIFEST_NAME)
    tmp = target + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, target)
    return target


def verify_checkpoint(path: str) -> list[str]:
    """Validate one checkpoint dir; returns a list of problems (empty =
    verified).

    With a manifest: every entry must exist with the recorded size, and match
    its CRC32C where one was recorded. Without one (legacy checkpoint from
    before this layer, or a save that died before its commit point): basic
    structural checks only — ``meta.json`` parses and the array dirs exist —
    so pre-manifest checkpoints stay restorable but a truncated meta still
    fails.
    """
    problems: list[str] = []
    try:
        with open(os.path.join(path, "meta.json")) as f:
            json.load(f)
    except (OSError, ValueError) as exc:
        problems.append(f"meta.json unreadable: {exc}")
    for item in ("params", "opt_state"):
        if not os.path.isdir(os.path.join(path, item)):
            problems.append(f"{item}/ missing")

    manifest_path = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        return problems  # legacy: structural checks above are all we have
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
        entries = manifest["entries"]
    except (OSError, ValueError, KeyError, TypeError) as exc:
        problems.append(f"{MANIFEST_NAME} unreadable: {exc}")
        return problems
    for entry in entries:
        rel = entry["path"]
        fp = os.path.join(path, rel)
        if not os.path.exists(fp):
            problems.append(f"{rel}: missing")
            continue
        size = os.path.getsize(fp)
        if size != entry["size"]:
            problems.append(f"{rel}: size {size} != recorded {entry['size']}")
            continue
        want = entry.get("crc32c")
        if want is not None:
            got = format(_file_crc32c(fp), "08x")
            if got != want:
                problems.append(f"{rel}: crc32c {got} != recorded {want}")
    return problems


# --- layer 4: preemption-safe shutdown --------------------------------------

PREEMPTED_EXIT_CODE = 143  # 128 + SIGTERM: the conventional "killed by TERM" rc

# Multi-host control-plane exit codes (coordination.py). Both are restarts
# that BURN a supervise.sh attempt, unlike preemption's free rc 143: a hang
# or a data-worker death is a fault, not scheduled infrastructure churn.
HANG_EXIT_CODE = 170        # hang watchdog fired: no step within --hang_timeout_s
DATA_ABORT_EXIT_CODE = 171  # pod-wide coordinated abort: a data worker died


class PreemptionHandler:
    """SIGTERM -> flag, checked by the driver at each optimizer-step boundary.

    TPU preemptions deliver SIGTERM with a grace window before the VM dies;
    killing training mid-``train_step`` would strand a partial orbax write,
    so the handler only *records* the signal and the driver saves one
    emergency checkpoint at the next step boundary, then exits
    :data:`PREEMPTED_EXIT_CODE` for ``supervise.sh`` to relaunch with
    ``--resume``.

    The serving stack reuses the same flag for graceful drain (SIGTERM to
    ``gpt2-tpu-serve`` / ``gpt2-tpu-frontend`` finishes in-flight requests,
    rejects new submits, exits 0) — ``notice`` swaps the announcement for
    one that matches what the driver will actually do.
    """

    def __init__(
        self,
        signals: tuple[int, ...] = (signal.SIGTERM,),
        notice: str | None = None,
    ) -> None:
        self.signals = signals
        self.notice = notice or (
            f"will save an emergency checkpoint and exit "
            f"{PREEMPTED_EXIT_CODE} at the next step boundary"
        )
        self._flag = False
        self._prev: dict[int, object] = {}

    def _on_signal(self, signum, frame) -> None:  # noqa: ARG002 — signal API
        self.trigger(f"received signal {signum}")

    def trigger(self, reason: str) -> None:
        """Raise the preemption flag from any source — the signal handler,
        or :class:`PreemptionPoller` when the cloud metadata endpoint posts a
        preemption notice. Safe from any thread (a bool store is atomic under
        the GIL), idempotent."""
        already = self._flag
        self._flag = True
        if not already:
            from gpt_2_distributed_tpu.obs.trace import get_tracer

            get_tracer().event("preempt_notice", reason=reason)
            print(f"[preempt] {reason}; {self.notice}", flush=True)

    def install(self) -> "PreemptionHandler":
        """Install handlers (main thread only — the signal-module contract);
        re-installation resets the flag, so one handler object can serve
        repeated in-process runs (tests)."""
        self._flag = False
        for s in self.signals:
            self._prev[s] = signal.signal(s, self._on_signal)
        return self

    def uninstall(self) -> None:
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        self._prev.clear()

    def preempted(self) -> bool:
        return self._flag


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def forced_host_device_env(n_devices: int, extra: dict | None = None) -> dict:
    """Subprocess env pinned to exactly ``n_devices`` virtual CPU devices.

    The force-before-jax-import dance (JAX_PLATFORMS=cpu, any pre-existing
    forced count in XLA_FLAGS replaced, highest matmul precision, repo on
    PYTHONPATH) packaged for child processes. Hoisted here from
    ``tests/conftest.py`` so the serving worker spawner (process-isolated
    replicas on a CPU host) and the test suite share one implementation —
    the pattern can't drift between library and tests. jax-free on purpose:
    the spawner builds worker envs before the frontend ever imports jax.
    ``extra`` overlays additional vars last.
    """
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
    env["PYTHONPATH"] = (
        _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    if extra:
        env.update(extra)
    return env


class InjectedFault(RuntimeError):
    """Raised by :class:`FaultInjector` at its configured trigger point."""


def parse_fault_spec(spec: str, flag: str) -> tuple[int, int | None]:
    """Parse ``"STEP"`` or ``"STEP:REPLICA"`` (the ``--inject_*`` flag
    grammar, mirroring ``--xla_profile_at``'s ``STEP[:N]``). Returns
    ``(step, replica)`` with ``replica=None`` meaning "first replica
    stepped at/after STEP". Import-light on purpose: ``serve.py`` and
    ``frontend/server.py`` refuse a bad spec before jax loads."""
    parts = str(spec).split(":")
    if len(parts) > 2:
        raise ValueError(f"{flag}={spec!r}: expected STEP or STEP:REPLICA")
    try:
        step = int(parts[0])
        replica = int(parts[1]) if len(parts) == 2 else None
    except ValueError:
        raise ValueError(
            f"{flag}={spec!r}: STEP and REPLICA must be integers"
        ) from None
    if step < 1:
        raise ValueError(f"{flag}={spec!r}: STEP must be >= 1")
    if replica is not None and replica < 0:
        raise ValueError(f"{flag}={spec!r}: REPLICA must be >= 0")
    return step, replica


class FaultInjector:
    """Deterministic fault injection for the serving fleet (tests and the
    ``--inject_*`` flags — never constructed in production).

    The driver calls :meth:`tick(step, replica)` immediately before each
    replica's ``step()``, inside the containment wrapper and the watchdog
    window. Each configured fault fires ONCE, at the first tick whose
    fleet step is >= the spec's STEP and whose replica matches (``>=``,
    not ``==``: a replica with no work that tick would otherwise dodge
    the fault forever):

    * ``fail_at``  — raise :class:`InjectedFault`: the "replica crashed"
      scenario (containment + migration).
    * ``hang_at``  — block cooperatively until :meth:`release_hangs`
      (the watchdog's trip path calls it) or ``hang_max_s``, then raise:
      the "replica wedged" scenario. A real hang can't be interrupted
      from within; the cooperative version lets tests drive the whole
      detect -> condemn -> migrate chain deterministically.
    * ``exception_at`` — replica-agnostic raise: the original
      fleet-killer at driver.py's step loop, now contained.
    * ``kill_at`` + ``kill_fn`` — call ``kill_fn(replica)`` and return
      WITHOUT raising: the "process killed from outside" scenario for
      subprocess placement. The chaos bench passes a ``kill_fn`` that
      SIGKILLs/SIGSTOPs the worker process; death then surfaces the way
      it would in production — as a broken or timed-out RPC on the very
      step the injector just allowed to proceed.
    """

    def __init__(
        self,
        fail_at: tuple[int, int | None] | None = None,
        hang_at: tuple[int, int | None] | None = None,
        exception_at: int | None = None,
        hang_max_s: float = 120.0,
        kill_at: tuple[int, int | None] | None = None,
        kill_fn=None,
    ) -> None:
        self.fail_at = fail_at
        self.hang_at = hang_at
        self.exception_at = exception_at
        self.hang_max_s = float(hang_max_s)
        self.kill_at = kill_at
        self.kill_fn = kill_fn
        self.fail_fired = False
        self.hang_fired = False
        self.exception_fired = False
        self.kill_fired = False
        self._release = threading.Event()

    @staticmethod
    def _match(spec, step: int, replica: int) -> bool:
        return step >= spec[0] and (spec[1] is None or replica == spec[1])

    def release_hangs(self) -> None:
        """Unblock any in-progress (and all future) injected hangs."""
        self._release.set()

    def tick(self, step: int, replica: int) -> None:
        if (self.kill_at is not None and not self.kill_fired
                and self.kill_fn is not None
                and self._match(self.kill_at, step, replica)):
            self.kill_fired = True
            self.kill_fn(replica)
            # No raise: the kill lands out-of-band and must be DETECTED
            # (broken RPC, heartbeat loss), not politely reported.
        if (self.fail_at is not None and not self.fail_fired
                and self._match(self.fail_at, step, replica)):
            self.fail_fired = True
            raise InjectedFault(
                f"injected replica failure (step {step}, replica {replica})"
            )
        if (self.exception_at is not None and not self.exception_fired
                and step >= self.exception_at):
            self.exception_fired = True
            raise InjectedFault(f"injected step exception (step {step})")
        if (self.hang_at is not None and not self.hang_fired
                and self._match(self.hang_at, step, replica)):
            self.hang_fired = True
            released = self._release.wait(self.hang_max_s)
            raise InjectedFault(
                f"injected replica hang (step {step}, replica {replica}) "
                + ("released by watchdog" if released
                   else f"expired after {self.hang_max_s:g}s")
            )


# GCE metadata server's preemption endpoint: returns "TRUE" once the VM has
# been marked for preemption. Requires the Metadata-Flavor header; only
# reachable from inside a GCE/TPU VM (tests inject a file:// URL instead).
GCE_METADATA_PREEMPTED_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/preempted"
)


class PreemptionPoller:
    """Poll a cloud preemption-notice endpoint; raise the same flag as
    :class:`PreemptionHandler`.

    SIGTERM (layer 4 above) is the *guaranteed* notice, but on GCE/TPU the
    metadata server often flips ``instance/preempted`` to ``TRUE`` seconds
    earlier than the signal lands — polling it buys extra grace time for the
    emergency save. The poller runs on a daemon thread, checks every
    ``interval_s``, and on a notice calls ``handler.trigger`` (when a handler
    is attached) as well as setting its own flag, so the driver's existing
    single ``preempted()`` check covers both sources.

    Endpoint errors are counted (``poll_errors``) but never raise: off-cloud
    the hostname simply doesn't resolve and the poller stays quiet. ``url``
    accepts anything ``urllib`` can open — tests point it at a ``file://``
    notice file and flip its contents to TRUE.
    """

    def __init__(
        self,
        url: str = GCE_METADATA_PREEMPTED_URL,
        interval_s: float = 5.0,
        handler: PreemptionHandler | None = None,
    ) -> None:
        self.url = url
        self.interval_s = interval_s
        self.handler = handler
        self.poll_errors = 0
        self._flag = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def poll_once(self) -> bool:
        """One synchronous check; True iff the endpoint reports preemption."""
        try:
            req = urllib.request.Request(
                self.url, headers={"Metadata-Flavor": "Google"}
            )
            with urllib.request.urlopen(req, timeout=2) as resp:
                body = resp.read(64).decode("utf-8", "replace").strip()
            return body.upper().startswith("TRUE")
        except Exception:
            self.poll_errors += 1
            return False

    def _run(self) -> None:
        while not self._stop.is_set():
            if self.poll_once():
                self._flag = True
                print(
                    "[preempt] cloud preemption notice "
                    f"({self.url})",
                    flush=True,
                )
                if self.handler is not None:
                    self.handler.trigger("cloud preemption notice")
                return
            self._stop.wait(self.interval_s)

    def start(self) -> "PreemptionPoller":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="preempt-poller", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def preempted(self) -> bool:
        return self._flag
