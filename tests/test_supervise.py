"""Supervised-restart integration (round-4 VERDICT missing #2).

``scripts/supervise.sh`` plays the process-level restart-on-failure role
torchrun plays for the reference's launchers
(``/root/reference/scripts/run_training_distributed_fsdp_main.sh:15-20``) —
but where torchrun restarts from scratch (the reference's load_checkpoint is
an empty stub, ``/root/reference/train_gpt2_distributed.py:104-111``), the
wrapper appends ``--resume`` so a relaunch continues from the latest
checkpoint cursor. The end-to-end test crashes a real training subprocess
mid-epoch (one-shot ``--inject_fail_at``) and asserts the relaunch resumed
from the last pre-crash checkpoint and finished the full run.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUPERVISE = os.path.join(REPO, "scripts", "supervise.sh")


def _env(max_restarts: str) -> dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MAX_RESTARTS"] = max_restarts
    env["RESTART_DELAY"] = "0"
    return env


def test_supervise_passes_through_success():
    # `true --resume` exits 0: the wrapper must not restart or alter rc.
    r = subprocess.run(
        ["bash", SUPERVISE, "true"], env=_env("3"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    assert "restart" not in r.stderr


def test_supervise_cleans_stale_uncommitted_dirs(tmp_path):
    """A crash mid-async-save leaves step dirs with .INPROGRESS but no
    COMMITTED; the wrapper removes them before (re)launching. Legacy dirs
    (no markers) and committed dirs are untouched. Both --save_dir spellings
    must be parsed."""
    save_dir = tmp_path / "ckpt"
    stale = save_dir / "step_0000005"
    stale.mkdir(parents=True)
    (stale / ".INPROGRESS").write_text("1\n")
    committed = save_dir / "step_0000004"
    committed.mkdir()
    (committed / "COMMITTED").write_text("{}")
    legacy = save_dir / "step_0000003"
    legacy.mkdir()
    (legacy / "meta.json").write_text("{}")

    r = subprocess.run(
        ["bash", SUPERVISE, "true", "--save_dir", str(save_dir)],
        env=_env("0"), capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    assert "removing stale uncommitted checkpoint" in r.stderr
    assert "step_0000005" in r.stderr
    assert not stale.exists()
    assert committed.exists() and legacy.exists()

    # --save_dir=DIR spelling; nothing stale left -> silent no-op.
    (stale).mkdir()
    (stale / ".INPROGRESS").write_text("1\n")
    r = subprocess.run(
        ["bash", SUPERVISE, "true", f"--save_dir={save_dir}"],
        env=_env("0"), capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    assert not stale.exists()
    assert committed.exists() and legacy.exists()


def test_supervise_bounded_restarts_then_gives_up():
    # A persistently failing command is relaunched MAX_RESTARTS times, then
    # the wrapper exits with the command's last rc (torchrun --max_restarts).
    r = subprocess.run(
        ["bash", SUPERVISE, "false"], env=_env("2"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert r.stderr.count("restart") >= 2
    assert "giving up after 2 restarts" in r.stderr


def test_supervise_preemption_rc143_does_not_burn_attempts(tmp_path):
    # rc 143 is the preemption contract (train.py PreemptionHandler): the
    # wrapper must relaunch WITHOUT counting a MAX_RESTARTS attempt — proven
    # by MAX_RESTARTS=0, under which any counted failure would give up
    # immediately. The stub "trainer" exits 143 twice (marker files), then 0.
    marker = tmp_path / "preempts"
    script = tmp_path / "fake_train.sh"
    script.write_text(
        "#!/usr/bin/env bash\n"
        f'n=$(ls "{marker}".* 2>/dev/null | wc -l)\n'
        'if [ "$n" -lt 2 ]; then\n'
        f'  touch "{marker}.$n"\n'
        "  exit 143\n"
        "fi\n"
        "exit 0\n"
    )
    script.chmod(0o755)
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=_env("0"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert r.stderr.count("preempted (rc=143)") == 2
    assert "giving up" not in r.stderr


def test_supervise_hang_rc170_restarts_but_burns_attempt(tmp_path):
    # rc 170 is the hang-watchdog contract (coordination.HangWatchdog): a
    # full-job restart is the recovery, but unlike rc 143 it IS a fault and
    # must count against MAX_RESTARTS. Stub exits 170 once, then 0: with
    # MAX_RESTARTS=1 the wrapper restarts once and the job completes.
    marker = tmp_path / "hangs"
    script = tmp_path / "fake_train.sh"
    script.write_text(
        "#!/usr/bin/env bash\n"
        f'if [ ! -e "{marker}" ]; then\n'
        f'  touch "{marker}"\n'
        "  exit 170\n"
        "fi\n"
        "exit 0\n"
    )
    script.chmod(0o755)
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=_env("1"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "hang watchdog fired (rc=170)" in r.stderr
    assert "restart 1/1" in r.stderr

    # The attempt-burning proof: under MAX_RESTARTS=0 the same rc gives up
    # immediately (a job that hangs every launch must not restart forever) —
    # exactly where rc 143 would have restarted for free.
    marker.unlink()
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=_env("0"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 170
    assert "hang watchdog fired (rc=170)" in r.stderr
    assert "giving up after 0 restarts" in r.stderr


def test_supervise_data_abort_rc171_burns_attempt(tmp_path):
    # rc 171 (pod-wide coordinated data-worker abort) follows the same
    # burns-an-attempt policy as 170, with its own diagnostic line.
    script = tmp_path / "fake_train.sh"
    script.write_text("#!/usr/bin/env bash\nexit 171\n")
    script.chmod(0o755)
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=_env("0"),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 171
    assert "data-worker abort (rc=171)" in r.stderr
    assert "giving up after 0 restarts" in r.stderr


def test_supervise_elastic_shrink_and_retry(tmp_path):
    # Elastic shrink-and-retry: repeated rc-143 preemptions with
    # ELASTIC_HOSTS_CMD set probe the live host count and relaunch the
    # survivors with WORLD_SIZE shrunk — without burning a MAX_RESTARTS
    # attempt (proven by MAX_RESTARTS=0). The stub "trainer" keeps exiting
    # 143 while WORLD_SIZE=2 and succeeds once relaunched at WORLD_SIZE=1.
    script = tmp_path / "fake_train.sh"
    script.write_text(
        "#!/usr/bin/env bash\n"
        'if [ "${WORLD_SIZE:-}" = "1" ]; then exit 0; fi\n'
        "exit 143\n"
    )
    script.chmod(0o755)
    env = _env("0")
    env["WORLD_SIZE"] = "2"
    env["ELASTIC_HOSTS_CMD"] = "echo 1"
    env["ELASTIC_SHRINK_AFTER"] = "2"
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    # Two preemptions at full size (SHRINK_AFTER=2), then the shrink.
    assert r.stderr.count("preempted (rc=143)") == 2
    assert "attempt counter unchanged: 0/0" in r.stderr
    assert "elastic shrink: 2 -> 1 host(s)" in r.stderr
    assert "does not count against MAX_RESTARTS" in r.stderr
    assert "giving up" not in r.stderr


def test_supervise_elastic_min_hosts_floor(tmp_path):
    # ELASTIC_MIN_HOSTS is the floor: when the probe reports fewer live
    # hosts, the wrapper refuses to shrink and gives up with the preemption
    # rc instead of relaunching a world too small to be worth training.
    script = tmp_path / "fake_train.sh"
    script.write_text("#!/usr/bin/env bash\nexit 143\n")
    script.chmod(0o755)
    env = _env("0")
    env["WORLD_SIZE"] = "4"
    env["ELASTIC_HOSTS_CMD"] = "echo 1"
    env["ELASTIC_MIN_HOSTS"] = "2"
    env["ELASTIC_SHRINK_AFTER"] = "1"
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 143, (r.stdout, r.stderr)
    assert "below ELASTIC_MIN_HOSTS=2" in r.stderr
    assert "refusing to shrink further" in r.stderr
    assert "elastic shrink:" not in r.stderr


def test_supervise_elastic_probe_failure_keeps_retrying(tmp_path):
    # A failing/garbage ELASTIC_HOSTS_CMD must not shrink or crash the
    # wrapper — the preemption keeps retrying at full size as if elastic
    # were off. The stub exits 143 twice, then succeeds.
    marker = tmp_path / "preempts"
    script = tmp_path / "fake_train.sh"
    script.write_text(
        "#!/usr/bin/env bash\n"
        f'n=$(ls "{marker}".* 2>/dev/null | wc -l)\n'
        'if [ "$n" -lt 2 ]; then\n'
        f'  touch "{marker}.$n"\n'
        "  exit 143\n"
        "fi\n"
        "exit 0\n"
    )
    script.chmod(0o755)
    env = _env("0")
    env["WORLD_SIZE"] = "2"
    env["ELASTIC_HOSTS_CMD"] = "echo not-a-number"
    env["ELASTIC_SHRINK_AFTER"] = "1"
    r = subprocess.run(
        ["bash", SUPERVISE, "bash", str(script)], env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert r.stderr.count("preempted (rc=143)") == 2
    assert "elastic shrink:" not in r.stderr and "giving up" not in r.stderr


def test_supervise_preempt_nan_grand_e2e(shard_dir, tmp_path):
    """The full resilience story through the wrapper: a NaN-poisoned step is
    skipped in place (guard), a SIGTERM preemption emergency-saves and exits
    rc 143, supervise relaunches without burning an attempt (MAX_RESTARTS=0),
    and the resumed run completes the full step budget."""
    save_dir = str(tmp_path / "ckpt")
    cmd = [
        "bash", SUPERVISE,
        sys.executable, "-m", "gpt_2_distributed_tpu.train",
        "--data_dir", shard_dir,
        "--n_layer", "2", "--n_embd", "32", "--n_head", "2",
        "--vocab_size", "257", "--seq_len", "32", "--batch", "4",
        "--grad_accum_steps", "1", "--lr", "1e-3", "--cli_every", "100",
        "--max_steps", "12", "--save_every", "4", "--save_dir", save_dir,
        "--inject_nan_at", "3", "--inject_preempt_at", "6",
    ]
    r = subprocess.run(
        cmd, env=_env("0"), cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "[guard] step 3 skipped (nonfinite_loss)" in r.stdout
    assert "[preempt] emergency checkpoint at step 6" in r.stdout
    assert "preempted (rc=143)" in r.stderr
    assert "resumed from" in r.stdout and "step 6" in r.stdout
    assert "training done: 12 optimizer steps" in r.stdout
    dirs = os.listdir(save_dir)
    assert "step_0000006" in dirs and "step_0000012" in dirs


def test_supervise_crash_resume_completes_run(shard_dir, tmp_path):
    """Kill training mid-epoch; the relaunch must resume from the checkpoint
    cursor (step 6, the last save before the step-7 crash) and finish."""
    save_dir = str(tmp_path / "ckpt")
    cmd = [
        "bash", SUPERVISE,
        sys.executable, "-m", "gpt_2_distributed_tpu.train",
        "--data_dir", shard_dir,
        "--n_layer", "2", "--n_embd", "32", "--n_head", "2",
        "--vocab_size", "257", "--seq_len", "32", "--batch", "4",
        "--grad_accum_steps", "1", "--lr", "1e-3", "--cli_every", "100",
        "--max_steps", "12", "--save_every", "3", "--save_dir", save_dir,
        "--inject_fail_at", "7",
    ]
    r = subprocess.run(
        cmd, env=_env("2"), cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    # First launch: fresh start (the appended --resume finds no checkpoint),
    # saves at steps 3 and 6, crashes one-shot after step 7.
    assert "[inject] simulated failure after step 7" in r.stdout
    assert "restart 1/2" in r.stderr
    # Relaunch: resumes from the step-6 cursor (not from scratch, not from 7).
    assert "resumed from" in r.stdout and "step 6" in r.stdout
    assert "training done: 12 optimizer steps" in r.stdout
    dirs = os.listdir(save_dir)
    assert "step_0000006" in dirs and "step_0000012" in dirs
