"""End-to-end CLI integration tests (SURVEY.md §4's point (d)): run the real
driver on synthetic shards, assert loss decreases, checkpoints appear, TB
events are written, and --resume continues from the saved cursor.
"""

import glob
import os
import re

import pytest

from gpt_2_distributed_tpu import train as train_mod


def run_cli(capsys, *argv):
    train_mod.main(list(argv))
    return capsys.readouterr().out


def losses_from(out: str) -> list[float]:
    return [float(m) for m in re.findall(r"loss: ([0-9.]+)", out)]


def test_cli_train_loss_decreases_and_artifacts(capsys, shard_dir, tmp_path):
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--seq_len", "32",
        "--batch", "4",
        "--grad_accum_steps", "2",
        "--max_steps", "8",
        "--lr", "3e-3",
        "--cli_every", "2",
        "--save_every", "5",
        "--save_dir", str(tmp_path / "ckpt"),
        "--log_dir", str(tmp_path / "tb"),
    )
    losses = losses_from(out)
    assert losses, f"no loss lines in output:\n{out}"
    assert losses[-1] < losses[0], out
    # periodic (step 5) + final (step 8) checkpoints
    dirs = sorted(os.listdir(tmp_path / "ckpt"))
    assert "step_0000005" in dirs and "step_0000008" in dirs
    assert glob.glob(str(tmp_path / "tb" / "events.out.tfevents.*"))
    assert "training done: 8 optimizer steps" in out


def test_cli_resume_continues_step_count(capsys, shard_dir, tmp_path):
    common = [
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--seq_len", "32",
        "--batch", "4",
        "--grad_accum_steps", "2",
        "--lr", "1e-3",
        "--cli_every", "100",
        "--save_every", "1000",
        "--save_dir", str(tmp_path / "ckpt"),
    ]
    run_cli(capsys, *common, "--max_steps", "3")
    out = run_cli(capsys, *common, "--max_steps", "6", "--resume")
    assert "resumed from" in out and "step 3" in out
    # final checkpoint from the resumed run
    assert "step_0000006" in os.listdir(tmp_path / "ckpt")


def test_cli_fsdp_mode_runs(capsys, shard_dir, tmp_path):
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--training_mode", "fsdp",
        "--seq_len", "32",
        "--batch", "8",
        "--grad_accum_steps", "1",
        "--max_steps", "3",
        "--lr", "1e-3",
        "--cli_every", "1",
    )
    assert "mesh: data=1, fsdp=8" in out
    losses = losses_from(out)
    assert losses and all(l > 0 for l in losses)


def test_cli_eval_every(capsys, shard_dir, tmp_path):
    """--eval_every runs make_eval_step over the val split (shard 0) and logs
    eval_loss through the tracker (VERDICT round-1 gap #4)."""
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--seq_len", "32",
        "--batch", "4",
        "--grad_accum_steps", "1",
        "--max_steps", "4",
        "--eval_every", "2",
        "--eval_batches", "2",
        "--cli_every", "1",
        "--log_dir", str(tmp_path / "tb"),
    )
    evals = [float(m) for m in re.findall(r"eval_loss: ([0-9.]+)", out)]
    assert len(evals) >= 2, f"expected eval_loss lines:\n{out}"
    assert all(e > 0 for e in evals)


def test_cli_sp_mesh_ring_attention(capsys, shard_dir):
    """--mesh with sp>1: the sequence dim is sharded and 'auto' resolves to
    ring attention; training still descends."""
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--mesh", "data=2,fsdp=2,sp=2",
        "--seq_len", "32",
        "--batch", "8",
        "--grad_accum_steps", "1",
        "--max_steps", "4",
        "--lr", "3e-3",
        "--cli_every", "1",
    )
    assert "sp=2" in out
    losses = losses_from(out)
    assert losses and losses[-1] < losses[0], out


def test_cli_device_flag(shard_dir):
    """--device pins the JAX platform (reference CLI parity,
    /root/reference/train_gpt2_distributed.py:292-294).

    Runs in a subprocess with JAX_PLATFORMS *unset*, so on a machine with a
    TPU attached the flag must actively override the default backend —
    in-process the conftest has already pinned cpu and the assertion would
    be vacuous."""
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "gpt_2_distributed_tpu.train",
         "--data_dir", shard_dir,
         "--device", "cpu",
         "--n_layer", "1", "--n_embd", "32", "--n_head", "2",
         "--vocab_size", "257", "--seq_len", "32", "--batch", "4",
         "--grad_accum_steps", "1", "--max_steps", "2", "--cli_every", "1"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "platform: cpu" in out.stdout, out.stdout
    assert "training done: 2 optimizer steps" in out.stdout


def test_cli_explicit_mesh(capsys, shard_dir):
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--mesh", "data=2,fsdp=4",
        "--seq_len", "32",
        "--batch", "8",
        "--grad_accum_steps", "1",
        "--max_steps", "2",
        "--cli_every", "1",
    )
    assert "mesh: data=2, fsdp=4" in out


def test_cli_fused_layers_trains(capsys, shard_dir):
    """--fused_layers all: the fused Pallas epilogues (interpret mode on CPU)
    run through the whole train loop and the loss still descends."""
    out = run_cli(
        capsys,
        "--data_dir", shard_dir,
        "--n_layer", "2",
        "--n_embd", "32",
        "--n_head", "2",
        "--vocab_size", "257",
        "--seq_len", "32",
        "--batch", "4",
        "--grad_accum_steps", "1",
        "--max_steps", "6",
        "--lr", "3e-3",
        "--cli_every", "1",
        "--fused_layers", "all",
    )
    losses = losses_from(out)
    assert losses and losses[-1] < losses[0], out
    assert "training done: 6 optimizer steps" in out


# --- operating-point warnings (utils/operating_point.py) ---------------------


def test_accum_cliff_message_exact_match_only():
    from gpt_2_distributed_tpu.utils.operating_point import accum_cliff_message

    msg = accum_cliff_message(1024, 16, scan_layers=False)
    assert msg is not None
    assert "grad_accum_steps=16" in msg and "PERF_ANALYSIS.md" in msg
    # The scan path compiles the accumulation loop differently — no cliff.
    assert accum_cliff_message(1024, 16, scan_layers=True) is None
    # Neighboring operating points measured fine; exact-match only.
    assert accum_cliff_message(1024, 12, scan_layers=False) is None
    assert accum_cliff_message(2048, 16, scan_layers=False) is None


def test_warn_once_dedupes_per_tag():
    from gpt_2_distributed_tpu.utils import operating_point as op

    seen = []
    op._WARNED.discard("t1")
    op._WARNED.discard("t2")
    assert op.warn_once("t1", "first", printer=seen.append) is True
    assert op.warn_once("t1", "first again", printer=seen.append) is False
    assert op.warn_once("t2", "second", printer=seen.append) is True
    assert seen == ["warning: first", "warning: second"]
