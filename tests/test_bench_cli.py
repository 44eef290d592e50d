"""bench.py CLI contract (jax-free: arg handling only).

The driver runs plain ``python bench.py`` and parses ONE JSON line; since
round 4 that default runs the 4-config suite so BENCH_r* third-party-records
every headline claim. These tests pin the arg surface without touching jax
(all failures happen at parse time, before the deferred jax import).
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(*argv, poison_jax_dir=None):
    env = dict(os.environ)
    if poison_jax_dir is not None:
        env["PYTHONPATH"] = poison_jax_dir + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, BENCH, *argv], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )


def _poison(tmp_path):
    """A jax.py that explodes on import: parse-time paths must never reach
    it (bench.py defers every jax-touching import until after parse_args)."""
    d = tmp_path / "poison"
    d.mkdir()
    (d / "jax.py").write_text("raise ImportError('bench touched jax at parse time')")
    return str(d)


def test_help_is_jax_free(tmp_path):
    r = _run("--help", poison_jax_dir=_poison(tmp_path))
    assert r.returncode == 0, r.stderr[-500:]
    assert "--suite" in r.stdout


def test_suite_rejects_single_config_flags(tmp_path):
    r = _run("--suite", "--model", "345M", poison_jax_dir=_poison(tmp_path))
    assert r.returncode != 0
    assert "drop --model" in r.stderr


def _import_bench():
    """Import bench.py as a module (jax-free: jax imports are deferred into
    run_config, which these tests stub out)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_module", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _suite_args(bench):
    return bench.argparse.Namespace(steps=30, warmup=2)


def test_suite_covers_all_headline_configs():
    # Round-4 VERDICT weak-point #2: 345M@2048/@4096 were claimed as headline
    # results but absent from SUITE_CONFIGS, so no driver capture covered
    # them; 774M@1024 is the round-5 single-chip operating point (item #3).
    bench = _import_bench()
    assert bench.SUITE_CONFIGS == (
        ("124M", 1024),
        ("345M", 1024),
        ("124M", 2048),
        ("124M", 4096),
        ("345M", 2048),
        ("345M", 4096),
        ("774M", 1024),
    )


def test_resilient_config_retries_in_fresh_subprocess(monkeypatch):
    # Every suite attempt runs in a fresh subprocess under a hard timeout
    # (true isolation: a runtime wedged in a C-level wait cannot hang
    # the capture, and a poisoned parent runtime cannot leak across
    # configs — round 4 lost the whole capture to one mid-suite failure).
    # A transient first-attempt failure must retry once and return the
    # retry's JSON record.
    bench = _import_bench()
    calls = []

    def fake_run(cmd, **kwargs):
        calls.append(cmd)

        class R:
            returncode = 1 if len(calls) == 1 else 0
            stdout = 'some jax warning\n{"value": 42.0, "model": "124M"}\n'
            stderr = "RuntimeError: device call failed"

        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    rec = bench.run_config_resilient(_suite_args(bench), model="124M", seq_len=2048)
    assert rec == {"value": 42.0, "model": "124M"}
    assert len(calls) == 2
    for cmd in calls:
        assert "--model" in cmd and "124M" in cmd and "2048" in cmd


def test_resilient_double_failure_yields_error_record(monkeypatch):
    # A config whose both subprocess attempts fail contributes an "error"
    # record instead of aborting the capture (round-4 BENCH was rc=1 with
    # ZERO records after one mid-suite failure).
    bench = _import_bench()

    def fake_run(cmd, **kwargs):
        class R:
            returncode = 1
            stdout = ""
            stderr = "still broken"

        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    rec = bench.run_config_resilient(_suite_args(bench), model="345M", seq_len=4096)
    assert "still broken" in rec["error"]
    assert "still broken" in rec["retry_error"]
    assert rec["model"] == "345M" and rec["seq_len"] == 4096
    assert rec["value"] is None


def test_resilient_forwards_operating_point_flags(monkeypatch):
    # The child subprocess must bench the SAME operating point the parent was
    # given — the invariant lives next to the cmd construction (ADVICE round
    # 5), not in suite mode's parse-time rejection of overrides.
    bench = _import_bench()
    calls = []

    def fake_run(cmd, **kwargs):
        calls.append(cmd)

        class R:
            returncode = 0
            stdout = '{"value": 1.0}\n'
            stderr = ""

        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    args = bench.argparse.Namespace(
        steps=30, warmup=2, batch=8, grad_accum_steps=2, remat="mlp",
        accum_dtype="bf16", unroll_accum=True, loss_block_rows=512,
        scan_layers="on",
    )
    bench.run_config_resilient(args, model="124M", seq_len=1024)
    cmd = calls[0]
    for flag, val in (
        ("--batch", "8"),
        ("--grad_accum_steps", "2"),
        ("--remat", "mlp"),
        ("--accum_dtype", "bf16"),
        ("--loss_block_rows", "512"),
        ("--scan_layers", "on"),
    ):
        assert flag in cmd and val in cmd, (flag, cmd)
    assert "--unroll_accum" in cmd
    # At-defaults args (the suite path) forward nothing extra.
    calls.clear()
    bench.run_config_resilient(_suite_args(bench), model="124M", seq_len=1024)
    assert not any(f in calls[0] for f in (
        "--batch", "--grad_accum_steps", "--remat", "--accum_dtype",
        "--unroll_accum", "--loss_block_rows", "--scan_layers",
    )), calls[0]


def test_resilient_labels_parse_failure_distinctly(monkeypatch):
    # rc=0 with unparseable stdout is a protocol bug in the child, not a
    # child crash — the error record must say so (ADVICE round 5: the broad
    # except lumped JSON decode errors in with subprocess failures).
    bench = _import_bench()

    def fake_run(cmd, **kwargs):
        class R:
            returncode = 0
            stdout = "no json anywhere\n"
            stderr = ""

        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    rec = bench.run_config_resilient(_suite_args(bench), model="124M", seq_len=1024)
    assert "parse failure (child rc=0)" in rec["error"]
    assert rec["value"] is None


def test_default_suite_rejects_operating_point_overrides(tmp_path):
    # No --model/--seq_len => suite mode; forced operating points or global
    # remat/CE overrides would record suite numbers that aren't the headline
    # claims (e.g. b8 OOMs 345M@1024; --remat mlp reads ~48% at 124M).
    poison = _poison(tmp_path)
    for flags, named in (
        (("--batch", "8"), "--batch"),
        (("--grad_accum_steps", "4"), "--grad_accum_steps"),
        (("--remat", "mlp"), "--remat"),
        (("--unroll_accum",), "--unroll_accum"),
        (("--loss_block_rows", "512"), "--loss_block_rows"),
        (("--scan_layers", "on"), "--scan_layers"),
    ):
        r = _run(*flags, poison_jax_dir=poison)
        assert r.returncode != 0, flags
        assert named in r.stderr, (flags, r.stderr[-300:])


def test_shard_update_rejected_in_suite_and_forwarded_resilient(monkeypatch, tmp_path):
    # --shard_update is an operating-point override like the rest: suite
    # mode rejects a non-default value at parse time (records must stay
    # comparable round-over-round; the mode is carried in-record), and the
    # resilient child subprocess gets it forwarded verbatim.
    r = _run("--shard_update", "on", poison_jax_dir=_poison(tmp_path))
    assert r.returncode != 0
    assert "--shard_update" in r.stderr

    bench = _import_bench()
    calls = []

    def fake_run(cmd, **kwargs):
        calls.append(cmd)

        class R:
            returncode = 0
            stdout = '{"value": 1.0}\n'
            stderr = ""

        return R()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    args = bench.argparse.Namespace(steps=30, warmup=2, shard_update="auto")
    bench.run_config_resilient(args, model="124M", seq_len=1024)
    assert "--shard_update" in calls[0] and "auto" in calls[0], calls[0]
    # Default ("off") forwards nothing.
    calls.clear()
    bench.run_config_resilient(_suite_args(bench), model="124M", seq_len=1024)
    assert "--shard_update" not in calls[0], calls[0]
