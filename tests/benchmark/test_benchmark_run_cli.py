"""The command refuses to measure off the chip: no CPU fallback."""

import os
import subprocess
import sys

from benchmark import harness


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "train-124m-1k",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
