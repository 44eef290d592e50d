"""The driver contract (__graft_entry__.py) must stay green: entry() is the
single-chip compile check, dryrun_multichip(n) the virtual-mesh sharded-step
check. Both run in subprocesses because dryrun_multichip re-initializes the
JAX backend (clear_backends + jax_num_cpu_devices), which must not leak into
this process's fixtures."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, drop_device_count_flag: bool = False, timeout: int = 1500):
    # dryrun_multichip now also shards the REAL 774M/1.5B pytrees (round-4;
    # ~1.5 min each on this 1-core host) — the timeout covers toy step +
    # both preset sharding proofs with margin.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if drop_device_count_flag:
        # Strip conftest's --xla_force_host_platform_device_count so the
        # child starts with 1 visible device.
        import re

        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", ""),
        ).strip()
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_entry_compiles_and_returns_finite_loss():
    r = _run(
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import math\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "logits, loss = jax.jit(fn)(*args)\n"
        "assert math.isfinite(float(loss)) and float(loss) > 0, loss\n"
        "print('ENTRY_OK', float(loss))\n"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENTRY_OK" in r.stdout


def test_dryrun_multichip_8_devices():
    # XLA_FLAGS with the 8-device count is inherited from conftest.
    r = _run(
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"  # raises on any compile/run failure
        "print('DRYRUN_OK')\n",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_OK" in r.stdout


def test_dryrun_multichip_backend_reinit_fallback():
    """Without the device-count XLA flag the child sees 1 device, so
    dryrun_multichip must take its clear_backends + jax_num_cpu_devices
    re-init path (the driver's real-world situation: a 1-device backend
    may already be committed) — the fallback the module docstring cites
    must actually work, not just exist."""
    r = _run(
        "import __graft_entry__ as g\n"
        # presets=False: the subject here is the backend re-init path; the
        # real-width preset proofs run in the other dryrun test and in
        # test_parallel.py.
        "g.dryrun_multichip(8, presets=False)\n"
        "print('DRYRUN_FALLBACK_OK')\n",
        drop_device_count_flag=True,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_FALLBACK_OK" in r.stdout
