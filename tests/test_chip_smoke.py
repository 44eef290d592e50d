"""chip_smoke.py off the chip: it must fail, say so in its last line, and keep
its parent process away from jax. (What it proves ON the chip is recorded in
CHANGES.md; a CPU run can only pin the refusal.)"""

import ast
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO_ROOT

SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)   # main() sits behind the __main__ guard
    return module


def test_fails_without_a_chip(tmp_path):
    """Under JAX_PLATFORMS=cpu: non-zero exit, last line ``"ok": false``.
    cwd is a temp dir — the script writes its child logs under the cwd."""
    r = subprocess.run(
        [sys.executable, SCRIPT], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert set(last) == {"ok", "device"}
    assert '"ok": true' not in r.stdout


def test_parent_imports_no_jax():
    """Module scope imports the standard library only: jax, and every module
    of the package (most reach jax), load inside child functions."""
    with open(SCRIPT) as f:
        tree = ast.parse(f.read())
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            top_level.add((node.module or "").split(".")[0])
    assert top_level - {"__future__"} <= set(sys.stdlib_module_names)


@pytest.mark.parametrize("ok,device,rehearse,passes", [
    (True, TPU, False, True),
    (False, TPU, False, False),
    # A rehearsal proves control flow only: never "ok": true, never exit 0.
    (True, TPU, True, False),
    # All checks "passed" on something that is not a TPU is still a failure.
    (True, {"platform": "cpu", "kind": "cpu", "count": 1}, False, False),
    (True, {"platform": None, "kind": None, "count": 0}, False, False),
], ids=["pass", "failed-check", "rehearsal", "cpu", "no-device"])
def test_verdict(chip_smoke, ok, device, rehearse, passes):
    line, code = chip_smoke.verdict(ok, device, rehearse)
    assert json.loads(line) == {"ok": passes, "device": device}
    assert (code == 0) is passes
