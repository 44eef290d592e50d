"""Every metric name pushed to ``StatsTracker.update`` anywhere in the
codebase must be registered in the metric registry.

The tracker no longer drops unregistered names silently (it counts and
warns — or raises under ``strict=True``), but the warn only fires at
runtime on paths a test may never execute.  This test closes the gap
statically: it walks the AST of every production module for
``tracker.update(...)`` call sites, resolves the pushed keyword names —
including ``**var`` splats built from dict literals and ``var["key"] =``
assignments in the enclosing function, and the engine's
``**eng.metrics_snapshot()`` — and asserts each against the registry.

This is exactly the check that would have caught ``fused_fallback``:
pushed by train.py since the fused-ops PR, registered only in this one.
"""

from __future__ import annotations

import ast
import os

import pytest

import gpt_2_distributed_tpu.metrics.builtin  # noqa: F401 — populate registry
from gpt_2_distributed_tpu.metrics.registry import METRIC_REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "gpt_2_distributed_tpu")
SCRIPTS = os.path.join(REPO, "scripts")

# update() kwargs that are control arguments, not metric names
NON_METRIC_KWARGS = {"count_tokens"}


def production_files():
    out = []
    for root in (PKG, SCRIPTS):
        for dirpath, _dirnames, filenames in os.walk(root):
            out.extend(
                os.path.join(dirpath, f) for f in filenames
                if f.endswith(".py")
            )
    return sorted(out)


def _is_tracker_update(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "update"
        and isinstance(f.value, ast.Name)
        and "tracker" in f.value.id.lower()
    )


def _dict_literal_keys(node: ast.Dict) -> set[str]:
    keys = set()
    for k in node.keys:
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
            keys.add(k.value)
    return keys


def _splat_keys_from_scope(scope: ast.AST, varname: str) -> set[str]:
    """Names a ``**varname`` splat can carry, from how the enclosing
    function builds it: ``var = {...}`` / ``var = dict(...)`` literals and
    ``var["key"] = ...`` subscript-assigns."""
    keys: set[str] = set()
    for node in ast.walk(scope):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == varname:
                    if isinstance(node.value, ast.Dict):
                        keys |= _dict_literal_keys(node.value)
                    elif (
                        isinstance(node.value, ast.Call)
                        and isinstance(node.value.func, ast.Name)
                        and node.value.func.id == "dict"
                    ):
                        keys |= {
                            kw.arg for kw in node.value.keywords
                            if kw.arg is not None
                        }
                elif (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == varname
                    and isinstance(tgt.slice, ast.Constant)
                    and isinstance(tgt.slice.value, str)
                ):
                    keys.add(tgt.slice.value)
    return keys


def _metrics_snapshot_keys() -> set[str]:
    """Union of every ``metrics_snapshot``'s returned dict-literal keys —
    what a ``**x.metrics_snapshot()`` splat can push. Both the engine's
    (single replica) and the router's (fleet aggregate) snapshots feed
    the same update site in serving/frontend/driver.py."""
    keys: set[str] = set()
    for rel in (("serving", "engine.py"),
                ("serving", "frontend", "router.py")):
        path = os.path.join(PKG, *rel)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        found = False
        for node in ast.walk(tree):
            if (isinstance(node, ast.FunctionDef)
                    and node.name == "metrics_snapshot"):
                for ret in ast.walk(node):
                    if isinstance(ret, ast.Return) and isinstance(
                        ret.value, ast.Dict
                    ):
                        keys |= _dict_literal_keys(ret.value)
                        found = True
        assert found, f"metrics_snapshot return dict literal not in {path}"
    return keys


def collect_pushed_names():
    """(file, line, metric_name) for every name pushed at an update site."""
    pushed = []
    for path in production_files():
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        # innermost enclosing function for splat resolution
        scopes: list[ast.AST] = []

        def visit(node, scopes=scopes, path=path):
            is_scope = isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            )
            if is_scope:
                scopes.append(node)
            if isinstance(node, ast.Call) and _is_tracker_update(node):
                scope = scopes[-1] if scopes else None
                for kw in node.keywords:
                    if kw.arg is not None:
                        if kw.arg not in NON_METRIC_KWARGS:
                            pushed.append((path, node.lineno, kw.arg))
                        continue
                    # **splat
                    if isinstance(kw.value, ast.Name) and scope is not None:
                        for name in _splat_keys_from_scope(scope, kw.value.id):
                            pushed.append((path, node.lineno, name))
                    elif (
                        isinstance(kw.value, ast.Call)
                        and isinstance(kw.value.func, ast.Attribute)
                        and kw.value.func.attr == "metrics_snapshot"
                    ):
                        for name in _metrics_snapshot_keys():
                            pushed.append((path, node.lineno, name))
                    else:
                        raise AssertionError(
                            f"{path}:{node.lineno}: tracker.update splat "
                            f"this test cannot resolve — push metrics via "
                            f"a local dict literal / subscript assigns, or "
                            f"teach the test the new pattern"
                        )
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_scope:
                scopes.pop()

        visit(tree)
    return pushed


def test_update_call_sites_found():
    """The walker sees the known push sites; if this drops to zero the
    registration check below would vacuously pass."""
    pushed = collect_pushed_names()
    files = {os.path.basename(p) for p, _, _ in pushed}
    # serving pushes now flow through the shared driver, not serve.py
    assert "train.py" in files and "driver.py" in files
    names = {n for _, _, n in pushed}
    # spot-check resolution of each pattern: direct kwarg, dict(...) call,
    # subscript assign, and the metrics_snapshot splat
    assert "eval_loss" in names        # direct kwarg (train.py eval)
    assert "lr" in names               # values = dict(lr=...)
    assert "skipped_steps" in names    # extra = {...} literal
    assert "save_failures" in names    # extra["save_failures"] = ...
    assert "fused_fallback" in names   # the bug this test exists to catch
    assert "queue_wait_ms" in names    # **router.metrics_snapshot()
    assert "route_affinity_hits" in names  # fleet-level router key
    # PR 16 fault-tolerance counters: snapshot splat + direct kwarg
    assert "replica_failures" in names     # **router.metrics_snapshot()
    assert "requests_migrated" in names    # **router.metrics_snapshot()
    assert "requests_timed_out" in names   # **router.metrics_snapshot()
    assert "watchdog_trips" in names       # direct kwarg (driver.step/drain)
    # PR 17 sharded-serving keys: present in BOTH snapshot dict literals
    # (engine per-replica, router fleet aggregate)
    assert "serve_mesh_devices" in names
    assert "kv_pool_bytes_per_device" in names
    assert "prefill_batched" in names
    # PR 18 process isolation: replacement-worker counter (router snapshot)
    assert "worker_restarts" in names
    # PR 20 speculative decoding: present in BOTH snapshot dict literals
    # (engine per-replica, router fleet aggregate)
    assert "spec_draft_tokens" in names
    assert "spec_accepted_tokens" in names
    assert "spec_rollbacks" in names
    assert "draft_ms" in names
    assert "verify_ms" in names


def test_every_pushed_metric_is_registered():
    unregistered = sorted(
        {
            (os.path.relpath(path, REPO), line, name)
            for path, line, name in collect_pushed_names()
            if name not in METRIC_REGISTRY
        }
    )
    assert not unregistered, (
        "metric names pushed to StatsTracker.update but never registered "
        "(the tracker drops them — register in metrics/builtin.py): "
        + ", ".join(f"{p}:{ln} {n!r}" for p, ln, n in unregistered)
    )


def test_registry_covers_loss_guard_paths():
    """The conditional extra-dict names are live registry entries with the
    processors the push sites rely on (int-coercion for counters)."""
    for name in ("skipped_steps", "clipped_steps", "last_skip_reason",
                 "save_failures", "desync_detected", "data_read_retries",
                 "fused_fallback", "elastic_resizes", "resume_world_delta"):
        d = METRIC_REGISTRY.get(name)
        assert d is not None, name
        assert d.processor(2.7) == 2.0  # int-coerced
