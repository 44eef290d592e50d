#!/usr/bin/env python
"""Merge per-process trace files and report where the time went.

Reads every ``trace-p*.jsonl`` (plus ``.1`` rotation generations) under a
trace directory written by ``gpt_2_distributed_tpu.obs.trace`` and prints:

* **Per-phase step breakdown** — for each ``step`` span, its direct child
  spans (data_fetch, consensus_exchange, step_dispatch, h2d_prefetch,
  device_sync, collector, ckpt_snapshot, ...) summed by name; p50/p99/mean
  per phase, each phase's share of mean step time, and the **unattributed
  residual** (step wall time minus the sum of its children) — the honest
  number an MFU-gap hunt starts from. Attribution % is printed, never
  hidden: if instrumentation misses a phase, the residual says so.
* **Per-request serving waterfall** — lifecycle events keyed by request id
  (submit, admit, prefill_chunk, prefix_hit, cow, preempt, resume,
  first_token, finish) folded into queue-wait / TTFT / total latency per
  request, plus pool-level p50/p99 TTFT. TTFT here is rebuilt purely from
  trace events; the engine stamps those events with its own monotonic
  timestamps, so this agrees with the engine's accounting to the
  microsecond.
* **Engine-step breakdown** — same treatment for ``engine_step`` spans
  (admit / prefill / grow / decode / emit phases of the continuous-batching
  loop; ``decode`` is the engine's ``decode_ms``, with its dispatch and
  its token read-back listed under it).

``--json`` emits the same content as one JSON object for dashboards.

Usage:
    python scripts/obs_report.py /path/to/trace_dir [--json] [--limit N]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict
from typing import Any


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank-interpolated percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q / 100.0 * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def _stats_ms(vals: list[float]) -> dict[str, float]:
    s = sorted(vals)
    return {
        "n": len(s),
        "mean_ms": sum(s) / len(s) * 1e3 if s else 0.0,
        "p50_ms": _percentile(s, 50) * 1e3,
        "p99_ms": _percentile(s, 99) * 1e3,
        "total_s": sum(s),
    }


def load_trace_dir(trace_dir: str) -> list[dict[str, Any]]:
    """All records from every process file, rotations included (oldest
    first so later analysis sees records roughly in emission order)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "trace-p*.jsonl.1"))) + sorted(
        glob.glob(os.path.join(trace_dir, "trace-p*.jsonl"))
    )
    if not paths:
        raise FileNotFoundError(f"no trace-p*.jsonl files under {trace_dir!r}")
    records: list[dict[str, Any]] = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn tail line from a crash — expected
    return records


def step_breakdown(
    records: list[dict[str, Any]], step_name: str = "step"
) -> dict[str, Any] | None:
    """Fold each ``step_name`` span's direct children into per-phase stats.

    Only *direct* children are summed — a nested span (e.g. a barrier
    inside consensus_exchange) is already inside its parent's duration, so
    counting it again would overstate attribution. A phase's own children
    (``dispatch`` and ``readback`` inside the engine's ``decode``) are
    listed under it as its ``parts``, and added to nothing.
    """
    spans = [r for r in records if r.get("ph") == "span"]
    by_key = {(r["pid"], r["sid"]): r for r in spans}
    steps = [r for r in spans if r["name"] == step_name]
    if not steps:
        return None
    children: dict[tuple[int, int], list[dict[str, Any]]] = defaultdict(list)
    for r in spans:
        if r.get("parent") is not None:
            parent = by_key.get((r["pid"], r["parent"]))
            if parent is not None:
                children[(r["pid"], r["parent"])].append(r)

    phase_durs: dict[str, list[float]] = defaultdict(list)
    part_durs: dict[str, dict[str, list[float]]] = defaultdict(
        lambda: defaultdict(list))
    step_durs: list[float] = []
    residuals: list[float] = []
    for st in steps:
        kids = children.get((st["pid"], st["sid"]), [])
        attributed = 0.0
        per_phase: dict[str, float] = defaultdict(float)
        per_part: dict[tuple[str, str], float] = defaultdict(float)
        for k in kids:
            per_phase[k["name"]] += k["dur"]
            attributed += k["dur"]
            for part in children.get((k["pid"], k["sid"]), []):
                per_part[k["name"], part["name"]] += part["dur"]
        for name, d in per_phase.items():
            phase_durs[name].append(d)
        for (name, part), d in per_part.items():
            part_durs[name][part].append(d)
        step_durs.append(st["dur"])
        residuals.append(max(0.0, st["dur"] - attributed))

    total_step = sum(step_durs)
    total_attr = total_step - sum(residuals)
    def share(durs: list[float]) -> float:
        return 100.0 * sum(durs) / total_step if total_step else 0.0

    def by_total(named: dict[str, list[float]]):
        return sorted(named.items(), key=lambda kv: -sum(kv[1]))

    phases = {
        name: {**_stats_ms(durs), "share_pct": share(durs)}
        for name, durs in by_total(phase_durs)
    }
    for name, parts in part_durs.items():
        phases[name]["parts"] = {
            part: {**_stats_ms(durs), "share_pct": share(durs)}
            for part, durs in by_total(parts)
        }
    return {
        "span": step_name,
        "n_steps": len(step_durs),
        "processes": sorted({s["pid"] for s in steps}),
        "step": _stats_ms(step_durs),
        "phases": phases,
        "residual": {
            **_stats_ms(residuals),
            "share_pct": 100.0 * sum(residuals) / total_step if total_step else 0.0,
        },
        "attributed_pct": 100.0 * total_attr / total_step if total_step else 0.0,
    }


# Lifecycle events that mark a request's trajectory, in waterfall order.
# route/shed come from the replica router (serving/frontend/router.py) —
# route precedes submit (the router picks a replica, then enqueues), and a
# shed request has a route event but no submit at all.  submit_refused comes
# from the driver inbox (a non-shed refusal: draining, bad args); migrate
# from the router's failure-containment path when a replica dies mid-flight.
_REQUEST_EVENTS = (
    "route",
    "shed",
    "submit_refused",
    "submit",
    "admit",
    "prefix_hit",
    "cow",
    "prefill_chunk",
    "first_token",
    "spec_accept",
    "preempt",
    "migrate",
    "resume",
    "finish",
)


def request_waterfall(records: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Rebuild each serving request's lifecycle from its rid-keyed events."""
    by_rid: dict[Any, list[dict[str, Any]]] = defaultdict(list)
    for r in records:
        if r.get("ph") == "event" and "rid" in r.get("attrs", {}):
            by_rid[r["attrs"]["rid"]].append(r)
    if not by_rid:
        return None

    requests = []
    ttfts: list[float] = []
    for rid, evs in sorted(by_rid.items(), key=lambda kv: str(kv[0])):
        evs.sort(key=lambda e: e["ts"])
        first_ts = {}
        counts: dict[str, int] = defaultdict(int)
        for e in evs:
            counts[e["name"]] += 1
            first_ts.setdefault(e["name"], e["ts"])
        t_submit = first_ts.get("submit")
        row: dict[str, Any] = {"rid": rid, "events": dict(counts)}
        if t_submit is not None:
            for name in ("admit", "first_token", "finish"):
                if name in first_ts:
                    row[f"{name}_ms"] = (first_ts[name] - t_submit) * 1e3
            if "first_token" in first_ts:
                ttfts.append(first_ts["first_token"] - t_submit)
        # Cached/chunked prefill details when the engine attached them,
        # plus the router's placement decision when a front end was in play.
        for e in evs:
            a = e.get("attrs", {})
            if e["name"] == "prefix_hit" and "tokens" in a:
                row["prefix_cached_tokens"] = a["tokens"]
            if e["name"] == "finish" and "n_generated" in a:
                row["n_generated"] = a["n_generated"]
            if e["name"] == "finish" and a.get("reason") == "timeout":
                row["timed_out"] = True
            if e["name"] == "finish" and a.get("reason") == "failed":
                row["failed"] = True
            if e["name"] == "route":
                row["replica"] = a.get("replica")
                row["route_policy"] = a.get("policy")
                row["affinity_blocks"] = a.get("affinity_blocks")
            if e["name"] == "shed":
                row["shed"] = True
            if e["name"] == "submit_refused":
                row["refused"] = True
                row["refuse_reason"] = a.get("reason")
            if e["name"] == "migrate":
                row["migrated"] = True
                row["migrated_to"] = a.get("dst")
        requests.append(row)

    return {
        "n_requests": len(requests),
        "ttft": _stats_ms(ttfts) if ttfts else None,
        "requests": requests,
    }


def frontend_summary(serving: dict[str, Any] | None) -> dict[str, Any] | None:
    """Fleet view over routed requests: placement spread, policy mix,
    sheds. None when no router events are in the trace."""
    if not serving:
        return None
    routed = [r for r in serving["requests"] if "replica" in r]
    refused = [r for r in serving["requests"] if r.get("refused")]
    if not routed and not refused:
        return None
    sheds = [r for r in serving["requests"] if r.get("shed")]
    per_replica: dict[str, int] = defaultdict(int)
    per_policy: dict[str, int] = defaultdict(int)
    for r in routed:
        if not r.get("shed"):
            per_replica[str(r["replica"])] += 1
        per_policy[str(r.get("route_policy"))] += 1
    return {
        "n_routed": len(routed),
        "n_shed": len(sheds),
        "n_refused": len(refused),
        "n_migrated": sum(1 for r in routed if r.get("migrated")),
        "n_timed_out": sum(
            1 for r in serving["requests"] if r.get("timed_out")),
        "n_failed": sum(1 for r in serving["requests"] if r.get("failed")),
        "requests_per_replica": dict(sorted(per_replica.items())),
        "routes_by_policy": dict(sorted(per_policy.items())),
        "affinity_share": round(
            (per_policy.get("affinity", 0) + per_policy.get("sticky", 0))
            / len(routed), 4
        ) if routed else 0.0,
    }


def mesh_summary(records: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Serving mesh shape(s) in the trace. Every engine emits one
    ``engine_mesh`` event at construction (mesh spec + data/tp degrees);
    the router's ``scale_up`` events add the replica index. A fleet where
    replicas disagree on mesh shape is worth seeing at a glance — capacity
    math (tok/s per device, concurrent slots) differs per replica."""
    engines = [
        r["attrs"] for r in records
        if r.get("ph") == "event" and r.get("name") == "engine_mesh"
    ]
    if not engines:
        return None
    per_replica: dict[str, str] = {}
    for r in records:
        if r.get("ph") == "event" and r.get("name") == "scale_up":
            a = r.get("attrs", {})
            if "mesh" in a:
                per_replica[str(a.get("replica"))] = a["mesh"]
    shapes: dict[str, int] = defaultdict(int)
    for a in engines:
        shapes[a.get("mesh", "single")] += 1
    return {
        "n_engines": len(engines),
        "shapes": dict(sorted(shapes.items())),
        "devices_per_engine": max(a.get("devices", 1) for a in engines),
        "replica_meshes": dict(sorted(per_replica.items())) or None,
    }


def worker_lifecycle(records: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Subprocess-placement supervision timeline: every ``worker_spawn``,
    ``worker_respawn`` (the backoff before a replacement spawn) and
    ``heartbeat_loss`` event, in time order. Spawns that replaced a dead
    worker carry ``respawn > 0``; a healthy fleet shows only the initial
    spawns. Remote placement adds ``host_lost`` (a whole failure domain
    contained as one batch) and ``host_joined`` (a quarantined host
    dial-probed back into service). None when the run never used
    subprocess or remote placement."""
    names = {"worker_spawn", "worker_respawn", "heartbeat_loss",
             "host_lost", "host_joined"}
    evs = sorted(
        (r for r in records
         if r.get("ph") == "event" and r.get("name") in names),
        key=lambda e: e["ts"],
    )
    if not evs:
        return None
    t0 = evs[0]["ts"]
    rows = []
    for e in evs:
        a = e.get("attrs", {})
        row = {"event": e["name"], "t_ms": round((e["ts"] - t0) * 1e3, 1)}
        if e["name"] == "worker_spawn":
            row["pid"] = a.get("pid")
            row["spawn"] = a.get("spawn")
            row["respawn"] = a.get("respawn")
            if a.get("host_id") is not None:
                row["host_id"] = a.get("host_id")
        elif e["name"] == "worker_respawn":
            row["respawn"] = a.get("respawn")
            row["backoff_s"] = a.get("backoff_s")
        elif e["name"] == "host_lost":
            row["host_id"] = a.get("host_id")
            row["replicas"] = a.get("replicas")
            row["reason"] = a.get("reason")
        elif e["name"] == "host_joined":
            row["host_id"] = a.get("host_id")
        else:  # heartbeat_loss
            row["pid"] = a.get("pid")
            if a.get("host_id") is not None:
                row["host_id"] = a.get("host_id")
        rows.append(row)
    return {
        "n_spawns": sum(1 for r in rows if r["event"] == "worker_spawn"),
        "n_respawns": sum(1 for r in rows if r["event"] == "worker_respawn"),
        "n_heartbeat_losses": sum(
            1 for r in rows if r["event"] == "heartbeat_loss"),
        "n_hosts_lost": sum(1 for r in rows if r["event"] == "host_lost"),
        "n_hosts_joined": sum(
            1 for r in rows if r["event"] == "host_joined"),
        "events": rows,
    }


def speculation_summary(records: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Two-model engine acceptance: the engine emits one ``spec_accept``
    event (rid, drafted=k, accepted) per active row per speculative
    round, and the draft/verify spans already fold into the engine-step
    breakdown. The measured acceptance rate α here is what the expected
    speedup model E[tokens/verify] = (1 − α^(k+1)) / (1 − α) plugs in.
    None when the trace never speculated."""
    evs = [r["attrs"] for r in records
           if r.get("ph") == "event" and r.get("name") == "spec_accept"]
    if not evs:
        return None
    drafted = sum(int(a.get("drafted", 0)) for a in evs)
    runs = [int(a.get("accepted", 0)) for a in evs]
    accepted = sum(runs)
    return {
        "n_rounds": len(evs),
        "n_requests": len({a.get("rid") for a in evs}),
        "draft_tokens": drafted,
        "accepted_tokens": accepted,
        "acceptance_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "mean_accepted_run": round(accepted / len(runs), 3),
        # every round also emits one token straight from the verify pass
        # (the correction or the bonus), so this is the measured
        # E[tokens/verify].
        "tokens_per_verify": round(1 + accepted / len(runs), 3),
    }


def build_report(trace_dir: str) -> dict[str, Any]:
    records = load_trace_dir(trace_dir)
    serving = request_waterfall(records)
    return {
        "trace_dir": trace_dir,
        "n_records": len(records),
        "train_steps": step_breakdown(records, "step"),
        "engine_steps": step_breakdown(records, "engine_step"),
        "serving": serving,
        "speculation": speculation_summary(records),
        "frontend": frontend_summary(serving),
        "meshes": mesh_summary(records),
        "workers": worker_lifecycle(records),
    }


def _print_breakdown(b: dict[str, Any], title: str) -> None:
    print(f"\n== {title}: {b['n_steps']} spans over "
          f"process(es) {b['processes']} ==")
    st = b["step"]
    print(f"  step wall: mean {st['mean_ms']:.2f} ms, p50 {st['p50_ms']:.2f}, "
          f"p99 {st['p99_ms']:.2f}  (total {st['total_s']:.2f} s)")
    print(f"  {'phase':<20} {'mean_ms':>9} {'p50_ms':>9} {'p99_ms':>9} "
          f"{'share':>7} {'n':>5}")
    for name, ph in b["phases"].items():
        rows = [(name, ph)] + [
            ("  " + part, p) for part, p in ph.get("parts", {}).items()]
        for label, row in rows:
            print(f"  {label:<20} {row['mean_ms']:>9.2f} {row['p50_ms']:>9.2f} "
                  f"{row['p99_ms']:>9.2f} {row['share_pct']:>6.1f}% {row['n']:>5}")
    res = b["residual"]
    print(f"  {'(unattributed)':<20} {res['mean_ms']:>9.2f} {res['p50_ms']:>9.2f} "
          f"{res['p99_ms']:>9.2f} {res['share_pct']:>6.1f}%")
    print(f"  attributed: {b['attributed_pct']:.1f}% of step wall time")


def _spec_line(sp: dict[str, Any]) -> str:
    return (f"  speculation: {sp['n_rounds']} round(s) over "
            f"{sp['n_requests']} request(s), acceptance rate "
            f"{sp['acceptance_rate']:.0%}, mean accepted run "
            f"{sp['mean_accepted_run']:.2f}, "
            f"{sp['tokens_per_verify']:.2f} tokens/verify")


def _print_serving(s: dict[str, Any], limit: int,
                   speculation: dict[str, Any] | None = None) -> None:
    print(f"\n== serving: {s['n_requests']} requests ==")
    if s["ttft"]:
        t = s["ttft"]
        print(f"  TTFT: mean {t['mean_ms']:.2f} ms, p50 {t['p50_ms']:.2f}, "
              f"p99 {t['p99_ms']:.2f}  (n={t['n']})")
    if speculation:
        print(_spec_line(speculation))
    print(f"  {'rid':<14} {'admit_ms':>9} {'ttft_ms':>9} {'finish_ms':>10} "
          f"{'chunks':>6} {'preempt':>7} {'cached':>6}")
    for row in s["requests"][:limit]:
        ev = row["events"]
        print(
            f"  {str(row['rid']):<14} "
            f"{row.get('admit_ms', float('nan')):>9.2f} "
            f"{row.get('first_token_ms', float('nan')):>9.2f} "
            f"{row.get('finish_ms', float('nan')):>10.2f} "
            f"{ev.get('prefill_chunk', 0):>6} "
            f"{ev.get('preempt', 0):>7} "
            f"{row.get('prefix_cached_tokens', 0):>6}"
        )
    if len(s["requests"]) > limit:
        print(f"  ... {len(s['requests']) - limit} more (raise --limit)")


def _print_frontend(report: dict[str, Any], limit: int) -> None:
    """Per-request routed waterfall: queue -> route -> admit -> first
    token, with the router's placement decision on every row."""
    fs = report["frontend"]
    s = report["serving"]
    print(f"\n== front end: {fs['n_routed']} routed, {fs['n_shed']} shed, "
          f"{fs['n_refused']} refused ==")
    print(f"  requests/replica: {fs['requests_per_replica']}  "
          f"routes by policy: {fs['routes_by_policy']}  "
          f"affinity share: {fs['affinity_share']:.0%}")
    meshes = report.get("meshes")
    if meshes:
        shapes = ", ".join(f"{m}×{n}" if n > 1 else m
                           for m, n in meshes["shapes"].items())
        line = (f"  replica mesh: {shapes} "
                f"({meshes['devices_per_engine']} device(s)/engine)")
        if meshes["replica_meshes"] and len(set(
                meshes["replica_meshes"].values())) > 1:
            line += f"  per replica: {meshes['replica_meshes']}"
        print(line)
    if fs["n_migrated"] or fs["n_timed_out"] or fs["n_failed"]:
        print(f"  fault tolerance: {fs['n_migrated']} migrated, "
              f"{fs['n_timed_out']} timed out, {fs['n_failed']} failed")
    if report.get("speculation"):
        print(_spec_line(report["speculation"]))
    workers = report.get("workers")
    if workers:
        hosts = ""
        if workers.get("n_hosts_lost") or workers.get("n_hosts_joined"):
            hosts = (f", {workers['n_hosts_lost']} host(s) lost, "
                     f"{workers['n_hosts_joined']} host(s) rejoined")
        print(f"  worker lifecycle: {workers['n_spawns']} spawn(s), "
              f"{workers['n_respawns']} respawn(s), "
              f"{workers['n_heartbeat_losses']} heartbeat loss(es)"
              f"{hosts}")
        for w in workers["events"]:
            if w["event"] == "worker_spawn":
                tag = (f"respawn #{w['respawn']}" if w.get("respawn")
                       else f"initial spawn #{w.get('spawn')}")
                if w.get("host_id"):
                    tag += f", host {w['host_id']}"
                print(f"    +{w['t_ms']:>9.1f} ms  worker_spawn    "
                      f"pid={w.get('pid')}  ({tag})")
            elif w["event"] == "worker_respawn":
                print(f"    +{w['t_ms']:>9.1f} ms  worker_respawn  "
                      f"#{w.get('respawn')} after "
                      f"{w.get('backoff_s', 0):g}s backoff")
            elif w["event"] == "host_lost":
                print(f"    +{w['t_ms']:>9.1f} ms  host_lost       "
                      f"{w.get('host_id')}  replicas={w.get('replicas')} "
                      f"({w.get('reason')})")
            elif w["event"] == "host_joined":
                print(f"    +{w['t_ms']:>9.1f} ms  host_joined     "
                      f"{w.get('host_id')}")
            else:
                extra = (f"  host={w['host_id']}" if w.get("host_id")
                         else "")
                print(f"    +{w['t_ms']:>9.1f} ms  heartbeat_loss  "
                      f"pid={w.get('pid')}{extra}")
    print(f"  {'rid':<8} {'replica':>7} {'policy':<12} {'aff_blk':>7} "
          f"{'queue_ms':>9} {'ttft_ms':>9} {'finish_ms':>10}")
    shown = 0
    for row in s["requests"]:
        if ("replica" not in row and not row.get("refused")) or shown >= limit:
            continue
        shown += 1
        if row.get("refused"):
            print(f"  {str(row['rid']):<8} {'—':>7} "
                  f"{str(row.get('refuse_reason')):<12} {'':>7} "
                  f"{'— refused':>31}")
            continue
        if row.get("shed"):
            print(f"  {str(row['rid']):<8} {row['replica']:>7} "
                  f"{str(row.get('route_policy')):<12} "
                  f"{row.get('affinity_blocks', 0):>7} "
                  f"{'— shed (503)':>31}")
            continue
        # a migrated row finished on a different replica than it was routed to
        mark = ""
        if row.get("migrated"):
            mark = f"  → r{row.get('migrated_to')} (migrated)"
        elif row.get("timed_out"):
            mark = "  — timeout (504)"
        elif row.get("failed"):
            mark = "  — failed (503)"
        print(
            f"  {str(row['rid']):<8} {row['replica']:>7} "
            f"{str(row.get('route_policy')):<12} "
            f"{row.get('affinity_blocks', 0):>7} "
            f"{row.get('admit_ms', float('nan')):>9.2f} "
            f"{row.get('first_token_ms', float('nan')):>9.2f} "
            f"{row.get('finish_ms', float('nan')):>10.2f}"
            f"{mark}"
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", help="directory holding trace-p*.jsonl files")
    ap.add_argument("--json", action="store_true", help="emit one JSON object")
    ap.add_argument("--limit", type=int, default=40,
                    help="max per-request rows to print (text mode)")
    ap.add_argument("--frontend", action="store_true",
                    help="per-request routed waterfall (queue -> route -> "
                         "admit -> first_token) with replica placement")
    args = ap.parse_args(argv)

    try:
        report = build_report(args.trace_dir)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(report, indent=2, default=str))
        return 0

    print(f"trace dir: {report['trace_dir']}  ({report['n_records']} records)")
    if report["train_steps"]:
        _print_breakdown(report["train_steps"], "training step breakdown")
    if report["engine_steps"]:
        _print_breakdown(report["engine_steps"], "serving engine-step breakdown")
    if report["serving"]:
        _print_serving(report["serving"], args.limit,
                       speculation=report.get("speculation"))
    if args.frontend:
        if report["frontend"]:
            _print_frontend(report, args.limit)
        else:
            print("no route/shed events in this trace — was the request "
                  "routed through the front end (gpt2-tpu-frontend) "
                  "with --trace_dir?")
    if not any((report["train_steps"], report["engine_steps"], report["serving"])):
        print("no step spans or request events found — was tracing enabled?")
    return 0


if __name__ == "__main__":
    sys.exit(main())
