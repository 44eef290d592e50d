"""Device time by the program's ``jax.named_scope``s, out of a kept trace.

``reduce_trace.Trace`` names a device operation by its HLO text, which
carries no scope; the ``.xplane.pb`` does: each operation's metadata holds a
``tf_op`` stat, the ``op_name`` that JAX gave it (``jit(decode_step)/sala/
select/dot_general:``). ``jax.profiler.ProfileData`` does not show metadata
stats, so this module reads the file's protobuf wire format itself (the
schema is tsl's ``xplane.proto``; only the fields named below are looked at).

The harness deletes a run's trace once it is reduced, before the readers
run, unless ``BENCH_KEEP_TRACE`` names a place to copy it to first (its own
knob, for looking at a trace by hand). ``keep_trace()`` sets that knob to a
path under the harness's work directory; a family's program module whose
readers need scopes calls it when it is loaded. A reader whose file is not
there reports nothing.
"""

from __future__ import annotations

import os

OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"


def kept_path() -> str:
    from benchmark import harness

    return os.environ.get("BENCH_KEEP_TRACE") or os.path.join(
        harness.WORK_DIR, "kept", "trace.xplane.pb")


def keep_trace() -> None:
    """Ask the harness to keep a copy of the next traced run's file."""
    os.environ.setdefault("BENCH_KEEP_TRACE", kept_path())


# --- protobuf wire format, as much as the trace needs ---------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def device_ops(path: str):
    """``{plane: [(hlo text, op_name, start_ns, end_ns)]}`` of the ``XLA
    Ops`` line of every TPU plane, an enclosing operation before those nested
    in it."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                key, value = _map_entry(v)
                event_meta[key] = value
            elif pf == 5:
                key, value = _map_entry(v)
                stat_names[key] = next(
                    (_text(s) for sf, s in _fields(value) if sf == 2), "")
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        named = {}

        def describe(meta_id):
            if meta_id not in named:
                text, op_name = "", ""
                for mf, v in _fields(event_meta.get(meta_id, b"")):
                    if mf == 2:
                        text = _text(v)
                    elif mf == 5:
                        stat = dict(
                            (sf, sv) for sf, sv in _fields(v) if sf in (1, 5, 7))
                        if stat.get(1) == tf_op:
                            op_name = (_text(stat[5]) if 5 in stat
                                       else stat_names.get(stat.get(7), ""))
                named[meta_id] = (text, op_name)
            return named[meta_id]

        events = []
        for line in lines:
            line_name, t0_ns, raw = "", 0, []
            for lf, v in _fields(line):
                if lf == 2:
                    line_name = _text(v)
                elif lf == 3:
                    t0_ns = v
                elif lf == 4:
                    raw.append(v)
            if line_name != OPS_LINE:
                continue
            for ev in raw:
                meta_id = offset_ps = duration_ps = 0
                for ef, v in _fields(ev):
                    if ef == 1:
                        meta_id = v
                    elif ef == 2:
                        offset_ps = v
                    elif ef == 3:
                        duration_ps = v
                start = t0_ns + offset_ps / 1e3
                events.append((*describe(meta_id), start, start + duration_ps / 1e3))
        out[name] = sorted(events, key=lambda e: (e[2], -e[3]))
    return out


def scope_seconds(path: str, lo: float, hi: float, scopes, program: str = "") -> float:
    """Device seconds, within ``[lo, hi]`` ns and averaged over chips, of the
    operations whose ``op_name`` lies in one of ``scopes`` (``"sala/select"``
    matches ``.../sala/select/...``) and, where ``program`` is given, starts
    with it (``"jit(decode_step)"``). Operations nest (a loop spans its
    body), so an operation counts with its own time less that of the
    operations inside it."""
    planes = device_ops(path)
    if not planes:
        return 0.0
    needles = tuple(f"/{s}/" for s in scopes)
    total = 0.0
    for events in planes.values():
        stack = []     # open events: [counts, end_ns, self_ns]

        def close(upto):
            nonlocal total
            while stack and stack[-1][1] <= upto:
                counts, _, own = stack.pop()
                if counts:
                    total += own / 1e9
        for _, op_name, s, e in events:
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            close(s)
            if stack:
                stack[-1][2] -= e - s
            counts = op_name.startswith(program) and any(n in op_name for n in needles)
            stack.append([counts, e, e - s])
        close(float("inf"))
    return total / len(planes)
