"""The program's own scopes and spans in a profiler trace.

``bench/trace_reduce.py`` names device time by HLO operation and idle gaps
by the benchmark's own host spans.  This module reads, from the same
``.xplane.pb``, what the program marks itself (``src/repro/trace.py``):

- scopes: device seconds, own time as ``trace_reduce`` counts ``ops``, by
  the program scope each device operation ran under.  A TPU trace carries
  each operation's JAX op-name path (``jit(sparse_plan)/vmap(rowwise)/gather``)
  as the ``tf_op`` stat of the operation's metadata; the operation is keyed
  by the innermost path element that names a scope (``vmap(rowwise)`` names
  ``rowwise``), else ``unscoped``.  ``jax.profiler.ProfileData`` exposes no
  metadata stats, so the ``tf_op`` of each event is read here with a small
  reader of the protobuf wire format.
- spans: host seconds and counts by ``treant.*`` span name, any TraceMe
  ``#k=v#`` suffix stripped.
- gaps: device idle seconds by the innermost host span open at the middle of
  each gap, the program's ``treant.*`` spans among the benchmark's own.
- edges: device idle seconds before the first and after the last device
  operation of the profile's window.

A program that marks nothing reads as all ``unscoped`` and no spans.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

from jax.profiler import ProfileData

from bench import trace_reduce

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"  # where bench/run.py profiles a --trace 1 run
SPAN_PREFIX = "treant."
HOST_SPANS = trace_reduce.HOST_SPANS + (SPAN_PREFIX,)
SCOPES = frozenset({"rowwise", "finalize", "batch_stage", "batch_slice", "dense_contract",
                    "cube_slice", "row_blocks"})
REDUCE_SCOPE = "segment_reduce_"
UNSCOPED = "unscoped"


# -- the protobuf wire format, as much of it as an XSpace needs -----------------------
def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int, or a memoryview of
    a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map(entries, parse) -> dict:
    """A protobuf map field: entries of key (1) and value (2)."""
    out = {}
    for entry in entries:
        key = value = None
        for number, v in _fields(entry):
            if number == 1:
                key = v
            elif number == 2:
                value = v
        if key is not None and value is not None:
            out[key] = parse(value)
    return out


def _stat_name(meta) -> str:
    return next((_text(v) for n, v in _fields(meta) if n == 2), "")


def tf_ops(path: str) -> dict[str, list[tuple[str, str | None]]]:
    """For each TPU plane, the ``(name, tf_op)`` of every event of its
    ``XLA Ops`` line, in the order the file holds them.

    XSpace: planes (1).  XPlane: name (2), lines (3), event_metadata (4),
    stat_metadata (5).  XLine: name (2), events (4).  XEvent: metadata_id
    (1).  XEventMetadata: name (2), stats (5).  XStat: metadata_id (1),
    str_value (5), ref_value (7, a stat metadata id whose name is the value).
    """
    data = memoryview(Path(path).read_bytes())
    out = {}
    for number, plane in _fields(data):
        if number != 1:
            continue
        parts: dict[int, list] = {2: [], 3: [], 4: [], 5: []}
        for n, v in _fields(plane):
            if n in parts:
                parts[n].append(v)
        name = _text(parts[2][0]) if parts[2] else ""
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = _map(parts[5], _stat_name)
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}

        def event_meta(meta):
            label, op = "", None
            for n, v in _fields(meta):
                if n == 2:
                    label = _text(v)
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op_ids:
                        op = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7))
            return label, op

        metas = _map(parts[4], event_meta)
        for line in parts[3]:
            fields = list(_fields(line))
            if next((_text(v) for n, v in fields if n == 2), "") != "XLA Ops":
                continue
            ids = [next((v for n, v in _fields(ev) if n == 1), 0) for n, ev in fields if n == 4]
            out[name] = [metas.get(i, ("", None)) for i in ids]
    return out


def scope_of(tf_op: str | None) -> str:
    """The innermost program scope in a ``tf_op`` (a JAX op-name path, then
    ``:`` and the op's type), or ``unscoped``."""
    for element in reversed((tf_op or "").rsplit(":", 1)[0].split("/")):
        while element.endswith(")") and "(" in element:  # vmap(rowwise) -> rowwise
            element = element[element.index("(") + 1:-1]
        if element in SCOPES or element.startswith(REDUCE_SCOPE):
            return element
    return UNSCOPED


def span_name(name: str) -> str:
    """A host span's name without a TraceMe ``#k=v,...#`` suffix."""
    return name.split("#", 1)[0]


# -- the reduction --------------------------------------------------------------------
def host_spans(data: ProfileData) -> list[tuple[str, int, int]]:
    """``(name, start ns, end ns)`` of every host span of the benchmark's
    and the program's, names without a TraceMe suffix."""
    return [(span_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(HOST_SPANS)]


def reduce(path: str) -> dict:
    """Scopes, spans, gaps and edges of one trace file (module docstring)."""
    data = ProfileData.from_file(path)
    paths = tf_ops(path)
    host = host_spans(data)
    devices, env = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if lines:
                events = list(lines[0].events)
                raw = paths.get(plane.name, [])
                if [name for name, _ in raw] == [ev.name for ev in events]:
                    ops = [op for _, op in raw]
                else:  # not in file order: match by name
                    by_name = dict(reversed(raw))
                    ops = [by_name.get(ev.name) for ev in events]
                devices.append(list(zip(events, ops)))
        elif plane.name == "Task Environment":
            env = dict(plane.stats)
    if "profile_start_time" in env and "profile_stop_time" in env:
        w0, w1 = 0.0, float(env["profile_stop_time"] - env["profile_start_time"])
    elif host:
        w0, w1 = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        w0 = w1 = 0.0

    spans: dict[str, dict] = {}
    for name, s, e in host:
        if name.startswith(SPAN_PREFIX):
            acc = spans.setdefault(name, {"seconds": 0.0, "count": 0})
            acc["seconds"] += (e - s) / 1e9
            acc["count"] += 1

    scopes: dict[str, float] = {}
    scope_ops: dict[str, dict[str, float]] = {}
    gaps: dict[str, float] = {}
    edges = {"start": 0.0, "stop": 0.0}
    for events in devices:
        # own time: an operation's duration less that of the operations
        # nested in it, as trace_reduce counts it
        evs = sorted(events, key=lambda x: (x[0].start_ns, -x[0].duration_ns))
        own = [ev.duration_ns for ev, _ in evs]
        stack: list[tuple[float, int]] = []
        intervals = []
        for i, (ev, _) in enumerate(evs):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            intervals.append((s, e))
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                own[stack[-1][1]] -= ev.duration_ns
            stack.append((e, i))
        if not intervals:
            continue
        for (ev, op), t in zip(evs, own):
            key, label = scope_of(op), trace_reduce.op_label(ev.name)
            scopes[key] = scopes.get(key, 0.0) + max(t, 0.0) / 1e9
            per = scope_ops.setdefault(key, {})
            per[label] = per.get(label, 0.0) + max(t, 0.0) / 1e9
        lo, hi = (w0, w1) if w1 > w0 else (min(s for s, _ in intervals),
                                            max(e for _, e in intervals))
        busy = trace_reduce._union([(max(s, lo), min(e, hi)) for s, e in intervals
                                    if e > lo and s < hi])
        if not busy:
            continue
        edges["start"] += (busy[0][0] - lo) / 1e9 / len(devices)
        edges["stop"] += (hi - busy[-1][1]) / 1e9 / len(devices)
        bounds = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(bounds[0::2], bounds[1::2]):
            if b > a:
                mid = (a + b) / 2
                open_ = [(s, name) for name, s, e in host if s <= mid < e]
                label = max(open_)[1] if open_ else "none"
                gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(devices)
    return {"scopes": scopes, "scope_ops": scope_ops, "spans": spans, "gaps": gaps,
            "edges": edges}


@functools.lru_cache(maxsize=4)
def _reduce_cached(path: str, mtime: float) -> dict:
    return reduce(path)


def read(w: dict) -> dict | None:
    """The reduction of a ``--trace 1`` run's profile, once per file for
    every metric that reads it; None when the run traced nothing."""
    if w.get("trace") is None:
        return None
    path = trace_reduce.find(str(TRACE_DIR))
    return _reduce_cached(path, os.path.getmtime(path))
