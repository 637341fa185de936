"""The program's own scopes and spans in a profiler trace.

``bench/trace_reduce.py`` names device time by HLO operation and idle gaps
by the innermost host span.  This module reads, from the same
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
- reduce_bytes: by ``segment_reduce_<op>`` scope, the bytes its segment
  reductions must move (``trace_reduce.reduction_bytes``), whether the
  Pallas kernel or XLA reduced.

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


# -- the reduction --------------------------------------------------------------------
def reduce(path: str) -> dict:
    """Scopes, spans and reduction bytes of one trace file (module docstring)."""
    data = ProfileData.from_file(path)
    paths = tf_ops(path)
    spans: dict[str, dict] = {}
    for name, s, e in trace_reduce.host_spans(data):
        if name.startswith(SPAN_PREFIX):
            acc = spans.setdefault(name, {"seconds": 0.0, "count": 0})
            acc["seconds"] += (e - s) / 1e9
            acc["count"] += 1
    scopes: dict[str, float] = {}
    scope_ops: dict[str, dict[str, float]] = {}
    reduce_bytes: dict[str, int] = {}
    for plane, events in trace_reduce.device_events(data).items():
        raw = paths.get(plane, [])
        if [name for name, _ in raw] == [ev.name for ev in events]:
            ops = [op for _, op in raw]
        else:  # not in file order: match by name
            by_name = dict(reversed(raw))
            ops = [by_name.get(ev.name) for ev in events]
        for i, t in trace_reduce.own_times(events):
            key, label = scope_of(ops[i]), trace_reduce.op_label(events[i].name)
            scopes[key] = scopes.get(key, 0.0) + t / 1e9
            per = scope_ops.setdefault(key, {})
            per[label] = per.get(label, 0.0) + t / 1e9
            moved = trace_reduce.reduction_bytes(events[i].name, ops[i]) \
                if key.startswith(REDUCE_SCOPE) else None
            if moved is not None:
                reduce_bytes[key] = reduce_bytes.get(key, 0) + moved
    return {"scopes": scopes, "scope_ops": scope_ops, "spans": spans,
            "reduce_bytes": reduce_bytes}


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
