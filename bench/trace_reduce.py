"""From a profiler trace to device busy and idle time, by operation and by host span.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host spans are the
events of the host plane named by the benchmark (``bench.*``, ``serve.*``)
or by the program (``treant.*``), each without a TraceMe ``#k=v#`` suffix.

- window: each device's slice from the start of its first operation to the
  end of its last.  The idle time before and after it, the profiler's start
  and stop latency, is reported apart as the edges.  ``window_s``,
  ``busy_s`` and ``edges`` are averaged over the devices that ran any
  operation; ``profile_s`` is the profile's own start to stop
- busy: the union of the device operations' intervals in the window
- ops: device seconds of each operation's own time (nested operations
  taken out), by its HLO instruction name without the instance number
- gaps: device idle seconds inside the window by the innermost host span
  (the one opened last) open at the middle of each gap, else ``none``;
  they sum to ``window_s - busy_s``

``reduction_bytes`` gives the bytes a segment reduction must move, from the
shapes of the operation that reduces its rows.
"""

from __future__ import annotations

import glob
import math
import os
import re

from jax.profiler import ProfileData

HOST_SPANS = ("bench.", "serve.", "treant.")
# JAX primitives that reduce a segment reduction's rows: the Pallas kernel
# and XLA's scatter, which ``jax.ops.segment_*`` lower to
REDUCING = ("pallas_call", "scatter")
CODE_BYTES = 4  # a row's segment code is an int32 in either implementation
_OPCODE = re.compile(r"[A-Za-z][\w.-]*\(")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|f16|bf16|f32|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def find(directory: str) -> str:
    """The newest ``.xplane.pb`` under a trace directory."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def hlo_call(text: str) -> str | None:
    """The result type and operand list of one HLO instruction's text
    (``%name = TYPE opcode(OPERANDS), attributes``), without its attributes,
    which can repeat the operand shapes."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return None
    while True:  # drop layouts and attribute blocks, which hold parentheses
        bare = re.sub(r"\{[^{}]*\}", "", rest)
        if bare == rest:
            break
        rest = bare
    m = _OPCODE.search(rest)
    if m is None:
        return None
    depth = 0
    for i in range(m.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return rest[: i + 1]
    return None


def _arrays(text: str) -> list[tuple[str, list[int]]]:
    return [(dtype, [int(d) for d in dims.split(",") if d]) for dtype, dims in _SHAPE.findall(text)]


def hlo_bytes(text: str) -> int | None:
    """Bytes of an HLO instruction's result and operands, each once."""
    call = hlo_call(text)
    if call is None:
        return None
    arrays = _arrays(call)
    return sum(math.prod(dims) * _BYTES[dtype] for dtype, dims in arrays) if arrays else None


def reduction_bytes(text: str, path: str | None) -> int | None:
    """The bytes a segment reduction must move: its row codes and value rows
    read once, its result written once.  ``text`` is the HLO text of one
    device operation, ``path`` its JAX op-name path (the ``tf_op`` stat).

    Only the operation that reduces the rows counts: its primitive is a
    Pallas call or a scatter, and its operands' longest dimension,
    the rows N, is longer than every dimension of its result.  The result
    holds one leading dimension per ``vmap`` in the path (the members of a
    batch), then the segments G, then each segment's value columns, so a row
    carries (result elements ÷ G) values.  The count takes nothing else from
    the operands, so a Pallas kernel and an XLA reduction of the same rows,
    segments and columns count the same bytes, whatever the XLA reduction
    fuses in.  None for any other operation.
    """
    primitive = (path or "").rsplit(":", 1)[0].rsplit("/", 1)[-1]
    call = hlo_call(text) if primitive.startswith(REDUCING) else None
    if call is None:
        return None
    opcode = _OPCODE.search(call)
    result, operands = _arrays(call[:opcode.start()]), _arrays(call[opcode.end():])
    rows = max((d for _, dims in operands for d in dims), default=0)
    if not result or any(d >= rows for _, dims in result for d in dims):
        return None
    dtype, dims = result[0]
    members = path.count("vmap(")
    segments = dims[members] if len(dims) > members else 1
    cells = sum(math.prod(dims) for _, dims in result)
    return rows * CODE_BYTES + (cells * rows // segments + cells) * _BYTES[dtype]


def op_label(name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%segment_aggregate_sum.1 = f32[...] ...`` -> ``segment_aggregate_sum``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def span_name(name: str) -> str:
    """A host span's name without a TraceMe ``#k=v,...#`` suffix."""
    return name.split("#", 1)[0]


def host_spans(data: ProfileData) -> list[tuple[str, int, int]]:
    """``(name, start ns, end ns)`` of every host span of the benchmark's
    and the program's, names without a TraceMe suffix."""
    return [(span_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(HOST_SPANS)]


def device_events(data: ProfileData) -> dict[str, list]:
    """The events of the ``XLA Ops`` line of each TPU plane, by plane name."""
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if lines:
                out[plane.name] = list(lines[0].events)
    return out


def own_times(events: list) -> list[tuple[int, float]]:
    """``(index, own ns)`` of each event in start order: its duration less
    that of the events nested in it (a while loop holds its body's
    operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].duration_ns))
    own = [float(ev.duration_ns) for ev in events]
    stack: list[tuple[float, int]] = []
    for i in order:
        s = events[i].start_ns
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= events[i].duration_ns
        stack.append((s + events[i].duration_ns, i))
    return [(i, max(own[i], 0.0)) for i in order]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gap_label(host: list[tuple[str, int, int]], a: float, b: float) -> str:
    """The innermost host span open at the middle of the gap ``[a, b)``."""
    mid = (a + b) / 2
    open_ = [(s, name) for name, s, e in host if s <= mid < e]
    return max(open_)[1] if open_ else "none"


def reduce(path: str) -> dict:
    """Reduce one trace file (module docstring)."""
    data = ProfileData.from_file(path)
    devices = list(device_events(data).values())
    host = host_spans(data)
    # event times count from the start of the profile; the task plane
    # gives the profile's start and stop
    env = next((dict(p.stats) for p in data.planes if p.name == "Task Environment"), {})
    if "profile_start_time" in env and "profile_stop_time" in env:
        w0, w1 = 0.0, float(env["profile_stop_time"] - env["profile_start_time"])
    else:
        ends = [(ev.start_ns, ev.start_ns + ev.duration_ns) for evs in devices for ev in evs]
        ends += [(s, e) for _, s, e in host]
        w0, w1 = (min(s for s, _ in ends), max(e for _, e in ends)) if ends else (0.0, 0.0)
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    window = busy_total = start = stop = 0.0
    ran = []
    for events in devices:
        for i, t in own_times(events):
            label = op_label(events[i].name)
            ops[label] = ops.get(label, 0.0) + t / 1e9
        busy = _union([(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
                       for ev in events if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1])
        if busy:
            ran.append(busy)
    for busy in ran:
        lo, hi = busy[0][0], busy[-1][1]
        window += (hi - lo) / 1e9 / len(ran)
        busy_total += sum(e - s for s, e in busy) / 1e9 / len(ran)
        start += (lo - w0) / 1e9 / len(ran)
        stop += (w1 - hi) / 1e9 / len(ran)
        for (_, a), (b, _) in zip(busy, busy[1:]):
            label = gap_label(host, a, b)
            gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(ran)
    return {
        "window_s": window,
        "busy_s": busy_total,
        "profile_s": (w1 - w0) / 1e9,
        "edges": {"start": start, "stop": stop},
        "devices": len(ran),
        "ops": ops,
        "gaps": gaps,
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": head(reduced["ops"]), "idle_gaps": head(reduced["gaps"])}
