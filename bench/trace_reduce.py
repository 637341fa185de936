"""From a profiler trace to device busy and idle time, kernel time and bytes.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host spans are the
events of the host plane whose names the benchmark gave them
(``bench.*``, ``serve.*``).

- busy: the union of the device operations' intervals, averaged over the
  devices that ran any; the window is the profile's own start to stop
- ops: device seconds of each operation's own time (nested operations
  taken out), by its HLO instruction name without the instance number
- calls: every call of a kernel whose name starts with one of the given
  prefixes, with its device seconds and the bytes of its operands and
  result, read from the HLO text that names the operation on a TPU
  (``None`` where the trace carries no shapes)
- gaps: device idle seconds by the host span open at the middle of each gap
"""

from __future__ import annotations

import glob
import os
import re

from jax.profiler import ProfileData

HOST_SPANS = ("bench.", "serve.")
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
    m = re.search(r"[A-Za-z][\w.-]*\(", rest)
    if m is None:
        return None
    depth = 0
    for i in range(m.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return rest[: i + 1]
    return None


def hlo_bytes(text: str) -> int | None:
    """Bytes of an HLO instruction's result and operands, each once."""
    call = hlo_call(text)
    if call is None:
        return None
    total, seen = 0, False
    for dtype, dims in _SHAPE.findall(call):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
        seen = True
    return total if seen else None


def op_label(name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%segment_aggregate_sum.1 = f32[...] ...`` -> ``segment_aggregate_sum``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(path: str, kernels: tuple[str, ...] = ()) -> dict:
    """Reduce one trace file; ``kernels`` are name prefixes to attribute."""
    kernels = tuple(sorted(kernels, key=len, reverse=True))  # longest prefix wins
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if ops:
                devices.append(list(ops[0].events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events if ev.name.startswith(HOST_SPANS))
    # event times count from the start of the profile; the task plane
    # gives the profile's start and stop
    env = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            env = dict(plane.stats)
    if "profile_start_time" in env and "profile_stop_time" in env:
        w0, w1 = 0.0, float(env["profile_stop_time"] - env["profile_start_time"])
    elif host:
        w0, w1 = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        w0 = w1 = 0.0
    ops: dict[str, float] = {}
    calls: list[dict] = []
    busy_total, gaps = 0.0, {}
    n_dev = 0
    for events in devices:
        # an operation's own time: its duration less that of the operations
        # nested in it (a while loop holds its body's operations)
        evs = sorted(events, key=lambda ev: (ev.start_ns, -ev.duration_ns))
        own = [ev.duration_ns for ev in evs]
        stack: list[tuple[float, int]] = []
        spans = []
        for i, ev in enumerate(evs):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            spans.append((s, e))
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                own[stack[-1][1]] -= ev.duration_ns
            stack.append((e, i))
            label = op_label(ev.name)
            kernel = next((k for k in kernels if label.startswith(k)), None)
            if kernel is not None:
                calls.append({"kernel": kernel, "name": label,
                              "seconds": ev.duration_ns / 1e9,
                              "bytes": hlo_bytes(ev.name)})
        for ev, t in zip(evs, own):
            label = op_label(ev.name)
            ops[label] = ops.get(label, 0.0) + max(t, 0.0) / 1e9
        if not spans:
            continue
        n_dev += 1
        if w1 <= w0:
            w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
        busy = _union([(max(s, w0), min(e, w1)) for s, e in spans if e > w0 and s < w1])
        busy_total += sum(e - s for s, e in busy) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                open_ = [(s, name) for name, s, e in host if s <= mid < e]
                label = max(open_)[1] if open_ else "none"
                gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / len(devices)
    window_s = (w1 - w0) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_dev if n_dev else 0.0,
        "devices": n_dev,
        "ops": ops,
        "calls": calls,
        "gaps": gaps,
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": head(reduced["ops"]), "idle_gaps": head(reduced["gaps"])}
