"""Segment reductions' share of the HBM roofline, whatever implements them.

The least time a segment reduction can take is the bytes it must move over
the chip's HBM bandwidth: its row codes and value rows read once and its
(G, V) result written once, counted from the shapes of the operation that
reduces the rows (``trace_reduce.reduction_bytes``), so the Pallas kernel
and an XLA reduction of the same shapes count the same bytes.  Its
operations (one ⊕ per value) would take a thousandth of that at the chip's
peak, so bytes bound it.  The share is that least time over the device
seconds under the program's ``segment_reduce_<op>`` scopes, pads, copies
and transposes included.
"""

from bench import program_trace


def read(w):
    t = program_trace.read(w)
    if t is None:
        return None
    scoped = [k for k in t["scopes"] if k.startswith(program_trace.REDUCE_SCOPE)]
    seconds = sum(t["scopes"][k] for k in scoped)
    moved = sum(t["reduce_bytes"].get(k, 0) for k in scoped)
    if seconds <= 0 or not moved:
        return None
    return 100.0 * moved / w["peaks"]["hbm_bytes_per_s"] / seconds
