"""Segment reductions' share of the HBM roofline.

The least time a segment reduction can take is the bytes it must move over
the chip's HBM bandwidth: its operands (row codes and value rows) read
once and its result written once, from each call's shapes in the trace.
Its operations (one add per value) would take a thousandth of that at the
chip's peak, so bytes bound it.  The share is that least time over the
kernels' device time, for the calls whose shapes the trace carries.
"""

KERNELS = ("level_segment_aggregate_", "segment_aggregate_")


def read(w):
    t = w["trace"]
    if t is None:
        return None
    calls = [c for c in t["calls"] if c["kernel"] in KERNELS and c["bytes"]]
    seconds = sum(c["seconds"] for c in calls)
    if not calls or seconds <= 0:
        return None
    least = sum(c["bytes"] for c in calls) / w["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
