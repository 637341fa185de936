"""Device milliseconds of segment-reduction kernels per event completed in
the traced slice of the window."""

KERNELS = ("level_segment_aggregate_", "segment_aggregate_")


def read(w):
    t = w["trace"]
    if t is None or not w["traced_events"]:
        return None
    calls = [c for c in t["calls"] if c["kernel"] in KERNELS]
    if not calls:
        return None
    return 1e3 * sum(c["seconds"] for c in calls) / w["traced_events"]
