"""Share of the traced window, from the start of the device's first
operation to the end of its last, in which no operation ran on the device.
The profiler's start and stop latency before and after (the trace's
``edges``) is left out."""


def read(w):
    t = w["trace"]
    if t is None or t["window_s"] <= 0 or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
