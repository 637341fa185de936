"""Share of the traced window in which no operation ran on the device."""


def read(w):
    t = w["trace"]
    if t is None or t["window_s"] <= 0 or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
