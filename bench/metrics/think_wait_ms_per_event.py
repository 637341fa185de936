"""Milliseconds an event's step waited for a think-time unit still on the
device: ``serve.think_wait_s`` over ``serve.events_processed``, both as
differences over the window.  None where no think-time unit ran in the
window (``scheduler.units``), or the program does not count it."""


def read(w):
    c = w["counters"]
    if not c.get("scheduler.units") or "serve.think_wait_s" not in c \
            or not c.get("serve.events_processed"):
        return None
    return 1e3 * c["serve.think_wait_s"] / c["serve.events_processed"]
