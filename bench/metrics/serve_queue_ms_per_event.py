"""Milliseconds an event waits in the server's queue, from ``submit`` until
``step`` drains its batch: ``ServeStats.queue_wait_s`` over events
processed, both as differences over the window."""


def read(w):
    c = w["counters"]
    if "serve.queue_wait_s" not in c or not c.get("serve.events_processed"):
        return None
    return 1e3 * c["serve.queue_wait_s"] / c["serve.events_processed"]
