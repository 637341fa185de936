"""Share of the message-store entries the think-time drain wrote in the
window that an execution then read: ``store.think_read`` over
``store.think_puts``, both as differences over the window.  None where the
drain wrote nothing, or the program does not count it."""


def read(w):
    c = w["counters"]
    if not c.get("store.think_puts") or "store.think_read" not in c:
        return None
    return 100.0 * c["store.think_read"] / c["store.think_puts"]
