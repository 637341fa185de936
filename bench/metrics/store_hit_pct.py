"""Share of message-store probes that hit (``cache_stats`` hits, misses)."""


def read(w):
    c = w["counters"]
    probes = c["hits"] + c["misses"]
    return 100.0 * c["hits"] / probes if probes else None
