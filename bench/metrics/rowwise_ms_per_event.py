"""Device milliseconds under the program's ``rowwise`` scope (incoming-message
gathers, ⊗ expansion, σ mask) per event completed in the traced slice."""

from bench import program_trace


def read(w):
    t = program_trace.read(w)
    if t is None or "rowwise" not in t["scopes"] or not w["traced_events"]:
        return None
    return 1e3 * t["scopes"]["rowwise"] / w["traced_events"]
