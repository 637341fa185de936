"""Host milliseconds in the program's ``treant.session.derive`` spans (each
session's queries re-derived, the affected vizzes named) per event completed
in the traced slice."""

from bench import program_trace

SPAN = "treant.session.derive"


def read(w):
    t = program_trace.read(w)
    if t is None or SPAN not in t["spans"] or not w["traced_events"]:
        return None
    return 1e3 * t["spans"][SPAN]["seconds"] / w["traced_events"]
