"""Device milliseconds under the program's ``segment_reduce_<op>`` scopes per
event completed in the traced slice: every segment reduction, whatever
implements it (Pallas kernel or XLA), its own pads, transposes and copies
included."""

from bench import program_trace


def read(w):
    t = program_trace.read(w)
    if t is None or not w["traced_events"]:
        return None
    seconds = [s for k, s in t["scopes"].items() if k.startswith(program_trace.REDUCE_SCOPE)]
    return 1e3 * sum(seconds) / w["traced_events"] if seconds else None
