"""Host time per dispatched span to group the drained rows by tenant and
union their pairs (the program's span/emit and span/group)."""


def read(r):
    spans = r.delta("runtime/spans_dispatched")
    if not spans or "span/group/time_s" not in r.after:
        return None
    t = r.delta("span/emit/time_s") + r.delta("span/group/time_s")
    return 1e3 * t / spans
