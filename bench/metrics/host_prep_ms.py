"""Host time to admit, coalesce and copy one dispatched span to the
device (the program's span/admit, span/coalesce and span/h2d)."""


def read(r):
    spans = r.delta("runtime/spans_dispatched")
    if not spans:
        return None
    t = sum(r.delta(f"span/{s}/time_s") for s in ("admit", "coalesce", "h2d"))
    return 1e3 * t / spans
