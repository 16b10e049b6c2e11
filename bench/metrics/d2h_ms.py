"""Host time per dispatched span of the copy thread's transfer of the
span's ready outputs to host memory (the program's span/d2h)."""


def read(r):
    spans = r.delta("runtime/spans_dispatched")
    if not spans or "span/d2h/time_s" not in r.after:
        return None
    return 1e3 * r.delta("span/d2h/time_s") / spans
