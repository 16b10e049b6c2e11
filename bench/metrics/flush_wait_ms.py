"""Time per dispatched span the caller waits in a flush for the device and
the copy thread (the program's span/flush_wait)."""


def read(r):
    spans = r.delta("runtime/spans_dispatched")
    if not spans or "span/flush_wait/time_s" not in r.after:
        return None
    return 1e3 * r.delta("span/flush_wait/time_s") / spans
