"""Device busy time per micro-batch outside the two Pallas kernels: the
level-2 merge, the window write and the summary refresh (XLA ops)."""


def read(r):
    if r.trace is None or not r.micro_batches:
        return None
    kern = sum(r.op_s(k) or 0.0 for k in ("sssj_candidates",
                                           "sssj_strip_gate"))
    return 1e3 * (r.trace["busy_s"] - kern) / r.micro_batches
