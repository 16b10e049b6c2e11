"""Device time of the strip-gate kernel per micro-batch, from the trace
events named ``sssj_strip_gate``."""

KERNEL = "sssj_strip_gate"


def read(r):
    s = r.op_s(KERNEL)
    if s is None or not r.micro_batches:
        return None
    return 1e3 * s / r.micro_batches
