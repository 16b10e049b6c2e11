"""Median time from a row's admission to the return of the drain that hands
it to the caller: the p50 of the window's rows in the program's
latency/admit_to_emit_s histogram (bucket counts after the window minus
before it, linearly interpolated inside the bucket)."""

KEY = "latency/admit_to_emit_s"


def read(r):
    after = r.after.get(KEY)
    if after is None:
        return None
    before = r.before.get(KEY) or {"counts": [0] * len(after["counts"])}
    bounds = after["bounds"]
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    n = sum(counts)
    if not n:
        return None
    target, cum = 0.5 * n, 0
    for i, c in enumerate(counts):
        if c and cum + c >= target:
            if i == len(bounds):          # above the last bound
                return 1e3 * bounds[-1]
            lo = bounds[i - 1] if i else 0.0
            return 1e3 * (lo + (target - cum) / c * (bounds[i] - lo))
        cum += c
