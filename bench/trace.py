"""From a profiler trace to device busy time, kernel times and idle gaps.

Two steps, kept apart so that the second can be checked on a recorded
trace:

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
  keeps what the reduction needs: every device operation (planes named
  ``/device:TPU:<n>``, line ``XLA Ops``) as ``[name, start_ns, dur_ns]``
  with the HLO instruction's name (``sssj_candidates.18``), and the
  benchmark's own host annotations (``bench.*``).
* :func:`reduce` turns that into numbers: the traced window (the
  ``bench.window`` annotation), the union of device-operation intervals
  inside it (busy time, averaged over the chips used), the device time of
  each operation, and the idle gaps of the device, each attributed to the
  host annotation that covers its midpoint.

The ``XLA Ops`` line nests the operations of a loop body inside the loop's
own event (the scan over micro-batches is one ``while``).  Busy time is
the union of every event; per-operation times count leaf events only, so
that a loop's time is not counted again beside its body.
"""

from __future__ import annotations

import glob
import os

__all__ = ["DEVICE_OP_LINE", "base_name", "extract", "op_name", "reduce"]

DEVICE_OP_LINE = "XLA Ops"
WINDOW = "bench.window"


def extract(logdir: str) -> dict:
    """Device operations and ``bench.*`` annotations of the newest trace
    under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    ops.extend([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(text: str) -> str:
    """``%sssj_candidates.18 = (s32[...]...) custom-call(...)`` →
    ``sssj_candidates.18``."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``sssj_candidates.18`` → ``sssj_candidates``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _union(intervals):
    """Merged ``[start, end)`` intervals of sorted ``(start, end)`` pairs."""
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(ev: dict, top: int = 10) -> dict | None:
    """Numbers of one traced window; ``None`` when the trace holds no
    window or no device operation inside it."""
    wins = [e for e in ev["host"] if e[0] == WINDOW]
    if not wins or not ev["devices"]:
        return None
    w0, w1 = wins[0][1], wins[0][1] + wins[0][2]
    busy_ns, per_op, gaps = [], {}, {}
    notes = [e for e in ev["host"] if e[0] != WINDOW]
    for ops in ev["devices"].values():
        spans = []
        for i, (name, s, d) in enumerate(ops):
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            spans.append((s0, s1))
            if (i + 1 < len(ops) and ops[i + 1][1] < s + d
                    and sum(ops[i + 1][1:]) <= s + d):
                continue                      # a loop around later events
            per_op[name] = per_op.get(name, 0) + (s1 - s0)
        merged = _union(sorted(spans))
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            what = "no annotation"
            for name, s, d in notes:
                if s <= mid < s + d:
                    what = name       # innermost: the latest one to start
            gaps[what] = gaps.get(what, 0) + (b - a)
    if not per_op:
        return None
    n_dev = len(ev["devices"])
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])
    by_base = {}
    for k, v in per_op.items():
        by_base[base_name(k)] = by_base.get(base_name(k), 0) + v
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "op_s": {k: v / n_dev * 1e-9 for k, v in by_base.items()},
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in ranked[:top]],
        "idle_gaps": [[k, v / n_dev * 1e-9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
