"""Per-layer metric readers: one module per metric, found by its name.

Each module ``bench/metrics/<name>.py`` defines ``read(r: Readings)`` and
returns the metric's value, or ``None`` when the run holds nothing to
read (the harness then leaves the metric out of the result line).
"""

from __future__ import annotations

import dataclasses

from bench.load import module

__all__ = ["Readings", "read_metric"]


@dataclasses.dataclass
class Readings:
    """What a traced run hands the readers."""

    cfg: dict                 # the configuration file
    device_kind: str
    trace: dict | None        # bench.trace.reduce() of the window
    before: dict              # service registry snapshot before the window
    after: dict               # ... and after it
    batches: list             # (lo, hi) arrival ranges of real micro-batches
    tenant: object            # (m,) tenant of arrivals [0, m)

    def delta(self, key: str) -> float:
        return self.after.get(key, 0) - self.before.get(key, 0)

    @property
    def micro_batches(self) -> int:
        """Scan steps dispatched in the window (span-fill ones included)."""
        return int(self.delta("runtime/spans_dispatched")) * self.cfg["span"]

    def op_s(self, name: str) -> float | None:
        """Device seconds in the window of the operations whose HLO name
        is ``name`` with a numeric suffix (``sssj_candidates.18``)."""
        if self.trace is None:
            return None
        return self.trace["op_s"].get(name)


def read_metric(root: str, name: str, r: Readings):
    """Run the reader ``<root>/bench/metrics/<name>.py`` on ``r``."""
    return module(root, "metrics", name).read(r)
