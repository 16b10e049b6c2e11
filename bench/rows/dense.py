"""Dense unit rows (``rows`` absent or ``"dense"``): the stream of
:mod:`bench.gen` and the brute force of :mod:`bench.reference`."""

from __future__ import annotations

import math

from bench.gen import host_rows, make_plan
from bench.reference import reference_pairs

__all__ = ["block_rows", "host_rows", "make_plan", "reference_pairs"]


def block_rows(cfg: dict) -> int:
    """256 MiB of f32 rows at most, and no more than the ring holds."""
    return min(1 << int(math.log2((1 << 26) // cfg["d"])), cfg["capacity"])
