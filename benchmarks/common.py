"""Shared benchmark utilities: timing, scaled dataset specs, CSV rows."""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

from repro.core import Counters, join_stream, make_joiner
from repro.data.synth import StreamSpec, synthetic_stream

__all__ = [
    "BENCH_SPECS",
    "DEVICE_PEAKS",
    "device_peaks",
    "run_config",
    "Row",
    "grid",
    "fmt_rows",
]

# Scaled-down analogues of the paper's Table 1 (sizes cut so the full
# harness completes in minutes on one CPU core; density + timestamp
# character preserved — the quantities compared are *relative*).
BENCH_SPECS: Dict[str, StreamSpec] = {
    "webspam": StreamSpec("webspam", 1200, 4096, 180.0, "poisson", rate=1.0),
    "rcv1": StreamSpec("rcv1", 3000, 2048, 40.0, "sequential", rate=1.0),
    "blogs": StreamSpec("blogs", 4000, 4096, 24.0, "bursty", rate=1.0),
    "tweets": StreamSpec("tweets", 6000, 8192, 8.0, "bursty", rate=1.0),
}


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, HBM at 819 GB/s.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device) -> Dict[str, float]:
    """The peaks of ``device``; a kind with no published entry is an error,
    never a default."""
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r}; "
            f"add them to benchmarks/common.py DEVICE_PEAKS with a source"
        ) from None


@dataclasses.dataclass
class Row:
    name: str
    value: float
    extra: str = ""

    def csv(self) -> str:
        return f"{self.name},{self.value:.6g},{self.extra}"


def run_config(
    items,
    framework: str,
    index: str,
    theta: float,
    lam: float,
    timeout_s: Optional[float] = None,
) -> Tuple[Optional[float], Counters, int]:
    """Run one (framework × index × θ × λ) config.

    Returns (seconds or None on timeout, counters, n_pairs).  The timeout is
    cooperative (checked between items) — the analogue of the paper's
    3-hour per-config budget.
    """
    c = Counters()
    j = make_joiner(framework, index, theta, lam, counters=c)
    t0 = time.perf_counter()
    pairs = 0
    deadline = t0 + timeout_s if timeout_s else None
    for k, item in enumerate(items):
        pairs += len(j.push(item))
        if deadline and (k & 63) == 0 and time.perf_counter() > deadline:
            return None, c, pairs
    pairs += len(j.finish())
    return time.perf_counter() - t0, c, pairs


def grid(thetas, lams):
    return [(th, lm) for th in thetas for lm in lams]


def fmt_rows(rows: List[Row]) -> str:
    return "\n".join(r.csv() for r in rows)
