"""The least work an exact candidate join has to do, from the stream's own
record, and the chip's peaks.

For a micro-batch of arrivals ``[lo, hi)``, query ``g`` of tenant ``k`` must
score every earlier row of tenant ``k`` within ``k``'s horizon
``ln(1/θ_k)/λ``: the window rows ``[g - H_k, lo)`` and the micro-batch's
own rows before ``g``.  Flops are ``2 · d`` per (query, row); bytes are the
union of those rows over the micro-batch (and the micro-batch itself),
``d · 4`` each.  No exact implementation can read or compute less, whatever
index it uses, where no admissible bound prunes a row — the isotropic
stream at d = 768.
"""

from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["cand_work", "peaks", "least_time"]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a kind with no entry is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json")
    return table[device_kind]


def cand_work(tenant: np.ndarray, horizon: np.ndarray, batches, d: int):
    """Per micro-batch ``(bytes, flops)`` arrays for ``batches``, a list of
    ``(lo, hi)`` arrival ranges; ``tenant`` covers arrivals ``[0, max hi)``."""
    k_n = horizon.size
    cum = np.zeros((k_n, tenant.size + 1), np.int64)
    for k in range(k_n):
        cum[k, 1:] = np.cumsum(tenant == k)
    h = np.floor(horizon).astype(np.int64)
    out_b, out_f = [], []
    for lo, hi in batches:
        g = np.arange(lo, hi)
        k = tenant[lo:hi].astype(np.int64)
        start = np.maximum(g - h[k], 0)
        win = np.maximum(cum[k, lo] - cum[k, np.minimum(start, lo)], 0)
        own = cum[k, g] - cum[k, lo]
        rows = 0
        for kk in np.unique(k):
            s = start[k == kk].min()
            rows += cum[kk, lo] - cum[kk, min(s, lo)]
        out_b.append((rows + (hi - lo)) * d * 4)
        out_f.append(int((win + own).sum()) * 2 * d)
    return np.asarray(out_b, np.float64), np.asarray(out_f, np.float64)


def least_time(nbytes, flops, peak: dict):
    """Per micro-batch least time (s) and which bound sets it."""
    t_mem = np.asarray(nbytes) / peak["hbm_bytes_per_s"]
    t_mxu = np.asarray(flops) / peak["bf16_flops_per_s"]
    bound = "hbm" if t_mem.sum() >= t_mxu.sum() else "mxu"
    return np.maximum(t_mem, t_mxu), bound
