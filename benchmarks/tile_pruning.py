"""Beyond-paper: end-to-end effectiveness of the device-resident strip
gate (DESIGN.md §13) on the streaming engine.

The gate computes, per (query-tile × window-strip), the admissible upper
bound ``min(prefix, chunk-ℓ2) · exp(-λ·Δt_min)`` from carry-resident strip
summaries and skips every tile it proves below θ — before any dot product
runs.  This benchmark drives the real engine over a topically clustered
stream (:func:`topic_drift_stream`; isotropic data defeats value bounds by
construction) and reports, from the ``engine/prune/*`` metrics:

  * **skip fraction** per (capacity, θ, λ) — must grow with capacity at
    fixed (θ, λ): a larger window holds more stale topics whose strips
    the value bound kills (and, at λ > 0, more expired history);
  * **non-vacuity** — some but not all tiles are skipped (a gate that
    skips nothing is dead weight; one that skips everything is either
    broken or the stream is degenerate);
  * **items/sec, gate on vs off** at the largest capacity — the gated
    engine must clear 1.3× the ungated one at capacity ≥ 2^16 (the
    non-smoke claim; smoke shapes only exercise the paths).

Standalone usage (CI smoke runs this):

    PYTHONPATH=src python -m benchmarks.tile_pruning --smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List

from repro.data.synth import topic_drift_stream
from repro.engine import EngineConfig, StreamEngine
from repro.launch.compile_cache import enable_compile_cache

from .common import Row

JSON_PATH = "BENCH_prune.json"

THETAS = (0.5, 0.7)
LAMS = (0.0, 0.05)


def _drive(cfg: EngineConfig, vecs, ts, batch: int) -> StreamEngine:
    eng = StreamEngine(cfg)
    for i in range(0, vecs.shape[0], batch):
        eng.push(vecs[i : i + batch], ts[i : i + batch])
    return eng


def _skip_frac(eng: StreamEngine) -> tuple[float, float, float]:
    m = eng.metrics()
    total = max(m["engine/prune/tiles_total"], 1)
    st = m["engine/prune/tiles_skipped_time"] / total
    sl = m["engine/prune/tiles_skipped_l2"] / total
    return st + sl, st, sl


def run(fast: bool = True, smoke: bool = False) -> List[Row]:
    rows: List[Row] = []
    if smoke:
        d, mb, seg, batch = 32, 64, 128, 64
        caps = (256, 512, 1024)
        cap_speed, n_timed = 1024, 1024
    elif fast:
        d, mb, seg, batch = 64, 256, 1024, 256
        caps = (1024, 4096, 16384)
        cap_speed, n_timed = 1 << 16, 8192
    else:
        d, mb, seg, batch = 64, 256, 1024, 256
        caps = (4096, 16384, 65536)
        cap_speed, n_timed = 1 << 17, 16384
    rows.append(Row("prune/smoke_mode", float(smoke)))
    rows.append(Row("prune/capacity_speed", float(cap_speed)))

    def cfg(capacity, theta, lam, gate=None):
        return EngineConfig(
            theta=theta, lam=lam, capacity=capacity, d=d, micro_batch=mb,
            block_q=mb, block_w=mb, tile_k=256,
            max_pairs=4096, join_impl="scan", l2_gate=gate,
        )

    # ---- skip fraction per (capacity, θ, λ) -------------------------------
    for cap in caps:
        # fixed topic geometry across capacities: a larger window retains
        # more stale topics, so the value bound has more to kill
        vecs, ts = topic_drift_stream(
            2 * cap, d, n_topics=8, seg=seg, seed=13, rate=8.0
        )
        for theta in THETAS:
            for lam in LAMS:
                eng = _drive(cfg(cap, theta, lam), vecs, ts, batch)
                frac, f_time, f_l2 = _skip_frac(eng)
                m = eng.metrics()
                rows.append(Row(
                    f"prune/cap={cap}/theta={theta}/lam={lam}/skip_frac",
                    frac,
                    f"time={f_time:.3f} l2={f_l2:.3f} "
                    f"strips_survived={m['engine/prune/strips_survived']}",
                ))

    # ---- items/sec, gate on vs off, at the largest capacity ---------------
    theta, lam = 0.7, 0.0  # λ=0: the win must come from value bounds alone
    vecs, ts = topic_drift_stream(
        cap_speed + 2 * n_timed, d, n_topics=8, seg=seg, seed=17, rate=8.0
    )
    fill_v, fill_t = vecs[:cap_speed], ts[:cap_speed]
    timed_v, timed_t = vecs[cap_speed:], ts[cap_speed:]
    rates = {}
    for label, gate in (("on", None), ("off", False)):
        eng = _drive(cfg(cap_speed, theta, lam, gate=gate),
                     fill_v, fill_t, batch)   # warmup: jit + window fill
        eng.drain_arrays()
        t0 = time.perf_counter()
        for i in range(0, timed_v.shape[0], batch):
            eng.push(timed_v[i : i + batch], timed_t[i : i + batch])
        eng.drain_arrays()   # synchronizes with the device
        dt = time.perf_counter() - t0
        rates[label] = timed_v.shape[0] / dt
        extra = f"cap={cap_speed}, {dt*1e3:.0f} ms"
        if gate is None:
            frac, f_time, f_l2 = _skip_frac(eng)
            extra += f", skip_frac={frac:.3f} (l2={f_l2:.3f})"
        rows.append(Row(f"prune/gate_{label}/items_per_s", rates[label],
                        extra))
    rows.append(Row("prune/speedup_x", rates["on"] / rates["off"],
                    f"gate on vs off at cap={cap_speed}"))
    return rows


def check(rows: List[Row]) -> List[str]:
    problems: List[str] = []
    by = {r.name: r.value for r in rows}
    smoke = bool(by.get("prune/smoke_mode"))
    caps = sorted(
        {int(r.name.split("/")[1].split("=")[1])
         for r in rows if "/skip_frac" in r.name}
    )
    for theta in THETAS:
        for lam in LAMS:
            seq = [by[f"prune/cap={c}/theta={theta}/lam={lam}/skip_frac"]
                   for c in caps]
            # monotone in capacity at fixed (θ, λ); small tolerance for
            # the λ>0 rows where expiry already saturates the skip rate
            if not all(b >= a - 0.02 for a, b in zip(seq, seq[1:])):
                problems.append(
                    f"skip fraction not monotone in capacity at "
                    f"θ={theta} λ={lam}: {seq}"
                )
    fracs = [v for k, v in by.items() if k.endswith("/skip_frac")]
    if not any(0.0 < v < 1.0 for v in fracs):
        problems.append(f"gate vacuous on every cell: {fracs}")
    if max(fracs) <= 0.0:
        problems.append("gate never skipped a tile")
    if min(fracs) >= 1.0:
        problems.append("gate skipped every tile (degenerate stream)")
    if not smoke:
        if by.get("prune/capacity_speed", 0.0) < (1 << 16):
            problems.append("speedup not measured at capacity ≥ 2^16")
        if by.get("prune/speedup_x", 0.0) < 1.3:
            problems.append(
                f"gated engine under 1.3× ungated at capacity "
                f"{by.get('prune/capacity_speed'):.0f} "
                f"({by.get('prune/speedup_x'):.2f}×)"
            )
    return problems


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI): exercises every path, relaxes "
                         "the wall-clock claim")
    ap.add_argument("--full", action="store_true", help="paper-scale shapes")
    ap.add_argument("--json", default=JSON_PATH,
                    help=f"machine-readable output path (default {JSON_PATH})")
    args = ap.parse_args()
    t0 = time.time()
    rows = run(fast=not args.full, smoke=args.smoke)
    print("name,value,extra")
    for r in rows:
        print(r.csv())
    problems = check(rows)
    payload = {
        "benchmark": "tile_pruning",
        "mode": "smoke" if args.smoke else ("fast" if not args.full else "full"),
        "elapsed_s": round(time.time() - t0, 3),
        "rows": [dict(name=r.name, value=r.value, extra=r.extra) for r in rows],
        "problems": problems,
    }
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {args.json} ({len(rows)} rows) in {payload['elapsed_s']}s")
    for p in problems:
        print(f"# CLAIM-FAIL {p}")
    sys.exit(1 if problems else 0)


if __name__ == "__main__":
    main()
