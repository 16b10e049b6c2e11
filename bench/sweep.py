#!/usr/bin/env python3
"""Knee sweep: the highest open-loop rate a cell's service sustains.

    python3 bench/sweep.py --workload <rate cell> --seed <n> --seconds 8 \
        --fractions 0.5 0.6 0.7 0.8 0.9 1.0 1.1

One process fills the ring once, measures the backlogged throughput R for
``--seconds``, then offers the cell's Poisson client at each fraction of R
in turn, on the following arrivals of the same stream.  A rate is sustained
when the mean latency of its last quarter of arrivals is under 1.5× that
of its first quarter (no growing backlog).  Prints one JSON line per rate; the cell's file keeps
4/5 of the highest sustained rate.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.5, 0.6, 0.7, 0.8, 0.9, 1.0, 1.1])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import client
    from bench.run import chip, enable_cache, load_cell, row_sides

    cell = load_cell(args.workload)
    enable_cache()
    chip(cell["workload"]["chips"])
    cfg, mix = cell["cfg"], cell["mix"]
    data, serve = row_sides(cell)
    cap, warm = cfg["capacity"], cfg["span"] * cfg["micro_batch"]
    block = data.block_rows(cfg)
    budget = int(8000 * args.seconds * (1 + len(args.fractions) * 1.2))
    plan = data.make_plan(cfg, mix, args.seed, cap + budget)
    svc = serve.build_service(cfg)
    serve.install(svc, plan, cap - warm, block)
    rng = np.random.default_rng([args.seed, 3])
    at = cap - warm

    def pool(n):
        nonlocal at
        p = client.Pool.of(plan, data.host_rows(plan, at, at + n, block),
                           at, at + n, serve.submit_args)
        at += n
        return p

    def rows_of(n_req):
        """Rows of the next ``n_req`` requests from ``at``."""
        s = plan.starts[plan.starts > at]
        return int(s[n_req - 1]) - at

    client.run_client(svc, pool(warm), cfg,
                      {"kind": "backlog", "spans_queued": 1}, math.inf)
    svc.flush(final=True)
    sat = client.run_client(svc, pool(int(4000 * args.seconds) // (2 * warm)
                                      * 2 * warm), cfg,
                            {"kind": "backlog", "spans_queued": 2},
                            args.seconds)
    r_sat = sat.returned / (sat.t_end - sat.t0)
    svc.flush(final=True)
    print(json.dumps({"backlog_items_per_s": r_sat}), flush=True)
    for f in args.fractions:
        rate = f * r_sat
        cl = dict(mix["client"], rate=rate)
        lo, hi = mix["request_items"]
        due = client.poisson_due(rng, rate * 2 / (lo + hi), args.seconds)
        if at + hi * due.size > plan.n:
            break
        res = client.run_client(svc, pool(rows_of(due.size)), cfg, cl,
                                args.seconds, due)
        lat = res.latency_s
        q = max(1, lat.size // 4)
        growth = float(lat[-q:].mean() / lat[:q].mean())
        done = res.returned / (res.t_end - res.t0)
        print(json.dumps({
            "fraction": f, "rate": rate, "completed_per_s": done,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "growth": growth,
            "sustained": bool(growth < 1.5),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
