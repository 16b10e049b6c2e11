#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16 in the program's
place must come out not correct.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--rows N]

For each seed it makes the cell's stream at the cell's own size, samples
query rows from the first ``--rows`` arrivals after the ring as a run
does, and compares the pairs that the reference computes from bfloat16
inputs against the float32 reference, by the numbers and limits that
decide ``correct``.  It prints one JSON line per seed.  The benchmark's own
runs never run it.  Rounding the inputs to bfloat16 is a notion of dense
rows: a configuration of another row representation is refused.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402


def control(cell: dict, seed: int, rows: int) -> dict:
    from bench import gen, reference
    from bench.rows.dense import block_rows
    from bench.run import _sample

    cfg, mix = cell["cfg"], cell["mix"]
    if cfg.get("rows", "dense") != "dense":
        raise SystemExit(f"the bfloat16 control takes dense rows, not "
                         f"{cfg['rows']!r}")
    cap = cfg["capacity"]
    plan = gen.make_plan(cfg, mix, seed, cap + rows)
    block = block_rows(cfg)
    window = np.arange(cap, cap + rows)
    sample = _sample(np.random.default_rng([seed, 2]), window,
                     plan.anchored[window], mix["sample_rows"])
    admitted = np.ones(plan.n, bool)
    ref, low = (reference.reference_pairs(plan, cfg, sample, admitted, block,
                                          mix["sample_rows"], precision=p)
                for p in ("f32", "bf16"))
    th = np.asarray(cfg["thetas"], np.float32)
    theta_of = {g: th[plan.tenant[g]] for g in sample.tolist()}
    got = {g: {j: s for j, s in pairs.items() if s >= theta_of[g]}
           for g, pairs in low.items()}
    nums = reference.compare(got, ref, theta_of)
    fails = [k for k, v in nums.items()
             if not reference.within(v, cell["limits"][k])]
    return {"seed": seed, "numbers": nums, "fails": fails,
            "correct": not fails}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=16384)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.run import enable_cache, load_cell

    cell = load_cell(args.workload)
    enable_cache()
    for seed in args.seeds:
        print(json.dumps(control(cell, seed, args.rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
