"""Benchmark harness: one module per paper table/figure + beyond-paper.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--full]

Each module exposes ``run(fast) → [Row]`` and ``check(rows) → [problem]``;
the harness prints ``name,value,extra`` CSV and a claim-validation summary,
exiting non-zero if any paper claim fails to reproduce.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (
    engine_throughput, fig2_entries_ratio, fig34_mb_vs_str, fig56_indexes,
    fig789_params, kernel_bench, roofline_table, table2_completion,
    tile_pruning,
)

MODULES = [
    ("table2_completion", table2_completion),
    ("fig2_entries_ratio", fig2_entries_ratio),
    ("fig34_mb_vs_str", fig34_mb_vs_str),
    ("fig56_indexes", fig56_indexes),
    ("fig789_params", fig789_params),
    ("tile_pruning", tile_pruning),
    ("kernel_bench", kernel_bench),
    ("engine_throughput", engine_throughput),
    ("roofline_table", roofline_table),
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger scales (slower, closer to the paper's)")
    ap.add_argument("--only", help="run a single module by name")
    args = ap.parse_args()

    fast = not args.full
    all_problems = []
    print("name,value,extra")
    for name, mod in MODULES:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        rows = mod.run(fast=fast)
        for r in rows:
            print(r.csv())
        problems = mod.check(rows)
        status = "OK" if not problems else f"{len(problems)} CLAIM FAILURES"
        print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s — {status}")
        for p in problems:
            print(f"#   CLAIM-FAIL {p}")
        all_problems.extend(problems)
    print(f"# TOTAL: {'all paper claims reproduced' if not all_problems else all_problems}")
    sys.exit(1 if all_problems else 0)


if __name__ == "__main__":
    main()
