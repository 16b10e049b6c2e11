"""Beyond-paper: hierarchical vs dense compaction on the streaming engine.

Three drivers over identical streams and join parameters (all XLA-compiled
CPU paths, so wall-clock is meaningful — the Pallas kernel itself targets
TPU and only runs interpreted here):

  * **host**   — the pre-engine host loop: one jit call per micro-batch,
    fetch the dense ``(B, capacity)`` + ``(B, B)`` score matrices, extract
    pairs with ``np.nonzero`` on the host;
  * **dense**  — the PR-1 engine (``emit_dense=True``): scan-pipelined, but
    every micro-batch materializes the dense score matrix in HBM and
    compacts it with one global ``lax.top_k`` over ``B·(capacity+B)``
    elements;
  * **hier**   — the hierarchical engine (default): level-1 per-tile
    candidate selection fused into the join (dead strips are skipped by the
    tile-level time filter), level-2 segmented merge.  No ``O(B·capacity)``
    array is ever allocated or sorted.

Claims checked (ISSUE 2 acceptance):

  * identical pair sets across all three drivers;
  * hier ≥ 2× dense items/sec at ``capacity ≥ 16384``;
  * hier runs at a capacity whose dense per-micro-batch intermediate
    (reported as a peak-memory estimate) would dwarf the old path;
  * compacted emission still moves O(pairs) host←device bytes.

A compaction-stage timing breakdown (global top-k vs tile-select + merge on
the same workload) and per-path peak-intermediate estimates are reported,
and everything is emitted machine-readably to ``BENCH_engine.json``.

Standalone usage (CI smoke runs this):

    PYTHONPATH=src python -m benchmarks.engine_throughput --smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synth import StreamSpec, dense_embedding_stream, synthetic_stream
from repro.engine import EngineConfig, StreamEngine
from repro.engine.window import init_window, push_with_overflow
from repro.kernels.sssj_join import (
    compact_pairs,
    merge_candidates,
    sssj_join_scores,
    tile_candidates,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import publish_counters

from .common import Row, run_config

JSON_PATH = "BENCH_engine.json"


class _HostDriver:
    """The pre-engine host loop (kept here as the historical baseline)."""

    def __init__(self, cfg: EngineConfig) -> None:
        self.kw = dict(theta=cfg.theta, lam=cfg.lam, block_q=cfg.block_q,
                       block_w=cfg.block_w, chunk_d=cfg.chunk_d,
                       use_ref=cfg.use_ref)
        self.state = init_window(cfg.capacity, cfg.d)
        self.tau = cfg.tau
        self.uid0 = 0
        self.bytes_to_host = 0

    def feed(self, vecs, ts, batch: int) -> set:
        pairs = set()
        for i in range(0, vecs.shape[0], batch):
            q = jnp.asarray(vecs[i:i + batch])
            tq = jnp.asarray(ts[i:i + batch], jnp.float32)
            uq = np.arange(self.uid0, self.uid0 + q.shape[0], dtype=np.int32)
            self.uid0 += q.shape[0]
            w_uids = np.asarray(self.state.uids)
            uqj = jnp.asarray(uq)
            s_win, _ = sssj_join_scores(q, self.state.vecs, tq, self.state.ts,
                                        uqj, self.state.uids, **self.kw)
            s_self, _ = sssj_join_scores(q, q, tq, tq, uqj, uqj, **self.kw)
            s_win = np.asarray(s_win)
            s_self = np.asarray(s_self)
            self.bytes_to_host += s_win.nbytes + s_self.nbytes
            for a, b in zip(*np.nonzero(s_win)):
                pairs.add((int(w_uids[b]), int(uq[a])))
            for a, b in zip(*np.nonzero(s_self)):
                pairs.add((int(uq[b]), int(uq[a])))
            self.state = push_with_overflow(
                self.state, q, tq, uqj, jnp.int32(q.shape[0]), tq.max(),
                self.tau,
            )
        return pairs


class _EngineDriver:
    def __init__(self, cfg: EngineConfig) -> None:
        self.engine = StreamEngine(cfg)

    def feed(self, vecs, ts, batch: int) -> set:
        eng = self.engine
        for i in range(0, vecs.shape[0], batch):
            eng.push(vecs[i:i + batch], ts[i:i + batch])
        ua, ub, _ = eng.drain_arrays()
        return set(zip(ub.tolist(), ua.tolist()))


def _timed_feed(driver, vecs, ts, batch):
    t0 = time.perf_counter()
    driver.feed(vecs, ts, batch)
    return time.perf_counter() - t0


def _compaction_stage_ms(scores, uq, uw_all, mb, cap, tile_k, max_pairs, reps=5):
    """Identical workload through both compaction schemes, join excluded."""
    dense_c = jax.jit(lambda s: compact_pairs(s, uq, uw_all, max_pairs=max_pairs))
    hier_sel = jax.jit(
        lambda s: tile_candidates(s, uq, uw_all, block_q=mb, block_w=mb,
                                  tile_k=tile_k)[0]
    )
    hier_mrg = jax.jit(lambda c: merge_candidates(c, max_pairs=max_pairs))

    def clock(f, *a):
        jax.block_until_ready(f(*a))          # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / reps * 1e3

    t_dense = clock(dense_c, scores)
    cands = hier_sel(scores)
    return t_dense, clock(hier_sel, scores), clock(hier_mrg, cands)


def run(fast: bool = True, smoke: bool = False) -> List[Row]:
    rows: List[Row] = []
    if smoke:
        n, d, batch, mb = 512, 32, 128, 64
        cap_small, cap_big, cap_huge = 512, 1024, 4096
    elif fast:
        n, d, batch, mb = 2048, 64, 256, 128
        cap_small, cap_big, cap_huge = 1024, 16384, 1 << 18
    else:
        n, d, batch, mb = 8192, 64, 256, 128
        cap_small, cap_big, cap_huge = 1024, 65536, 1 << 20
    theta, lam = 0.75, 0.05
    max_pairs, tile_k = 2048, 256
    rows.append(Row("engine/smoke_mode", float(smoke)))
    rows.append(Row("engine/capacity_big", float(cap_big)))

    def cfg(capacity, **kw):
        base = dict(theta=theta, lam=lam, capacity=capacity, d=d,
                    micro_batch=mb, max_pairs=max_pairs, tile_k=tile_k,
                    block_q=mb, block_w=mb)
        base.update(kw)
        return EngineConfig(**base)

    # one long stream: a warmup prefix (jit compilation + window fill) and a
    # timed continuation — a streaming service runs at steady state
    vecs, ts = dense_embedding_stream(2 * n, d, seed=11, rate=4.0)

    # ---- equivalence at a small capacity: all three drivers, one truth ----
    host = _HostDriver(cfg(cap_small, use_ref=True))
    dense = _EngineDriver(cfg(cap_small, emit_dense=True, use_ref=True))
    hier = _EngineDriver(cfg(cap_small))
    host_pairs = host.feed(vecs[:n], ts[:n], batch)
    dense_pairs = dense.feed(vecs[:n], ts[:n], batch)
    hier_pairs = hier.feed(vecs[:n], ts[:n], batch)
    match = host_pairs == dense_pairs == hier_pairs
    rows.append(Row("engine/pair_sets_match", float(match),
                    f"{len(hier_pairs)} pairs, 3 drivers"))

    h0 = host.bytes_to_host
    t_host = _timed_feed(host, vecs[n:], ts[n:], batch)
    rows.append(Row("engine/host/items_per_s", n / t_host,
                    f"cap={cap_small}, {t_host*1e3:.0f} ms"))
    rows.append(Row("engine/host/bytes_per_batch",
                    (host.bytes_to_host - h0) / (-(-n // batch)),
                    "O(B·capacity) host←device"))
    e0 = hier.engine.bytes_to_host
    t_hier_small = _timed_feed(hier, vecs[n:], ts[n:], batch)
    rows.append(Row("engine/hier/items_per_s", n / t_hier_small,
                    f"cap={cap_small}, {t_hier_small*1e3:.0f} ms"))
    rows.append(Row("engine/hier/bytes_per_batch",
                    (hier.engine.bytes_to_host - e0) / (-(-n // batch)),
                    "O(max_pairs) host←device"))
    rows.append(Row("engine/bytes_reduction_x",
                    (host.bytes_to_host - h0)
                    / max(hier.engine.bytes_to_host - e0, 1)))
    rows.append(Row("engine/pairs_dropped",
                    float(hier.engine.pairs_dropped)))

    # ---- paper-counters bridge (DESIGN.md §12) ----------------------------
    # the paper's host-side Fig. 2/6 counters (entries traversed,
    # candidates generated, full similarities) and the device engine's
    # telemetry, published into ONE registry and read from one snapshot
    n_ref = 200 if smoke else 600
    spec = StreamSpec("bridge", n_ref, 1024, 16.0, "poisson", rate=1.0)
    _, c_ref, ref_pairs = run_config(
        synthetic_stream(spec, seed=9), "STR", "L2", theta, 0.05
    )
    publish_counters(hier.engine.registry, c_ref)
    snap = hier.engine.metrics()
    rows.append(Row("paper/entries_traversed",
                    float(snap["paper/entries_traversed"]),
                    "STR × L2 reference joiner (Fig. 2/6 vocabulary)"))
    rows.append(Row("paper/candidates_generated",
                    float(snap["paper/candidates_generated"])))
    rows.append(Row("paper/full_sims_computed",
                    float(snap["paper/full_sims_computed"])))
    rows.append(Row("paper/pairs_emitted", float(snap["paper/pairs_emitted"]),
                    f"{ref_pairs} pairs over {n_ref} items"))
    rows.append(Row("obs/unified_snapshot", float(
        snap["paper/items_processed"] == n_ref
        and snap["engine/n_items"] == 2 * n
    ), "paper/… and engine/… coherent in one registry snapshot"))

    # ---- the tentpole claim: hier ≥ 2× dense at a large capacity ----------
    dense_big = _EngineDriver(cfg(cap_big, emit_dense=True, use_ref=True))
    hier_big = _EngineDriver(cfg(cap_big))
    pd = dense_big.feed(vecs[:n], ts[:n], batch)      # warmup + fill
    ph = hier_big.feed(vecs[:n], ts[:n], batch)
    match_big = pd == ph
    t_dense_big = _timed_feed(dense_big, vecs[n:], ts[n:], batch)
    t_hier_big = _timed_feed(hier_big, vecs[n:], ts[n:], batch)
    rows.append(Row("engine/dense_bigcap/items_per_s", n / t_dense_big,
                    f"cap={cap_big}, {t_dense_big*1e3:.0f} ms"))
    rows.append(Row("engine/hier_bigcap/items_per_s", n / t_hier_big,
                    f"cap={cap_big}, {t_hier_big*1e3:.0f} ms"))
    rows.append(Row("engine/hier_speedup_x", t_dense_big / t_hier_big,
                    f"vs PR-1 dense compaction at cap={cap_big}"))
    rows.append(Row("engine/bigcap_pair_sets_match", float(match_big)))

    # ---- strip-gate skip fraction (DESIGN.md §13) -------------------------
    # the hier drivers run gate-auto-on; a larger window holds more expired
    # history, so the admissible skip fraction must grow with capacity
    for label, drv, cap in (("smallcap", hier, cap_small),
                            ("bigcap", hier_big, cap_big)):
        m = drv.engine.metrics()
        total = max(m["engine/prune/tiles_total"], 1)
        skipped = (m["engine/prune/tiles_skipped_time"]
                   + m["engine/prune/tiles_skipped_l2"])
        rows.append(Row(f"engine/prune/{label}_skip_frac", skipped / total,
                        f"cap={cap}, survived="
                        f"{m['engine/prune/strips_survived']}"))

    # ---- compaction-stage breakdown on the identical dense workload -------
    rng = np.random.default_rng(3)
    sc = np.where(rng.random((mb, cap_big + mb)) < 2e-4,
                  rng.uniform(theta, 1.0, (mb, cap_big + mb)), 0.0)
    scores = jnp.asarray(sc, jnp.float32)
    uq = jnp.arange(cap_big, cap_big + mb, dtype=jnp.int32)
    uw_all = jnp.arange(cap_big + mb, dtype=jnp.int32)
    t_topk, t_sel, t_mrg = _compaction_stage_ms(
        scores, uq, uw_all, mb, cap_big, tile_k, max_pairs
    )
    rows.append(Row("compact_stage/dense_topk_ms", t_topk,
                    f"lax.top_k over {mb*(cap_big+mb)/1e6:.1f}M"))
    rows.append(Row("compact_stage/tile_select_ms", t_sel,
                    "level-1 (from dense input; fused into join in engine)"))
    rows.append(Row("compact_stage/merge_ms", t_mrg,
                    f"level-2 over {(cap_big+mb)//mb + 1} segments"))

    # ---- peak per-micro-batch intermediate estimates ----------------------
    n_tiles = (cap_big + mb) // mb + 1
    dense_bytes = 4 * mb * (cap_big + mb)
    hier_bytes = n_tiles * (tile_k * 8 + 12) + 4 * mb
    rows.append(Row("peak_mem/dense_intermediate_bytes", float(dense_bytes),
                    f"(B, capacity+B) f32 at cap={cap_big}"))
    rows.append(Row("peak_mem/hier_intermediate_bytes", float(hier_bytes),
                    f"{n_tiles} tiles × tile_k={tile_k} candidates"))

    # ---- capacity the dense intermediate could not reasonably hold --------
    nh = max(n // 2, 2 * batch)
    hv, hts = dense_embedding_stream(2 * nh, 32, seed=7, rate=4.0)
    huge = _EngineDriver(EngineConfig(
        theta=theta, lam=lam, capacity=cap_huge, d=32, micro_batch=mb,
        max_pairs=max_pairs, tile_k=tile_k, block_q=mb,
        block_w=min(2048, cap_huge), chunk_d=32,
    ))
    huge.feed(hv[:nh], hts[:nh], batch)
    t_huge = _timed_feed(huge, hv[nh:], hts[nh:], batch)
    rows.append(Row("engine/hugecap/items_per_s", nh / t_huge,
                    f"cap={cap_huge}, dense equiv "
                    f"{4*mb*(cap_huge+mb)/1e6:.0f} MB/micro-batch"))
    rows.append(Row("engine/hugecap/pairs_dropped",
                    float(huge.engine.pairs_dropped)))
    return rows


def check(rows: List[Row]) -> List[str]:
    by = {r.name: r.value for r in rows}
    problems = []
    if by.get("engine/pair_sets_match") != 1.0:
        problems.append("hierarchical pair set differs from dense oracles")
    if by.get("engine/bigcap_pair_sets_match") != 1.0:
        problems.append("pair sets diverge at large capacity")
    if by.get("engine/bytes_reduction_x", 0.0) < 2.0:
        problems.append(
            "compacted emission does not materially cut host←device bytes "
            f"(reduction {by.get('engine/bytes_reduction_x'):.2f}×)"
        )
    if by.get("engine/pairs_dropped", 0.0) != 0.0:
        problems.append("emission overflowed on the benchmark stream")
    if by.get("obs/unified_snapshot") != 1.0:
        problems.append(
            "paper counters and engine telemetry incoherent in the unified "
            "registry snapshot"
        )
    if by.get("paper/entries_traversed", 0.0) <= 0.0 or \
            by.get("paper/full_sims_computed", 0.0) <= 0.0:
        problems.append("paper-counters bridge published empty counters")
    if by.get("engine/hugecap/pairs_dropped", 0.0) != 0.0:
        problems.append("emission overflowed at the huge capacity")
    small = by.get("engine/prune/smallcap_skip_frac", 0.0)
    big = by.get("engine/prune/bigcap_skip_frac", 0.0)
    if not 0.0 < big < 1.0:
        problems.append(f"strip gate vacuous at big capacity ({big})")
    if big < small - 0.02:
        problems.append(
            f"skip fraction not growing with capacity: {small:.3f} → {big:.3f}"
        )
    if not by.get("engine/smoke_mode") and by.get("engine/hier_speedup_x", 0.0) < 2.0:
        problems.append(
            "hierarchical compaction under 2× vs dense at capacity "
            f"{by.get('engine/capacity_big'):.0f} "
            f"({by.get('engine/hier_speedup_x'):.2f}×)"
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
        "benchmark": "engine_throughput",
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
