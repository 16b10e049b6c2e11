"""Beyond-paper: multi-tenant coalescing vs sequential per-tenant pushes.

The serving shape the ROADMAP targets — many small independent streams —
is hostile to the micro-batched engine: a tenant submitting 4 items at a
time pads every micro-batch 32× and pays a full window scan per push.
The runtime's router coalesces sub-batch arrivals across tenants into
full micro-batches (DESIGN.md §9), so per-arrival device cost tracks
output, not tenant count.

Two drivers over the identical interleaved traffic (T tenants, each
submitting ``per_round`` items per round, globally time-ordered), both on
the same stream-tagged multi-tenant engine:

  * **sequential** — ``flush(final=True)`` after every tenant's submit:
    each sub-batch rides alone in a padded micro-batch (the no-router
    baseline a naive per-tenant serving loop would produce);
  * **coalesced**  — submits queue up; one flush per round packs every
    tenant's items into full micro-batches.

Claims checked (ISSUE 3 acceptance):

  * identical per-tenant pair sets from both drivers (coalescing is
    semantically free);
  * coalesced ≥ 3× items/sec with 64 low-rate tenants (non-smoke);
  * padding waste telemetry: sequential ≫ coalesced.

Results are written machine-readably to ``BENCH_runtime.json``.

``--shards P`` additionally runs the coalesced driver with the runtime on
``ShardedFacade`` over P in-process shards (forcing
``--xla_force_host_platform_device_count`` before jax initializes — the
host-platform device-count trick) and enforces that the sharded per-tenant
pair sets are identical to the single-device ones (DESIGN.md §10).

``--eviction {oldest,dead,quota}`` selects the window write-slot policy
for the coalescing comparison (DESIGN.md §11; quota splits the ring
evenly), and ``--bursty`` runs the tenant-isolation scenario: one tenant
floods at ≫10× the others' rate into a deliberately undersized ring,
under **each** policy.  Claims enforced there: the slow tenants' live-item
overflow is *lower* under ``quota`` than under ``oldest``, and under
``quota`` the slow tenants' pair sets equal the brute-force truth
(pair-set check).  ``--bursty`` writes ``BENCH_eviction.json`` by default.

``--latency`` runs the open-loop arrival scenario (DESIGN.md §12):
wall-clock Poisson arrivals replayed in real time against deadline
flushes, with per-tenant admission→return latency percentiles read off
the metrics registry's log-bucket histograms.  Writes
``BENCH_latency.json`` (including the raw global histogram).

Standalone usage (CI smoke runs these):

    PYTHONPATH=src python -m benchmarks.runtime_throughput --smoke
    PYTHONPATH=src python -m benchmarks.runtime_throughput --smoke --shards 2
    PYTHONPATH=src python -m benchmarks.runtime_throughput --smoke --bursty
    PYTHONPATH=src python -m benchmarks.runtime_throughput --smoke --latency
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import require_devices, use_host_devices

# a CPU run shards over virtual host devices: the flag must land in the
# environment BEFORE jax initializes (which the repro imports below
# trigger), so sniff argv here rather than waiting for argparse (both
# --shards N and --shards=N; malformed values are left for argparse to
# reject properly).  A run on a chip uses its real devices.
def _sniff_shards(argv) -> int:
    for i, a in enumerate(argv):
        v = None
        if a == "--shards" and i + 1 < len(argv):
            v = argv[i + 1]
        elif a.startswith("--shards="):
            v = a.split("=", 1)[1]
        if v is not None:
            try:
                return int(v)
            except ValueError:
                return 1
    return 1


_n = _sniff_shards(sys.argv)
if _n > 1:
    use_host_devices(_n)

import numpy as np

from repro.data.synth import bursty_tenant_traffic, dense_embedding_stream
from repro.engine import EngineConfig, quota_partition
from repro.runtime import MultiTenantRuntime, ShardedFacade, TenantTable

from .common import Row

JSON_PATH = "BENCH_runtime.json"
BURSTY_JSON_PATH = "BENCH_eviction.json"
LATENCY_JSON_PATH = "BENCH_latency.json"


def _traffic(n_tenants, rounds, per_round, d, seed=0):
    """Interleaved multi-tenant traffic: per-tenant near-dup streams,
    globally time-ordered rounds."""
    streams = [
        dense_embedding_stream(rounds * per_round, d, seed=seed + k, rate=4.0)
        for k in range(n_tenants)
    ]
    # one global clock: round r spans [r, r+1); tenants jitter inside it
    rng = np.random.default_rng(seed + 999)
    order = [rng.permutation(n_tenants) for _ in range(rounds)]
    events = []
    for r in range(rounds):
        for j, k in enumerate(order[r]):
            lo = r * per_round
            ts = r + (j + rng.random(per_round) * 0.5) / (n_tenants + 1)
            events.append((k, streams[k][0][lo:lo + per_round], np.sort(ts)))
    return events


def _run(events, cfg, table, span, coalesce: bool, engine=None):
    rt = MultiTenantRuntime(cfg, table, span=span,
                            max_queue_per_tenant=1 << 20, engine=engine)
    t0 = time.perf_counter()
    last_round_start = 0
    for i, (k, vecs, ts) in enumerate(events):
        rt.submit(int(k), vecs, ts)
        if not coalesce:
            rt.flush(final=True)
        elif i - last_round_start + 1 >= table.n_tenants:
            rt.flush()                      # once per round: pack the queue
            last_round_start = i + 1
    rt.flush(final=True)
    per = rt.drain_by_tenant()
    elapsed = time.perf_counter() - t0
    pairs_per_tenant = [
        set(zip(per[k][0].tolist(), per[k][1].tolist()))
        for k in range(table.n_tenants)
    ]
    return rt, elapsed, pairs_per_tenant


def run(
    fast: bool = True, smoke: bool = False, shards: int = 1,
    eviction: str = "oldest",
) -> List[Row]:
    rows: List[Row] = []
    if smoke:
        n_tenants, rounds, per_round, d, mb, cap = 8, 4, 4, 32, 32, 512
    elif fast:
        n_tenants, rounds, per_round, d, mb, cap = 64, 8, 4, 64, 128, 4096
    else:
        n_tenants, rounds, per_round, d, mb, cap = 64, 24, 4, 64, 128, 8192
    span = 2 if smoke else 4
    theta, lam = 0.8, 0.5
    rows.append(Row("runtime/smoke_mode", float(smoke)))
    rows.append(Row("runtime/n_tenants", float(n_tenants)))
    rows.append(Row("runtime/items_per_submit", float(per_round)))
    rows.append(Row("runtime/shards", float(shards)))
    rows.append(Row("runtime/eviction_" + eviction, 1.0))

    table = TenantTable.uniform(n_tenants, theta, lam)
    quotas = (
        quota_partition(cap, [1.0] * n_tenants)
        if eviction == "quota" else None
    )
    cfg = EngineConfig(
        theta=theta, lam=lam, capacity=cap, d=d, micro_batch=mb,
        max_pairs=4096, tile_k=mb * mb, block_q=mb, block_w=mb,
        eviction=eviction, quotas=quotas,
    )
    n_items = n_tenants * rounds * per_round
    events = _traffic(n_tenants, rounds, per_round, d)

    # warmup both drivers (jit compile), then timed runs on fresh runtimes
    warm = events[: 2 * n_tenants]
    _run(warm, cfg, table, span, coalesce=True)
    _run(warm[: n_tenants], cfg, table, span, coalesce=False)

    rt_c, t_coal, pairs_c = _run(events, cfg, table, span, True)
    rt_s, t_seq, pairs_s = _run(events, cfg, table, span, False)

    match = pairs_c == pairs_s
    total_pairs = sum(len(p) for p in pairs_c)
    rows.append(Row("runtime/pair_sets_match", float(match),
                    f"{total_pairs} pairs, {n_tenants} tenants"))
    rows.append(Row("runtime/coalesced/items_per_s", n_items / t_coal,
                    f"{t_coal*1e3:.0f} ms for {n_items} items"))
    rows.append(Row("runtime/sequential/items_per_s", n_items / t_seq,
                    f"{t_seq*1e3:.0f} ms"))
    rows.append(Row("runtime/coalescing_speedup_x", t_seq / t_coal,
                    f"{n_tenants} tenants × {per_round}-item submits"))
    sc, ss = rt_c.stats(), rt_s.stats()
    rows.append(Row("runtime/coalesced/padding_waste", sc["padding_waste"],
                    f"{sc['padded_rows']} inert rows"))
    rows.append(Row("runtime/sequential/padding_waste", ss["padding_waste"],
                    f"{ss['padded_rows']} inert rows"))
    rows.append(Row("runtime/coalesced/spans", float(sc["spans_dispatched"])))
    rows.append(Row("runtime/sequential/spans", float(ss["spans_dispatched"])))
    rows.append(Row("runtime/pairs_dropped",
                    float(rt_c.pairs_dropped + rt_s.pairs_dropped)))
    rows.append(Row("runtime/window_overflow",
                    float(rt_c.overflow + rt_s.overflow)))
    rows.append(Row("runtime/queue_delay_mean_s", sc["queue_delay_mean_s"],
                    "coalesced admission → dispatch"))

    if shards > 1:
        # multi-tenant × sharded (DESIGN.md §10): same coalesced traffic,
        # runtime on ShardedFacade over P in-process shards — identical
        # per-tenant pair sets are a hard claim, throughput is informative
        import jax

        require_devices(shards, f"--shards {shards}")
        mesh = jax.make_mesh((shards,), ("data",))
        scfg = EngineConfig(
            theta=theta, lam=lam, capacity=cap // shards, d=d,
            micro_batch=mb, max_pairs=4096, tile_k=mb * mb, block_q=mb,
            block_w=mb, eviction=eviction,
            quotas=None if quotas is None
            else quota_partition(cap // shards, [1.0] * n_tenants),
        )
        _run(warm, scfg, table, span, True, engine=ShardedFacade(mesh))
        rt_sh, t_sh, pairs_sh = _run(
            events, scfg, table, span, True, engine=ShardedFacade(mesh)
        )
        rows.append(Row("runtime/sharded/pair_sets_match_single",
                        float(pairs_sh == pairs_c), f"{shards} shards"))
        rows.append(Row("runtime/sharded/items_per_s", n_items / t_sh,
                        f"{t_sh*1e3:.0f} ms, {shards} host shards"))
        rows.append(Row("runtime/sharded/pairs_dropped",
                        float(rt_sh.pairs_dropped)))
        rows.append(Row("runtime/sharded/window_overflow",
                        float(rt_sh.overflow)))
        ssh = rt_sh.stats()
        rows.append(Row("runtime/sharded/live_slots_max",
                        float(max(ssh["shards"]["live_slots"])),
                        "per-shard ring liveness"))
    return rows


def _slow_truth(per_tenant, theta, lam):
    """Per-slow-tenant brute-force pair sets in local index space."""
    out = []
    for vecs, ts in per_tenant[1:]:
        dec = (vecs @ vecs.T) * np.exp(-lam * np.abs(ts[:, None] - ts[None, :]))
        n = vecs.shape[0]
        out.append({
            (j, i) for i in range(n) for j in range(i) if dec[i, j] >= theta
        })
    return out


def run_bursty(smoke: bool = False, shards: int = 1) -> List[Row]:
    """Tenant-isolation scenario: the identical bursty traffic under every
    eviction policy; per-policy slow-tenant overflow and pair recall."""
    rows: List[Row] = []
    if smoke:
        n_slow, rounds, burst, d, mb, cap = 3, 8, 45, 32, 16, 32
    else:
        # per-round arrivals (burst + n_slow) must exceed capacity plus the
        # micro-batch ingest lag (cap + mb − 1) so oldest-first reliably
        # evicts the slow tenants' previous round
        n_slow, rounds, burst, d, mb, cap = 7, 20, 150, 64, 32, 96
    k_total = n_slow + 1
    th_slow, lam_slow = 0.8, 0.1
    table = TenantTable(
        [0.9] + [th_slow] * n_slow, [2.0] + [lam_slow] * n_slow
    )
    submits, per_tenant = bursty_tenant_traffic(n_slow, rounds, burst, d,
                                                seed=11)
    truth = _slow_truth(per_tenant, th_slow, lam_slow)
    n_true = sum(len(t) for t in truth)
    engine = None
    if shards > 1:
        import jax

        require_devices(shards, f"--shards {shards}")
        engine = ShardedFacade(jax.make_mesh((shards,), ("data",)))
    rows.append(Row("bursty/smoke_mode", float(smoke)))
    rows.append(Row("bursty/shards", float(shards)))
    rows.append(Row("bursty/n_slow_tenants", float(n_slow)))
    rows.append(Row("bursty/burst_per_round", float(burst)))
    rows.append(Row("bursty/true_slow_pairs", float(n_true)))

    for eviction in ("oldest", "dead", "quota"):
        quotas = (
            quota_partition(cap // shards, [1.0] * k_total)
            if eviction == "quota" else None
        )
        cfg = EngineConfig(
            theta=th_slow, lam=lam_slow, capacity=cap // shards, d=d,
            micro_batch=mb, max_pairs=8192, tile_k=mb * mb, block_q=mb,
            block_w=mb, join_impl="scan",
            eviction=eviction, quotas=quotas,
        )
        rt = MultiTenantRuntime(cfg, table, span=2,
                                max_queue_per_tenant=1 << 20, engine=engine)
        local_of = [dict() for _ in range(k_total)]
        counts = [0] * k_total
        t0 = time.perf_counter()
        for k, v, t in submits:
            for u in rt.submit(k, v, t).tolist():
                local_of[k][u] = counts[k]
                counts[k] += 1
        rt.flush(final=True)
        per = rt.drain_by_tenant()
        elapsed = time.perf_counter() - t0
        got = []
        for k in range(1, k_total):
            ua, ub = per[k][0], per[k][1]
            got.append({
                tuple(sorted((local_of[k][a], local_of[k][b])))
                for a, b in zip(ua.tolist(), ub.tolist())
            })
        s = rt.stats()
        by = s["window_overflow_by_tenant"]
        slow_ovf = sum(by[1:])
        recall = sum(len(g & t) for g, t in zip(got, truth)) / max(n_true, 1)
        exact = all(g == t for g, t in zip(got, truth))
        p = f"bursty/{eviction}"
        rows.append(Row(f"{p}/slow_overflow", float(slow_ovf),
                        f"bursty tenant lost {by[0]} of its own"))
        rows.append(Row(f"{p}/bursty_overflow", float(by[0])))
        rows.append(Row(f"{p}/overflow_by_tenant_sums", float(
            sum(by) == s["window_overflow"]
        )))
        rows.append(Row(f"{p}/slow_pair_recall", recall,
                        f"{n_true} true pairs over {n_slow} slow tenants"))
        rows.append(Row(f"{p}/slow_pairs_exact", float(exact)))
        rows.append(Row(f"{p}/items_per_s", s["n_items"] / elapsed,
                        f"{elapsed*1e3:.0f} ms for {s['n_items']} items"))
    return rows


def _hist_delta(final: dict, base: dict) -> dict:
    """Snapshot-form histogram delta (observations between two snapshots)."""
    counts = [b - a for a, b in zip(base["counts"], final["counts"])]
    return {
        "bounds": final["bounds"],
        "counts": counts,
        "sum": final["sum"] - base["sum"],
        "count": final["count"] - base["count"],
    }


def run_latency(smoke: bool = False):
    """Open-loop arrival scenario: admission→return latency histograms.

    Arrivals are scheduled on a wall clock (Poisson per tenant) and
    replayed in real time; the runtime flushes and drains on a fixed
    deadline (``flush(final=True)``, the latency-deadline case), so each
    item's latency = queueing until its deadline flush + device scan + D2H
    copy + the drain returning its rows.  Percentiles come from the
    registry's log-bucket histograms (``latency/admit_to_emit_s``,
    ``tenant/<k>/latency_s``) — the same metrics a scraper would see — with
    warmup observations subtracted via a baseline snapshot.

    Returns ``(rows, latency_histogram)`` — the delta histogram rides
    into ``BENCH_latency.json`` for offline analysis.
    """
    from repro.obs import PIPELINE_STAGES, histogram_percentile

    rows: List[Row] = []
    if smoke:
        n_tenants, horizon_s, rate, d, mb, cap = 4, 0.6, 400.0, 32, 16, 512
        deadline_s = 0.02
    else:
        n_tenants, horizon_s, rate, d, mb, cap = 16, 3.0, 1000.0, 64, 64, 4096
        deadline_s = 0.01
    theta, lam = 0.8, 0.5
    rng = np.random.default_rng(7)
    # per-tenant Poisson arrivals over the horizon, merged into one
    # globally time-ordered open-loop schedule
    events = []
    for k in range(n_tenants):
        vecs, _ = dense_embedding_stream(
            int(rate * horizon_s), d, seed=100 + k, rate=4.0
        )
        t, i = 0.0, 0
        while True:
            t += rng.exponential(n_tenants / rate)
            if t >= horizon_s or i >= vecs.shape[0]:
                break
            events.append((t, k, vecs[i]))
            i += 1
    events.sort(key=lambda e: e[0])

    table = TenantTable.uniform(n_tenants, theta, lam)
    cfg = EngineConfig(
        theta=theta, lam=lam, capacity=cap, d=d, micro_batch=mb,
        max_pairs=8192, tile_k=mb * mb, block_q=mb, block_w=mb,
    )
    rt = MultiTenantRuntime(cfg, table, span=2, max_queue_per_tenant=1 << 20)
    # warmup: one dispatch + drain compiles the (fixed-shape) step; the
    # baseline snapshot subtracts its latency observations afterwards
    warm = np.zeros((mb, d), np.float32)
    warm[:, 0] = 1.0
    rt.submit(0, warm, np.full(mb, -1e6))
    rt.flush(final=True)
    rt.drain_by_tenant()
    base = rt.registry.snapshot()

    t0 = time.perf_counter()
    next_deadline = deadline_s
    for t_sched, k, vec in events:
        now = time.perf_counter() - t0
        if t_sched > now:
            time.sleep(t_sched - now)
            now = t_sched
        while now >= next_deadline:
            rt.flush(final=True)
            rt.drain_by_tenant()
            next_deadline += deadline_s
            now = time.perf_counter() - t0
        rt.submit(int(k), vec[None, :], np.asarray([t_sched]))
    rt.flush(final=True)
    rt.drain_by_tenant()                 # pops records → observes latency
    snap = rt.registry.snapshot()

    hist = _hist_delta(snap["latency/admit_to_emit_s"],
                       base["latency/admit_to_emit_s"])
    rows.append(Row("latency/smoke_mode", float(smoke)))
    rows.append(Row("latency/n_tenants", float(n_tenants)))
    rows.append(Row("latency/deadline_ms", deadline_s * 1e3))
    rows.append(Row("latency/items", float(len(events)),
                    f"open loop over {horizon_s}s"))
    rows.append(Row("latency/observed", float(hist["count"])))
    rows.append(Row("latency/p50_ms",
                    histogram_percentile(hist, 0.50) * 1e3))
    rows.append(Row("latency/p99_ms",
                    histogram_percentile(hist, 0.99) * 1e3))
    rows.append(Row("latency/mean_ms",
                    hist["sum"] / max(hist["count"], 1) * 1e3))
    for k in range(n_tenants):
        th = _hist_delta(snap[f"tenant/{k}/latency_s"],
                         base[f"tenant/{k}/latency_s"])
        rows.append(Row(f"latency/tenant/{k}/observed", float(th["count"])))
        rows.append(Row(f"latency/tenant/{k}/p50_ms",
                        histogram_percentile(th, 0.50) * 1e3))
        rows.append(Row(f"latency/tenant/{k}/p99_ms",
                        histogram_percentile(th, 0.99) * 1e3))
    for stage in PIPELINE_STAGES:
        rows.append(Row(f"latency/span/{stage}/time_s",
                        snap[f"span/{stage}/time_s"],
                        f"{snap[f'span/{stage}/calls']} calls"))
    return rows, hist


def check_latency(rows: List[Row]) -> List[str]:
    by = {r.name: r.value for r in rows}
    problems = []
    n_items = by.get("latency/items", 0.0)
    if by.get("latency/observed") != n_items or n_items == 0.0:
        problems.append(
            f"latency histogram observed {by.get('latency/observed')} of "
            f"{n_items} admitted items"
        )
    p50, p99 = by.get("latency/p50_ms", 0.0), by.get("latency/p99_ms", 0.0)
    if not 0.0 < p50 <= p99:
        problems.append(f"degenerate percentiles (p50={p50}, p99={p99})")
    k = 0
    while f"latency/tenant/{k}/observed" in by:
        if by[f"latency/tenant/{k}/observed"] == 0.0 or \
                by[f"latency/tenant/{k}/p50_ms"] <= 0.0:
            problems.append(f"tenant {k}: latency histogram not populated")
        k += 1
    if k == 0:
        problems.append("no per-tenant latency histograms in output")
    return problems


def check_bursty(rows: List[Row]) -> List[str]:
    by = {r.name: r.value for r in rows}
    problems = []
    for ev in ("oldest", "dead", "quota"):
        if by.get(f"bursty/{ev}/overflow_by_tenant_sums") != 1.0:
            problems.append(
                f"{ev}: window_overflow_by_tenant does not sum to "
                f"window_overflow"
            )
    if by.get("bursty/quota/slow_overflow", 1.0) >= \
            by.get("bursty/oldest/slow_overflow", 0.0):
        problems.append(
            "quota eviction did not lower slow-tenant overflow vs oldest "
            f"({by.get('bursty/quota/slow_overflow')} vs "
            f"{by.get('bursty/oldest/slow_overflow')})"
        )
    if by.get("bursty/quota/slow_pairs_exact") != 1.0:
        problems.append(
            "quota: within-quota tenants did not emit their exact truth "
            f"(recall {by.get('bursty/quota/slow_pair_recall'):.3f})"
        )
    if by.get("bursty/oldest/slow_pair_recall", 1.0) >= 1.0:
        problems.append(
            "bursty scenario is vacuous: oldest-first lost no slow pairs"
        )
    return problems


def check(rows: List[Row]) -> List[str]:
    by = {r.name: r.value for r in rows}
    problems = []
    if by.get("runtime/pair_sets_match") != 1.0:
        problems.append("coalesced and sequential drivers emit different pairs")
    if by.get("runtime/pairs_dropped", 0.0) != 0.0:
        problems.append("emission overflowed on the benchmark traffic")
    if by.get("runtime/window_overflow", 0.0) != 0.0:
        problems.append("ring window overflowed on the benchmark traffic")
    waste_s = by.get("runtime/sequential/padding_waste", 0.0)
    waste_c = by.get("runtime/coalesced/padding_waste", 1.0)
    if waste_c >= waste_s:
        problems.append(
            f"coalescing did not cut padding waste "
            f"({waste_c:.2f} vs {waste_s:.2f})"
        )
    if not by.get("runtime/smoke_mode") and \
            by.get("runtime/coalescing_speedup_x", 0.0) < 3.0:
        problems.append(
            "coalescing under the claimed 3× vs sequential per-tenant "
            f"pushes ({by.get('runtime/coalescing_speedup_x'):.2f}×)"
        )
    if by.get("runtime/shards", 1.0) > 1.0:
        if by.get("runtime/sharded/pair_sets_match_single") != 1.0:
            problems.append(
                "sharded runtime emitted different per-tenant pairs than "
                "the single-device runtime"
            )
        if by.get("runtime/sharded/pairs_dropped", 0.0) != 0.0:
            problems.append("sharded emission overflowed on benchmark traffic")
        if by.get("runtime/sharded/window_overflow", 0.0) != 0.0:
            problems.append("sharded ring window overflowed on benchmark traffic")
    return problems


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI): exercises both drivers, relaxes "
                         "the wall-clock claim")
    ap.add_argument("--full", action="store_true", help="longer streams")
    ap.add_argument("--shards", type=int, default=1,
                    help="also run the coalesced driver on ShardedFacade "
                         "over this many in-process shards (forces host "
                         "platform devices before jax init)")
    ap.add_argument("--eviction", choices=["oldest", "dead", "quota"],
                    default="oldest",
                    help="window write-slot policy for the coalescing "
                         "comparison (DESIGN.md §11)")
    ap.add_argument("--bursty", action="store_true",
                    help="run the bursty-tenant isolation scenario instead: "
                         "identical flood traffic under each eviction "
                         "policy; enforces lower slow-tenant overflow and "
                         "exact slow pair sets under quota")
    ap.add_argument("--latency", action="store_true",
                    help="run the open-loop arrival scenario instead: "
                         "wall-clock Poisson arrivals, deadline flushes, "
                         "per-tenant admission→return latency histograms "
                         "from the metrics registry (DESIGN.md §12)")
    ap.add_argument("--json", default=None,
                    help=f"machine-readable output path (default {JSON_PATH}; "
                         f"{BURSTY_JSON_PATH} with --bursty, "
                         f"{LATENCY_JSON_PATH} with --latency)")
    args = ap.parse_args()
    if args.bursty and args.latency:
        ap.error("--bursty and --latency are mutually exclusive scenarios")
    json_path = args.json or (
        BURSTY_JSON_PATH if args.bursty
        else LATENCY_JSON_PATH if args.latency
        else JSON_PATH
    )
    t0 = time.time()
    latency_hist = None
    if args.bursty:
        benchmark = "runtime_throughput_bursty"
        rows = run_bursty(smoke=args.smoke, shards=args.shards)
        problems = check_bursty(rows)
    elif args.latency:
        benchmark = "runtime_latency"
        rows, latency_hist = run_latency(smoke=args.smoke)
        problems = check_latency(rows)
    else:
        benchmark = "runtime_throughput"
        rows = run(fast=not args.full, smoke=args.smoke, shards=args.shards,
                   eviction=args.eviction)
        problems = check(rows)
    print("name,value,extra")
    for r in rows:
        print(r.csv())
    payload = {
        "benchmark": benchmark,
        "mode": "smoke" if args.smoke else ("fast" if not args.full else "full"),
        "shards": args.shards,
        "eviction": "all" if args.bursty else args.eviction,
        "elapsed_s": round(time.time() - t0, 3),
        "rows": [dict(name=r.name, value=r.value, extra=r.extra) for r in rows],
        "problems": problems,
    }
    if latency_hist is not None:
        payload["latency_histogram"] = latency_hist
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {json_path} ({len(rows)} rows) in {payload['elapsed_s']}s")
    for p in problems:
        print(f"# CLAIM-FAIL {p}")
    sys.exit(1 if problems else 0)


if __name__ == "__main__":
    main()
