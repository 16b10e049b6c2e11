"""Unified observability layer (DESIGN.md §12): registry semantics,
histogram bucket exactness, exposition round-trips, span tracing (stage
counts, tiling of a flush, profiler annotations), registry↔legacy-stats
conformance across engine cells, per-tenant admission→return latency
attribution, and the pinned metrics schema."""

import glob
import json
import math
import os
import re
import time

import jax
import numpy as np
import pytest

from repro.core import Counters
from repro.engine import EngineConfig, StreamEngine, ShardedStreamEngine
from repro.data.synth import dense_embedding_stream
from repro.obs import (
    LATENCY_BOUNDS_S,
    Histogram,
    MetricsRegistry,
    PIPELINE_STAGES,
    Span,
    SpanTracer,
    histogram_percentile,
    log_buckets,
    merge_disjoint,
    publish_counters,
)
from repro.runtime import MultiTenantRuntime, ShardedFacade, TenantTable
from repro.serving import MultiTenantSSSJService

SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "metrics_schema.json")

K, D = 4, 32


def _cfg(**kw):
    base = dict(theta=0.8, lam=0.05, capacity=256, d=D, micro_batch=16,
                max_pairs=1024, block_q=16, block_w=16, chunk_d=32)
    base.update(kw)
    return EngineConfig(**base)


def _mt_runtime(shards: int = 1, **kw):
    table = TenantTable.uniform(K, 0.8, 0.05)
    engine = None
    if shards > 1:
        engine = ShardedFacade(jax.make_mesh((shards,), ("data",)))
    return MultiTenantRuntime(_cfg(**kw), table, span=2, engine=engine)


def _drive(rt, n_per=24, seed0=50):
    """Submit K interleaved streams, flush, and drain; returns per-tenant
    submitted counts."""
    streams = [
        dense_embedding_stream(n_per, D, seed=seed0 + k, rate=1.0)
        for k in range(K)
    ]
    events = sorted(
        (float(streams[k][1][i]), k, i)
        for k in range(K) for i in range(n_per)
    )
    for _, k, i in events:
        v, t = streams[k]
        rt.submit(k, v[i:i + 1], t[i:i + 1])
    rt.flush(final=True)
    rt.drain_by_tenant()
    return {k: n_per for k in range(K)}


# --------------------------------------------------------------------- #
# histogram bucket-boundary exactness (satellite: exposition primitives)
# --------------------------------------------------------------------- #
def test_log_buckets_exact_boundaries():
    b = log_buckets(1e-5, 64.0, 2.0)
    assert b[0] == 1e-5
    for lo, hi in zip(b, b[1:]):
        assert hi == lo * 2.0          # exact repeated multiplication
    assert b[-2] < 64.0 <= b[-1]
    # the latency vocabulary: the same range in ×2^¼ steps, each bucket
    # 19% wide
    q = log_buckets(1e-5, 64.0, 2.0 ** 0.25)
    assert LATENCY_BOUNDS_S == q
    assert 90 <= len(q) <= 93
    for lo, hi in zip(q, q[1:]):
        assert hi == lo * 2.0 ** 0.25


def test_log_buckets_rejects_degenerate():
    for lo, hi, g in [(0.0, 1.0, 2.0), (1.0, 1.0, 2.0), (1e-3, 1.0, 1.0)]:
        with pytest.raises(ValueError):
            log_buckets(lo, hi, g)


def test_histogram_le_semantics_at_boundaries():
    h = Histogram("t", bounds=(1.0, 2.0, 4.0))
    # a value exactly at a bound lands in the bucket it upper-bounds
    for v, bucket in [(0.5, 0), (1.0, 0), (1.0000001, 1), (2.0, 1),
                      (4.0, 2), (4.0001, 3)]:
        before = list(h.counts)
        h.observe(v)
        delta = [b - a for a, b in zip(before, h.counts)]
        assert delta == [int(i == bucket) for i in range(4)], v


def test_observe_many_matches_observe():
    vals = np.array([0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 9.0, 1.0])
    h1 = Histogram("a", bounds=(1.0, 2.0, 4.0))
    h2 = Histogram("b", bounds=(1.0, 2.0, 4.0))
    for v in vals:
        h1.observe(float(v))
    h2.observe_many(vals)
    assert h1.counts == h2.counts
    assert h1.count == h2.count == vals.size
    assert math.isclose(h1.sum, h2.sum)


def test_histogram_percentile_interpolation():
    h = Histogram("t", bounds=(1.0, 2.0, 4.0))
    assert h.percentile(0.5) == 0.0                    # empty
    h.observe_many(np.full(100, 1.5))                  # all in (1, 2]
    assert 1.0 < h.percentile(0.5) <= 2.0
    assert h.percentile(1.0) == 2.0                    # bucket upper edge
    h2 = Histogram("o", bounds=(1.0,))
    h2.observe(50.0)                                   # overflow bucket
    assert h2.percentile(0.99) == 1.0                  # last finite bound
    with pytest.raises(ValueError):
        h2.percentile(1.5)


def test_percentile_from_snapshot_dict():
    h = Histogram("t")
    h.observe_many(np.array([1e-4] * 90 + [1.0] * 10))
    snap = h.read()
    assert json.loads(json.dumps(snap)) == snap        # JSON round-trip
    assert math.isclose(
        histogram_percentile(snap, 0.5), h.percentile(0.5)
    )


# --------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------- #
def test_registry_get_or_create_and_kind_guard():
    reg = MetricsRegistry()
    c = reg.counter("x/total")
    c.inc(3)
    assert reg.counter("x/total") is c                 # idempotent getter
    with pytest.raises(TypeError):
        reg.gauge("x/total")                           # kind change = break
    reg.histogram("x/lat")
    with pytest.raises(ValueError):
        reg.histogram("x/lat", bounds=(1.0, 2.0))      # bounds change too


def test_merge_disjoint_raises_on_collision():
    assert merge_disjoint({"a": 1}, {"b": 2}) == {"a": 1, "b": 2}
    with pytest.raises(ValueError, match="pairs_emitted"):
        merge_disjoint({"pairs_emitted": 1}, {"pairs_emitted": 2})


def test_collector_republishes_at_snapshot_time():
    reg = MetricsRegistry()
    state = {"v": 1}
    reg.register_collector(lambda r: r.counter("s/v").set(state["v"]))
    assert reg.snapshot()["s/v"] == 1
    state["v"] = 7                      # externally-owned total moved
    assert reg.snapshot()["s/v"] == 7   # snapshot is coherent, not stale


def test_snapshot_json_and_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("engine/pairs_emitted").inc(5)
    reg.gauge("router/items_queued").set(3)
    reg.info("runtime/eviction").set("quota")
    reg.histogram("latency/admit_to_emit_s").observe_many(
        np.array([1e-4, 2e-3, 0.5])
    )
    snap = json.loads(reg.to_json())
    assert snap["engine/pairs_emitted"] == 5
    assert snap["latency/admit_to_emit_s"]["count"] == 3
    text = reg.prometheus_text()
    assert "# TYPE engine_pairs_emitted counter" in text
    assert "engine_pairs_emitted 5" in text.splitlines()
    assert 'runtime_eviction{value="quota"} 1' in text
    # histogram series are cumulative and end at the +Inf bucket == count
    buckets = re.findall(
        r'latency_admit_to_emit_s_bucket\{le="([^"]+)"\} (\d+)', text
    )
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts) and buckets[-1][0] == "+Inf"
    assert counts[-1] == 3
    assert "latency_admit_to_emit_s_count 3" in text


def test_publish_counters_bridges_paper_vocabulary():
    reg = MetricsRegistry()
    c = Counters()
    publish_counters(reg, c)
    c.entries_traversed += 11
    c.full_sims_computed += 4
    c.peak_index_entries = 9
    snap = reg.snapshot()
    assert snap["paper/entries_traversed"] == 11
    assert snap["paper/full_sims_computed"] == 4
    assert snap["paper/peak_index_entries"] == 9
    sch = reg.schema()
    assert sch["paper/entries_traversed"] == "counter"
    assert sch["paper/peak_index_entries"] == "gauge"   # maxima are gauges


# --------------------------------------------------------------------- #
# span tracer
# --------------------------------------------------------------------- #
def test_span_tracer_records_stage_timings():
    reg = MetricsRegistry()
    tr = SpanTracer(reg)
    with tr.span("dispatch", 1):
        pass
    tr.record("d2h", 0.25)
    snap = reg.snapshot()
    assert snap["span/dispatch/calls"] == 1
    assert snap["span/dispatch/time_s"] >= 0.0
    assert snap["span/d2h/calls"] == 1
    assert math.isclose(snap["span/d2h/time_s"], 0.25)
    assert PIPELINE_STAGES == (
        "admit", "take", "coalesce", "h2d", "dispatch", "device_wait", "d2h",
        "flush_wait", "emit", "group",
    )
    # every stage is in the registry from the start, at zero
    assert snap["span/group/calls"] == 0
    # a bare Span (the copy thread's) times without touching a registry
    with Span("d2h", 2) as s:
        time.sleep(0.002)
    assert s.seconds >= 0.002
    assert reg.snapshot() == snap


# --------------------------------------------------------------------- #
# the spans of one service flush
# --------------------------------------------------------------------- #
CALLER_STAGES = ("take", "coalesce", "h2d", "dispatch", "flush_wait", "emit",
                 "group")
PER_DISPATCH = ("take", "coalesce", "h2d", "dispatch", "device_wait", "d2h")
FLUSH_MB, FLUSH_D, FLUSH_CAP, FLUSH_REQ = 32, 256, 8192, 8


class _Flusher:
    """A service whose window is full of live rows, fed requests of eight
    rows from K tenants in turn: a flush does join work over the whole
    window, so its time is mostly stages and not bookkeeping."""

    def __init__(self):
        self.svc = MultiTenantSSSJService(
            TenantTable.uniform(K, 0.8, 0.001), dim=FLUSH_D,
            capacity=FLUSH_CAP, micro_batch=FLUSH_MB, max_pairs=1024, span=2,
        )
        self.rng = np.random.default_rng(3)
        self.t = 0.0
        self.r = 0
        while self.svc.runtime.n_items < FLUSH_CAP:
            self.submit(4 * FLUSH_MB)
            self.svc.flush()

    def submit(self, n):
        for _ in range(n // FLUSH_REQ):
            self.svc.submit(
                self.r % K, self.rng.normal(size=(FLUSH_REQ, FLUSH_D)),
                self.t + 0.01 * np.arange(FLUSH_REQ),
            )
            self.t += 0.01 * FLUSH_REQ
            self.r += 1

    def flush(self):
        """Two spans' rows submitted and flushed: ``(before, after,
        wall_s)``."""
        self.submit(4 * FLUSH_MB)
        before = self.svc.snapshot()
        t0 = time.perf_counter()
        self.svc.flush()
        wall = time.perf_counter() - t0
        return before, self.svc.snapshot(), wall


@pytest.fixture(scope="module")
def flusher():
    return _Flusher()


def _delta(before, after, key):
    return after[key] - before[key]


def test_flush_records_each_stage(flusher):
    n_submits = flusher.r
    admits = flusher.svc.snapshot()["span/admit/calls"]
    before, after, _ = flusher.flush()
    assert after["span/admit/calls"] - admits == flusher.r - n_submits
    spans = _delta(before, after, "runtime/spans_dispatched")
    assert spans == 2
    for stage in PER_DISPATCH:
        assert _delta(before, after, f"span/{stage}/calls") == spans, stage
    for stage in ("flush_wait", "emit", "group"):
        assert _delta(before, after, f"span/{stage}/calls") == 1, stage
    for stage in PIPELINE_STAGES:
        assert _delta(before, after, f"span/{stage}/time_s") >= 0.0, stage


def test_flush_spans_tile_the_flush(flusher):
    # the remainder is bookkeeping between stages (the router's take, the
    # latency observation): a few milliseconds here when other processes
    # share the cores, against tens of milliseconds of join work per flush
    covered = walls = 0.0
    for _ in range(3):
        before, after, wall = flusher.flush()
        c = sum(_delta(before, after, f"span/{s}/time_s")
                for s in CALLER_STAGES)
        assert c <= wall
        covered += c
        walls += wall
    assert covered >= 0.9 * walls, (covered, walls)


def test_latency_observed_per_returned_row(flusher):
    before, after, _ = flusher.flush()
    rows = _delta(before, after, "router/items_dispatched")
    assert rows == 4 * FLUSH_MB
    key = "latency/admit_to_emit_s"
    assert after[key]["bounds"] == list(LATENCY_BOUNDS_S)
    counts = np.subtract(after[key]["counts"], before[key]["counts"])
    assert counts.sum() == rows
    per_tenant = sum(
        after[f"tenant/{k}/latency_s"]["count"]
        - before[f"tenant/{k}/latency_s"]["count"] for k in range(K)
    )
    assert per_tenant == rows
    # every row waited at least the flush's wait on the copy thread: no
    # observation lies in a bucket whose upper bound is below it
    wait = _delta(before, after, "span/flush_wait/time_s")
    assert wait > 0.0
    low = np.asarray(LATENCY_BOUNDS_S) < wait
    assert counts[:low.size][low].sum() == 0


def test_profiler_trace_holds_stage_annotations(flusher, tmp_path):
    n0 = flusher.svc.runtime.spans_dispatched
    jax.profiler.start_trace(str(tmp_path))
    try:
        flusher.submit(4 * FLUSH_MB)
        flusher.svc.flush()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    seen = {}                      # stage -> [(line, dispatch)]
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines.append(line)
            for e in line.events:
                if e.name.startswith("sssj."):
                    seen.setdefault(e.name[5:], []).append(
                        (len(lines) - 1, dict(e.stats)["dispatch"]))
    ordinals = {n0 + 1, n0 + 2}
    for stage in PIPELINE_STAGES:
        assert stage in seen, stage
    # rows admitted while nothing is dispatched ride in the next dispatch
    assert {d for _, d in seen["admit"]} == {n0 + 1}
    for stage in PER_DISPATCH:
        assert sorted(d for _, d in seen[stage]) == sorted(ordinals), stage
    for stage in ("flush_wait", "emit", "group"):
        assert [d for _, d in seen[stage]] == [n0 + 2], stage
    # the copy thread's stages sit on a line of their own: not the
    # caller's, which holds the flush's other stages
    caller = {ln for s in CALLER_STAGES for ln, _ in seen[s]}
    drain = {ln for s in ("device_wait", "d2h") for ln, _ in seen[s]}
    assert len(caller) == 1 and len(drain) == 1 and caller != drain


# --------------------------------------------------------------------- #
# conformance: registry values == legacy stats() across engine cells
# --------------------------------------------------------------------- #
_ENGINE_KEYS = {
    "n_items": "engine/n_items",
    "chunks_executed": "engine/chunks_executed",
    "tiles_total": "engine/tiles_total",
    "pairs_emitted": "engine/pairs_emitted",
    "pairs_dropped": "engine/pairs_dropped",
    "pairs_dropped_budget": "engine/pairs_dropped_budget",
    "pairs_dropped_tile": "engine/pairs_dropped_tile",
    "window_overflow": "engine/window_overflow",
    "bytes_to_host": "engine/bytes_to_host",
    "bytes_dense_equiv": "engine/bytes_dense_equiv",
}


def _assert_registry_matches_stats(obj, stats):
    snap = obj.metrics()
    for legacy, namespaced in _ENGINE_KEYS.items():
        assert snap[namespaced] == stats[legacy], legacy


def test_single_engine_registry_equals_stats():
    eng = StreamEngine(_cfg())
    vecs, ts = dense_embedding_stream(96, D, seed=1, rate=2.0)
    for i in range(0, 96, 16):
        eng.push(vecs[i:i + 16], ts[i:i + 16])
    ua, _, _ = eng.drain_arrays()
    stats = eng.stats()
    _assert_registry_matches_stats(eng, stats)
    assert stats["n_items"] == 96
    assert stats["pairs_emitted"] == ua.size
    assert eng.metrics()["engine/pairs_emitted"] == ua.size


@pytest.mark.skipif(jax.device_count() < 2, reason="needs ≥ 2 devices")
def test_sharded_engine_registry_equals_stats():
    mesh = jax.make_mesh((2,), ("data",))
    eng = ShardedStreamEngine(_cfg(capacity=128), mesh)
    vecs, ts = dense_embedding_stream(64, D, seed=2, rate=2.0)
    for i in range(0, 64, 16):
        eng.push(vecs[i:i + 16], ts[i:i + 16])
    eng.drain_arrays()
    stats = eng.stats()
    _assert_registry_matches_stats(eng, stats)
    snap = eng.metrics()
    assert snap["engine/n_shards"] == stats["n_shards"] == 2
    for i in range(2):
        for f in ("live_slots", "pairs_emitted", "window_overflow"):
            assert snap[f"engine/shard/{i}/{f}"] == stats["shards"][f][i]
    assert sum(
        snap[f"engine/shard/{i}/pairs_emitted"] for i in range(2)
    ) >= stats["pairs_emitted"] - stats["pairs_dropped"]


@pytest.mark.parametrize("shards", [1, 2])
def test_runtime_registry_equals_stats(shards):
    if jax.device_count() < shards:
        pytest.skip(f"needs ≥ {shards} devices")
    rt = _mt_runtime(shards=shards, capacity=256 if shards == 1 else 128)
    _drive(rt)
    stats = rt.stats()
    snap = rt.metrics()
    _assert_registry_matches_stats(rt, stats)
    assert snap["runtime/n_tenants"] == stats["n_tenants"] == K
    assert snap["router/items_queued"] == stats["items_queued"] == 0
    assert snap["router/items_rejected"] == stats["items_rejected"]
    assert snap["runtime/spans_dispatched"] == stats["spans_dispatched"]
    assert snap["runtime/padded_rows"] == stats["padded_rows"]
    assert snap["runtime/eviction"] == stats["eviction"]
    assert math.isclose(
        stats["queue_delay_mean_s"],
        snap["router/queue_delay_sum_s"]
        / max(snap["router/items_dispatched"], 1),
    )
    for k in range(K):
        ts = rt.tenant_stats(k)
        assert snap[f"tenant/{k}/submitted"] == ts["submitted"]
        assert snap[f"tenant/{k}/pairs_drained"] == ts["pairs_drained"]
        assert snap[f"tenant/{k}/window_overflow"] == ts["window_overflow"]


# --------------------------------------------------------------------- #
# per-tenant admission→return latency attribution
# --------------------------------------------------------------------- #
def test_latency_histograms_attribute_every_row():
    rt = _mt_runtime()
    per_tenant = _drive(rt)
    snap = rt.metrics()
    total = snap["latency/admit_to_emit_s"]
    assert total["count"] == sum(per_tenant.values())
    assert total["sum"] > 0.0
    for k, n in per_tenant.items():
        h = snap[f"tenant/{k}/latency_s"]
        assert h["count"] == n, f"tenant {k}"
        assert histogram_percentile(h, 0.5) > 0.0
    # pipeline spans saw the dispatch path
    assert snap["span/admit/calls"] == sum(per_tenant.values())
    for stage in ("take", "coalesce", "h2d", "dispatch", "device_wait", "d2h"):
        assert snap[f"span/{stage}/calls"] == snap["runtime/spans_dispatched"]
    assert snap["span/flush_wait/calls"] == snap["span/emit/calls"] == 1


# --------------------------------------------------------------------- #
# serving facade: one snapshot, every layer
# --------------------------------------------------------------------- #
def test_service_snapshot_spans_all_layers():
    table = TenantTable.uniform(K, 0.8, 0.05)
    svc = MultiTenantSSSJService(
        table, dim=D, capacity=256, micro_batch=16, max_pairs=1024, span=2
    )
    rng = np.random.default_rng(0)
    for k in range(K):
        svc.submit(k, rng.normal(size=(8, D)), np.arange(8, dtype=float))
    svc.flush(final=True)
    snap = svc.snapshot()
    assert svc.registry is svc.runtime.registry
    for probe in ("engine/pairs_emitted", "router/items_admitted",
                  "runtime/spans_dispatched", "latency/admit_to_emit_s",
                  "tenant/0/latency_s", "span/dispatch/time_s"):
        assert probe in snap, probe
    assert snap["router/items_admitted"] == 8 * K
    assert snap["latency/admit_to_emit_s"]["count"] == 8 * K
    text = svc.prometheus_text()
    assert "engine_pairs_emitted" in text
    assert 'tenant_0_latency_s_bucket{le="+Inf"}' in text
    # legacy dict is a view over the same snapshot
    assert svc.stats()["n_items"] == snap["engine/n_items"]


# --------------------------------------------------------------------- #
# pinned schema: renaming or dropping a metric is a reviewed change
# --------------------------------------------------------------------- #
def normalize_schema(schema):
    """Collapse per-tenant / per-shard indices so the pinned schema is
    cardinality-independent."""
    out = {}
    for name, kind in schema.items():
        name = re.sub(r"tenant/\d+/", "tenant/<k>/", name)
        name = re.sub(r"engine/shard/\d+/", "engine/shard/<s>/", name)
        prev = out.setdefault(name, kind)
        assert prev == kind, f"{name}: {prev} vs {kind}"
    return out


def test_metrics_schema_matches_pinned():
    rt = _mt_runtime()
    _drive(rt)
    got = normalize_schema(rt.registry.schema())
    with open(SCHEMA_PATH) as f:
        want = json.load(f)
    missing = sorted(set(want) - set(got))
    assert not missing, (
        f"metrics dropped or renamed (update tests/metrics_schema.json "
        f"deliberately if intended): {missing}"
    )
    changed = {n: (want[n], got[n]) for n in want if got[n] != want[n]}
    assert not changed, f"metric kinds changed: {changed}"
    extra = sorted(set(got) - set(want))
    assert not extra, (
        f"new metrics not in the pinned schema (add them to "
        f"tests/metrics_schema.json): {extra}"
    )
