"""The per-layer readers of the program's spans and latency histogram, on
registry snapshots and traces made up for the purpose."""

import numpy as np
import pytest

from bench import run
from bench.metrics import Readings, read_metric

SPAN_KEYS = ("span/d2h/time_s", "span/emit/time_s", "span/group/time_s",
             "span/flush_wait/time_s")


def _readings(before, after):
    return Readings(cfg={"span": 4}, device_kind="test", trace=None,
                    before=before, after=after, batches=[], tenant=None)


def _snap(spans, seconds):
    out = {"runtime/spans_dispatched": spans}
    out.update({k: seconds for k in SPAN_KEYS})
    return out


def _read(name, r):
    return read_metric(run.ROOT, name, r)


@pytest.mark.parametrize("name, per_span_ms", [
    ("d2h_ms", 50.0), ("group_ms", 100.0), ("flush_wait_ms", 50.0),
])
def test_span_readers(name, per_span_ms):
    # 4 spans in the window, 0.2 s in each stage
    r = _readings(_snap(10, 1.0), _snap(14, 1.2))
    assert _read(name, r) == pytest.approx(per_span_ms)
    # nothing dispatched in the window: nothing to read
    assert _read(name, _readings(_snap(10, 1.0), _snap(10, 1.0))) is None
    # a program without the span: nothing to read, no error
    bare = {"runtime/spans_dispatched": 14, "span/emit/time_s": 3.0}
    assert _read(name, _readings({"runtime/spans_dispatched": 10},
                                 bare)) is None


def _hist(counts):
    bounds = [0.1, 0.2, 0.4, 0.8]
    return {"bounds": bounds, "counts": counts, "sum": 0.0,
            "count": sum(counts)}


def test_service_p50_reads_the_window_delta():
    key = "latency/admit_to_emit_s"
    before = {key: _hist([5, 0, 0, 0, 0])}
    # the window adds 4 rows in (0.2, 0.4] and 4 in (0.4, 0.8]: the
    # median is the top of (0.2, 0.4], whatever came before the window
    after = {key: _hist([5, 0, 4, 4, 0])}
    assert _read("service_p50_ms", _readings(before, after)) == \
        pytest.approx(400.0)
    # 2 rows in (0.1, 0.2]: interpolated to the bucket's middle
    after = {key: _hist([5, 2, 0, 0, 0])}
    assert _read("service_p50_ms", _readings(before, after)) == \
        pytest.approx(150.0)
    # above the last bound: the last bound
    after = {key: _hist([5, 0, 0, 0, 3])}
    assert _read("service_p50_ms", _readings(before, after)) == \
        pytest.approx(800.0)
    # no row returned in the window, or no histogram: nothing to read
    assert _read("service_p50_ms", _readings(before, before)) is None
    assert _read("service_p50_ms", _readings({}, {})) is None


@pytest.mark.parametrize("workload, present, absent", [
    ("trend-d384.burst-sat", {"d2h_ms", "group_ms"},
     {"flush_wait_ms", "service_p50_ms"}),
    ("dedup-d768.iso-rate", {"flush_wait_ms", "service_p50_ms"},
     {"d2h_ms", "group_ms"}),
])
def test_traced_tiny_run_reports_the_span_metrics(workload, present, absent):
    from bench.tests.tiny import cpu, tiny_cell

    cell = tiny_cell(run.load_cell, workload)
    result, checks = run.run(cell, 2**31 + 11, 1.0, True, chips=cpu)
    assert result["correct"], checks
    got = result["metrics"]
    assert present <= set(got) and not absent & set(got), got
    for name in present:
        assert got[name]["value"] > 0.0 and got[name]["unit"] == "ms", name


def _four_chip_trace():
    """A traced window of 0..100 ns on four chips: on chip ``i`` the
    kernel, an async all-gather in two halves, an all-reduce and two
    other ops, each collective ``i`` ns longer than on chip 0."""
    devices = {}
    for i in range(4):
        devices[f"/device:TPU:{i}"] = [
            ["sssj_candidates.3", 0, 20], ["all-gather-start.1", 20, 2 + i],
            ["all-gather-done.1", 30, 3 + i], ["all-reduce.7", 40, 5 + i],
            ["reduce.4", 50, 6], ["fusion.2", 60, 10],
        ]
    return {"devices": devices, "host": [["bench.window", 0, 100]]}


def test_collective_ms_reads_every_chip():
    from bench import trace

    r = _readings({"runtime/spans_dispatched": 0},
                  {"runtime/spans_dispatched": 2})
    r.trace = trace.reduce(_four_chip_trace())
    # per chip 10 + 3i ns, mean 14.5 ns, over 2 spans of 4 micro-batches
    assert _read("collective_ms", r) == pytest.approx(1e3 * 14.5e-9 / 8)
    # one chip, no collective: nothing to read
    one = _four_chip_trace()
    one["devices"] = {"/device:TPU:0": [["sssj_candidates.3", 0, 20],
                                        ["reduce.4", 50, 6]]}
    r.trace = trace.reduce(one)
    assert _read("collective_ms", r) is None


@pytest.mark.parametrize("shards", [None, 4])
def test_cand_roofline_takes_each_chips_share(shards):
    from bench import gen, roofline

    cfg = {"span": 4, "d": 768, "capacity": 4096, "horizon_share": 0.5,
           "thetas": [0.85, 0.9, 0.95]}
    if shards:
        cfg["shards"] = shards
    tenant = np.random.default_rng(0).integers(0, 3, 2048).astype(np.int32)
    batches = [(a, a + 64) for a in range(1024, 2048, 64)]
    r = Readings(cfg=cfg, device_kind="TPU v5 lite",
                 trace={"op_s": {"sssj_candidates": 1e-3}, "busy_s": 1.0,
                        "window_s": 1.0},
                 before={}, after={}, batches=batches, tenant=tenant)
    nbytes, flops = roofline.cand_work(tenant, gen.horizons(cfg), batches,
                                       768)
    t_min, _ = roofline.least_time(nbytes, flops,
                                   roofline.peaks("TPU v5 lite"))
    whole = 100.0 * float(t_min.sum()) / 1e-3
    got = _read("cand_roofline", r)
    if shards is None:
        assert got == whole
    else:
        assert got == pytest.approx(whole / 4, rel=1e-12)
