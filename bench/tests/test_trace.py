"""The reduction from trace events to device busy time, kernel times and
idle gaps."""

import pytest

from bench import trace


def _ev():
    # window 0..100 ns; a loop 10..50 around three ops; gaps 0..10,
    # 50..60 (submit), 80..95 (flush)
    return {
        "devices": {"/device:TPU:0": [
            ["while.5", 10, 40], ["sssj_candidates.3", 10, 20],
            ["fusion.1", 30, 5], ["sssj_strip_gate.2", 35, 15],
            ["fusion.2", 60, 20], ["fusion.3", 95, 30],
        ]},
        "host": [["bench.window", 0, 100], ["bench.submit", 45, 20],
                 ["bench.flush", 75, 30]],
    }


def test_reduce_unions_and_attributes_gaps():
    r = trace.reduce(_ev())
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10, 50) + [60, 80) + [95, 100) = 65 ns
    assert r["busy_s"] == pytest.approx(65e-9)
    assert r["op_s"]["sssj_candidates"] == pytest.approx(20e-9)
    assert r["op_s"]["sssj_strip_gate"] == pytest.approx(15e-9)
    assert r["op_s"]["fusion"] == pytest.approx(30e-9)    # fusion.3 clipped
    assert "while" not in r["op_s"]
    gaps = dict(r["idle_gaps"])
    assert gaps["no annotation"] == pytest.approx(10e-9)
    assert gaps["bench.submit"] == pytest.approx(10e-9)
    assert gaps["bench.flush"] == pytest.approx(15e-9)
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(20e-9)] or \
        r["device_ops"][0] == ["sssj_candidates.3", pytest.approx(20e-9)]


def test_op_names():
    assert trace.op_name("%sssj_candidates.18 = (s32[1]) custom-call(x)") \
        == "sssj_candidates.18"
    assert trace.base_name("sssj_candidates.18") == "sssj_candidates"
    assert trace.base_name("copy-start") == "copy-start"


def test_reduce_without_window_or_device_reads_nothing():
    ev = _ev()
    assert trace.reduce({"devices": ev["devices"], "host": []}) is None
    assert trace.reduce({"devices": {}, "host": ev["host"]}) is None


def test_reduce_of_a_chip_trace_gives_fixed_numbers():
    """The first 0.75 s of a traced ``dedup-d768.iso-sat`` window recorded
    on one TPU v5e, as :func:`trace.extract` keeps it."""
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "v5e_iso_sat_trace.json.gz")
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.75)
    assert r["busy_s"] == pytest.approx(0.702010486)
    assert r["op_s"]["sssj_candidates"] == pytest.approx(0.429532354)
    assert r["op_s"]["sssj_strip_gate"] == pytest.approx(0.002717146)
    assert dict(r["idle_gaps"]) == {
        "bench.flush": pytest.approx(0.029220251),
        "no annotation": pytest.approx(0.018769263)}
    assert r["device_ops"][0][0].startswith("sssj_candidates.")
