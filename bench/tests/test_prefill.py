"""The prefilled ring is the ring that streaming would have built."""

import copy

import numpy as np
import pytest

from bench import client, gen, prefill
from bench.run import load_cell, mesh_of
from bench.serve.dense import submit_args

from .tiny import TINY_CFG, on_devices


def _cell(name):
    cell = copy.deepcopy(load_cell(name))
    cell["cfg"].update(TINY_CFG)
    if "story_sizes" in cell["mix"]:
        cell["mix"]["story_sizes"] = [2, 32]
    return cell["cfg"], cell["mix"]


def _stream(svc, plan, lo, hi, block):
    """Submit and flush arrivals [lo, hi) span by span; the flush results."""
    pool = client.Pool.of(plan, gen.host_rows(plan, lo, hi, block), lo, hi,
                          submit_args)
    res = client.run_client(svc, pool, {"span": 1, "micro_batch": block},
                            {"kind": "backlog", "spans_queued": 1}, np.inf)
    return res.flushes + [svc.flush(final=True)]


@pytest.mark.parametrize("name,gate", [
    ("dedup-d768.iso-sat", True),
    ("dedup-d768.iso-sat", False),
    ("trend-d384.burst-sat", True),
    ("dedup-d768x4.iso-sat", True),
    ("dedup-d768x4.iso-sat", False),
])
def test_prefill_equals_streaming(name, gate, request):
    if load_cell(name)["workload"]["chips"] == 1:
        check_prefill(name, gate)
        return
    failure = request.getfixturevalue("sharded")[(name, gate)]
    assert failure is None, failure


@pytest.fixture(scope="module")
def sharded():
    """The cases on a mesh, checked together on four devices."""
    return on_devices(4, check_prefill, [("dedup-d768x4.iso-sat", True),
                                         ("dedup-d768x4.iso-sat", False)])


def check_prefill(name, gate):
    import jax

    cfg, mix = _cell(name)
    cap = cfg["capacity"]
    span_rows = cfg["span"] * cfg["micro_batch"]
    n0 = cap - span_rows
    plan = gen.make_plan(cfg, mix, seed=2**31 + 11, n=cap + cap // 2)
    mesh = mesh_of(cfg, jax.devices())
    shards = cfg.get("shards", 1)

    filled = prefill.build_service(cfg, gate=gate, mesh=mesh)
    # on a mesh too, each shard's ring is summarized in several parts
    prefill.install(filled, plan, n0, block=256 // shards)
    streamed = prefill.build_service(cfg, gate=gate, mesh=mesh)
    _stream(streamed, plan, 0, n0, span_rows)

    a, b = filled.runtime.state, streamed.runtime.state
    # arrival i on shard i mod P, in that shard's own ring and cursor
    assert np.asarray(a.cursor).shape == (() if shards == 1 else (shards,))
    np.testing.assert_array_equal(
        np.asarray(a.uids).reshape(shards, -1)[:, :n0 // shards],
        np.arange(n0).reshape(-1, shards).T)
    np.testing.assert_allclose(np.asarray(a.vecs), np.asarray(b.vecs),
                               atol=1e-6)
    for field in ("ts", "uids", "sids", "cursor", "overflow",
                  "lane_overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)
    assert (a.summary is None) == (b.summary is None) == (not gate)
    if gate:
        for field in ("tmin", "tmax", "umax"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.summary, field)),
                np.asarray(getattr(b.summary, field)), field)
        for field in ("vmax", "cnorm"):
            np.testing.assert_allclose(
                np.asarray(getattr(a.summary, field)),
                np.asarray(getattr(b.summary, field)), atol=1e-6)

    # the arrivals after the prefix, through both: the same pairs
    got = [_stream(svc, plan, n0, plan.n, span_rows)
           for svc in (filled, streamed)]
    n_pairs = 0
    for fa, fb in zip(*got):
        assert fa.keys() == fb.keys()
        for t in fa:
            pa = {(x, y): s for x, y, s in fa[t]}
            pb = {(x, y): s for x, y, s in fb[t]}
            assert pa.keys() == pb.keys()
            for k in pa:
                assert abs(pa[k] - pb[k]) < 1e-5
            n_pairs += len(pa)
    assert n_pairs > 0
    assert filled.stats()["pairs_dropped"] == 0
