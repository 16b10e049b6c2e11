"""Device-resident L2/prefix strip gate (DESIGN.md §13): maintenance,
admissibility, and engine integration.

Four contracts:

  * **maintenance invariant** — the summary carried incrementally through
    :func:`refresh_strip_summary` on every policy push equals a full
    :func:`summarize_strips` rebuild of the ring, under all three eviction
    policies, with ring wrap and a ragged (non-``block_w``-multiple)
    capacity;
  * **admissible pruning** — a gated join (scan and Pallas-interpret)
    emits pair-identical candidates to the ungated dense oracle, while
    actually skipping work (``iters`` strictly below the dense count);
  * **impl equivalence** — the Pallas gate variant computes the identical
    gate and stats to the jnp variant;
  * **engine integration** — gate-on vs gate-off engines drain identical
    pair sets; ``l2_gate=True`` on a dense-oracle config is rejected at
    construction; the four ``engine/prune/*`` metrics publish.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.engine.engine import EngineConfig, StreamEngine
from repro.engine.window import init_window, push_with_overflow
from repro.kernels.sssj_join import (
    init_strip_summary,
    refresh_strip_summary,
    sssj_join_candidates,
    strip_gate,
    summarize_strips,
)

D = 32
BW = 16
CHUNK = 16


def _unit(rng, n, d=D):
    v = rng.standard_normal((n, d)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _assert_summary_equal(got, want, ctx=""):
    np.testing.assert_allclose(
        np.asarray(got.vmax), np.asarray(want.vmax), atol=1e-6, err_msg=ctx
    )
    np.testing.assert_allclose(
        np.asarray(got.cnorm), np.asarray(want.cnorm), atol=1e-6, err_msg=ctx
    )
    assert np.array_equal(np.asarray(got.tmin), np.asarray(want.tmin)), ctx
    assert np.array_equal(np.asarray(got.tmax), np.asarray(want.tmax)), ctx
    assert np.array_equal(np.asarray(got.umax), np.asarray(want.umax)), ctx


@pytest.mark.parametrize(
    "eviction,quotas,cap",
    [
        ("oldest", None, 40),   # ragged: 40 = 2.5 strips of 16
        ("oldest", None, 64),
        ("dead", None, 40),
        ("quota", (24, 16), 40),
    ],
)
def test_refresh_matches_full_rebuild(eviction, quotas, cap):
    """Incremental per-write refresh == full summarize, through ring wrap."""
    rng = np.random.default_rng(11)
    n_lanes = len(quotas) if quotas else None
    state = init_window(
        cap, D, n_lanes=n_lanes, eviction=eviction,
        summary_block_w=BW, summary_chunk_d=CHUNK,
    )
    q = jnp.asarray(quotas, jnp.int32) if quotas else None
    uid = 0
    t = 0.0
    for step in range(12):  # 12 × 16 rows ≫ cap → several wraps
        b = 16
        v = _unit(rng, b)
        tq = np.float32(t) + 0.05 * np.arange(b, dtype=np.float32)
        uq = np.arange(uid, uid + b, dtype=np.int32)
        sq = (
            rng.integers(0, n_lanes, b).astype(np.int32)
            if n_lanes else None
        )
        n_valid = b if step % 3 else b - 5  # exercise padded tails too
        uq[n_valid:] = -1
        t += 1.0
        state = push_with_overflow(
            state, jnp.asarray(v), jnp.asarray(tq), jnp.asarray(uq),
            jnp.asarray(n_valid, jnp.int32), jnp.asarray(t, jnp.float32),
            tau=4.0, sq=None if sq is None else jnp.asarray(sq),
            eviction=eviction, quotas=q,
            summary_block_w=BW, summary_chunk_d=CHUNK,
        )
        uid += b
        want = summarize_strips(
            state.vecs, state.ts, state.uids, block_w=BW, chunk_d=CHUNK
        )
        _assert_summary_equal(
            state.summary, want, f"{eviction} cap={cap} step={step}"
        )


def test_refresh_requires_geometry():
    """A summary-carrying state must be pushed with the strip geometry —
    silently skipping the refresh would corrupt the gate."""
    state = init_window(32, D, summary_block_w=BW, summary_chunk_d=CHUNK)
    rng = np.random.default_rng(0)
    v = jnp.asarray(_unit(rng, 4))
    tq = jnp.arange(4, dtype=jnp.float32)
    uq = jnp.arange(4, dtype=jnp.int32)
    with pytest.raises(ValueError, match="summary_block_w"):
        push_with_overflow(
            state, v, tq, uq, jnp.asarray(4, jnp.int32),
            jnp.asarray(1.0, jnp.float32), tau=4.0,
        )


def _window_with_holes(rng, cap, t_hi):
    """A ring in mid-life shape: live rows, expired rows, empty slots.
    Slots carry decreasing timestamps (as ring strips written in stream
    order do), so older strips are genuinely beyond the decay horizon."""
    vecs = _unit(rng, cap)
    ts = (t_hi - 0.15 * np.arange(cap) - rng.random(cap)).astype(np.float32)
    uids = np.arange(cap, dtype=np.int32)
    dead = rng.random(cap) < 0.3
    vecs[dead] = 0.0
    ts[dead] = 3.0e30
    uids[dead] = -1
    return jnp.asarray(vecs), jnp.asarray(ts), jnp.asarray(uids)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_gated_join_matches_dense_oracle(impl):
    """Pair-identical to the dense oracle AND strictly less work."""
    rng = np.random.default_rng(5)
    cap, b = 128, 32
    w, tw, uw = _window_with_holes(rng, cap, t_hi=100.0)
    q = jnp.asarray(_unit(rng, b))
    tq = jnp.asarray(100.0 + 0.1 * np.arange(b, dtype=np.float32))
    uq = jnp.asarray(np.arange(1000, 1000 + b, dtype=np.int32))
    summary = summarize_strips(w, tw, uw, block_w=BW, chunk_d=CHUNK)
    kw = dict(theta=0.4, lam=0.3, tile_k=64, block_q=16, block_w=BW,
              chunk_d=CHUNK, interpret=True)
    dense = sssj_join_candidates(q, w, tq, tw, uq, uw, impl="dense", **kw)
    gated = sssj_join_candidates(
        q, w, tq, tw, uq, uw, impl=impl, summary=summary, **kw
    )

    def slots(x):   # the pallas impl's slots are (n_rows, 128) slabs
        x = np.asarray(x)
        return x.reshape(x.shape[0], -1)[:, :kw["tile_k"]]

    for name in ("uid_a", "uid_b"):
        assert np.array_equal(
            slots(getattr(dense.cands, name)),
            slots(getattr(gated.cands, name)),
        ), name
    for name in ("kept", "emitted"):
        assert np.array_equal(
            np.asarray(getattr(dense.cands, name)),
            np.asarray(getattr(gated.cands, name)),
        ), name
    np.testing.assert_allclose(
        slots(dense.cands.score), slots(gated.cands.score), atol=1e-5,
    )
    assert np.array_equal(np.asarray(dense.row_mask),
                          np.asarray(gated.row_mask))
    # non-vacuity: λ=0.3 over a 6-time-unit spread must kill some strips
    assert int(jnp.sum(gated.iters)) < int(jnp.sum(dense.iters))
    stats = np.asarray(gated.gate_stats)
    assert stats[0] + stats[1] > 0 and stats[2] >= 1


def test_strip_gate_pallas_matches_jnp():
    rng = np.random.default_rng(9)
    cap, b = 96, 32
    w, tw, uw = _window_with_holes(rng, cap, t_hi=50.0)
    summary = summarize_strips(w, tw, uw, block_w=BW, chunk_d=CHUNK)
    qp = jnp.asarray(_unit(rng, b))
    args = dict(block_q=16, chunk_d=CHUNK,
                tq_lo=jnp.float32(50.0), tq_hi=jnp.float32(52.0),
                th_min=jnp.float32(0.4), lam_min=jnp.float32(0.2))
    g_j, s_j = strip_gate(qp, summary, impl="jnp", **args)
    g_p, s_p = strip_gate(qp, summary, impl="pallas", interpret=True, **args)
    assert np.array_equal(np.asarray(g_j), np.asarray(g_p))
    assert np.array_equal(np.asarray(s_j), np.asarray(s_p))


def test_l2_gate_config_validation():
    base = dict(theta=0.5, lam=0.1, capacity=64, d=D, micro_batch=8,
                block_q=8, block_w=8, chunk_d=16, tile_k=64, max_pairs=256)
    assert EngineConfig(**base, join_impl="scan").gate_enabled
    assert not EngineConfig(**base, join_impl="dense").gate_enabled
    assert not EngineConfig(**base, use_ref=True).gate_enabled
    assert not EngineConfig(**base, join_impl="scan",
                            emit_dense=True).gate_enabled
    assert not EngineConfig(**base, join_impl="scan",
                            l2_gate=False).gate_enabled
    for bad in (dict(join_impl="dense"), dict(emit_dense=True),
                dict(use_ref=True)):
        with pytest.raises(ValueError, match="l2_gate"):
            EngineConfig(**base, l2_gate=True, **bad)


def test_engine_gate_on_off_identical():
    from repro.data.synth import topic_drift_stream

    v, t = topic_drift_stream(768, D, n_topics=4, seg=96, seed=2, rate=4.0)
    base = dict(theta=0.5, lam=0.05, capacity=192, d=D, micro_batch=16,
                block_q=16, block_w=BW, chunk_d=CHUNK, tile_k=256,
                max_pairs=1 << 14, join_impl="scan")

    def drive(cfg):
        eng = StreamEngine(cfg)
        for i in range(0, len(v), 16):
            eng.push(v[i : i + 16], t[i : i + 16])
        ua, ub, sc = eng.drain_arrays()
        o = np.lexsort((ub, ua))
        return ua[o], ub[o], sc[o], eng

    on = drive(EngineConfig(**base))
    off = drive(EngineConfig(**base, l2_gate=False))
    assert len(on[0]) > 0
    assert np.array_equal(on[0], off[0])
    assert np.array_equal(on[1], off[1])
    np.testing.assert_allclose(on[2], off[2], atol=1e-5)
    m = on[3].metrics()
    assert m["engine/prune/tiles_total"] > 0
    skipped = (m["engine/prune/tiles_skipped_time"]
               + m["engine/prune/tiles_skipped_l2"])
    assert 0 < skipped < m["engine/prune/tiles_total"]
    assert m["engine/prune/strips_survived"] > 0
    # gate-off path never runs the gate
    m_off = off[3].metrics()
    assert m_off["engine/prune/tiles_skipped_time"] == 0
    assert m_off["engine/prune/tiles_skipped_l2"] == 0
