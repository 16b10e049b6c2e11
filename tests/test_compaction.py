"""Hierarchical compaction vs the dense oracle, property-based.

The contract under test (DESIGN.md §3):

  * whenever no drop counter fires, the hierarchical path (level-1 tile
    selection → level-2 segmented merge) reproduces the dense-oracle pair
    set **pair-for-pair and score-for-score**, across random shapes,
    thresholds, decay rates, and tile/budget capacities;
  * when a capacity does overflow — ``tile_k`` at level 1 or ``max_pairs``
    at level 2 — every lost pair is counted at its level, the counters sum
    exactly (``survivors + dropped_tile + dropped_budget == true pairs``),
    and the survivors are a prefix-ordered subset of the true pair set;
  * the per-row match mask is exact regardless of any overflow.

The three join implementations ("dense" jnp oracle, "scan" tile-scan, and
the "pallas" kernel in interpret mode) must emit identical candidate
buffers (scores up to kernel float accumulation order).
"""

import numpy as np
import jax.numpy as jnp
import pytest

try:  # optional dev dependency: richer search when present, fixed sweep not
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels.sssj_join import (  # noqa: E402
    PairCandidates,
    compact_pairs,
    merge_candidates,
    sssj_join_candidates,
    sssj_join_ref,
    sssj_join_tiles,
    tile_candidates,
)


def _stream(rng, Q, W, d, clustered):
    """Query/window batch with a controllable amount of near-duplicates."""
    q = rng.standard_normal((Q, d)).astype(np.float32)
    w = rng.standard_normal((W, d)).astype(np.float32)
    if clustered:
        n = min(Q, W) // 2
        w[:n] = q[:n] + 0.02 * rng.standard_normal((n, d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    w /= np.linalg.norm(w, axis=1, keepdims=True)
    tq = np.sort(rng.random(Q)).astype(np.float32) + 0.5
    tw = np.sort(rng.random(W)).astype(np.float32)
    uq = np.arange(1000, 1000 + Q, dtype=np.int32)
    uw = np.arange(W, dtype=np.int32)
    uw[::5] = -1                          # empty ring slots
    return map(jnp.asarray, (q, w, tq, tw, uq, uw))


def _dense_truth(scores, uq, uw):
    s = np.asarray(scores)
    qi, wi = np.nonzero(s)
    uq, uw = np.asarray(uq), np.asarray(uw)
    return {
        (int(uq[a]), int(uw[b])): float(s[a, b]) for a, b in zip(qi, wi)
    }


def _slots(x, tile_k):
    """A candidate buffer's first ``tile_k`` slots per segment, row-major
    over whatever slot layout its join wrote (``(K,)`` rows or the Pallas
    kernel's ``(n_rows, 128)`` slabs); slots past ``tile_k`` must be inert."""
    x = np.asarray(x)
    flat = x.reshape(x.shape[0], -1)
    assert (flat[:, tile_k:] == (-1 if x.dtype.kind == "i" else 0)).all()
    return flat[:, :tile_k]


def _buffer_pairs(buf):
    n = int(buf.n_pairs)
    return {
        (int(a), int(b)): float(s)
        for a, b, s in zip(
            np.asarray(buf.uid_a)[:n],
            np.asarray(buf.uid_b)[:n],
            np.asarray(buf.score)[:n],
        )
    }


def _check_hierarchical_vs_oracle(
    seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs, clustered
):
    """Exactness when nothing drops; exact per-level accounting when it
    does — across shapes, parameters, and both overflow boundaries."""
    rng = np.random.default_rng(seed)
    B = 32
    Q, W = q_tiles * B, w_tiles * B
    if ragged:                       # exercise padding in both dimensions
        Q, W = Q - 7, W - 5
    q, w, tq, tw, uq, uw = _stream(rng, Q, W, 64, clustered)

    scores, _, _ = sssj_join_tiles(
        q, w, tq, tw, uq, uw,
        theta=theta, lam=lam, block_q=B, block_w=B, chunk_d=32,
    )
    truth = _dense_truth(scores, uq, uw)

    jc = sssj_join_candidates(
        q, w, tq, tw, uq, uw,
        theta=theta, lam=lam, tile_k=tile_k, block_q=B, block_w=B,
        chunk_d=32, impl="scan" if seed % 2 else "dense",
    )
    buf = merge_candidates(jc.cands, max_pairs=max_pairs)
    got = _buffer_pairs(buf)
    n_budget, n_tile = int(buf.n_dropped), int(buf.n_dropped_tile)

    # drop counters always sum exactly — nothing is lost silently
    assert len(got) + n_budget + n_tile == len(truth)
    assert int(np.asarray(jc.cands.emitted).sum()) == len(truth)
    # survivors are true pairs with true scores
    assert got.keys() <= truth.keys()
    for k in got:
        assert abs(got[k] - truth[k]) < 1e-6
    if n_budget == 0 and n_tile == 0:
        # lossless run ⇒ pair-for-pair, score-for-score equality
        assert got.keys() == truth.keys()
        # and agreement with the PR-1 dense global-top-k oracle
        dense_buf = compact_pairs(scores, uq, uw, max_pairs=max_pairs)
        if int(dense_buf.n_dropped) == 0:
            assert got == pytest.approx(_buffer_pairs(dense_buf))
    # the match mask is exact regardless of overflow
    want_mask = (np.asarray(scores) > 0).any(axis=1)
    np.testing.assert_array_equal(np.asarray(jc.row_mask), want_mask)
    # buffer tail is inert
    n = int(buf.n_pairs)
    assert (np.asarray(buf.uid_a)[n:] == -1).all()
    assert (np.asarray(buf.score)[n:] == 0.0).all()


# Fixed sweep: every (overflow × shape-raggedness × impl) regime appears at
# least once, so tier-1 retains full contract coverage without hypothesis.
_SWEEP = [
    # seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs, clustered
    (0, 1, 1, False, 0.3, 0.2, 1024, 4096, True),    # lossless
    (1, 2, 3, True, 0.6, 0.02, 1024, 4096, True),    # lossless, ragged
    (2, 1, 2, False, 0.3, 0.2, 4, 4096, True),       # tile_k overflow
    (3, 2, 2, True, 0.3, 0.2, 1024, 8, True),        # max_pairs overflow
    (4, 1, 4, True, 0.3, 0.02, 4, 8, True),          # both levels overflow
    (5, 3, 2, False, 0.9, 1.0, 16, 64, False),       # sparse / mostly dead
    (6, 1, 1, True, 0.6, 0.2, 1, 1, True),           # capacity-1 boundary
]


@pytest.mark.parametrize(
    "seed,q_tiles,w_tiles,ragged,theta,lam,tile_k,max_pairs,clustered", _SWEEP
)
def test_hierarchical_matches_dense_oracle_sweep(
    seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs, clustered
):
    _check_hierarchical_vs_oracle(
        seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs,
        clustered,
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        q_tiles=st.integers(1, 3),
        w_tiles=st.integers(1, 4),
        ragged=st.booleans(),
        theta=st.sampled_from([0.3, 0.6, 0.9]),
        lam=st.sampled_from([0.02, 0.2, 1.0]),
        tile_k=st.sampled_from([1, 4, 16, 64, 1024]),
        max_pairs=st.sampled_from([1, 8, 64, 4096]),
        clustered=st.booleans(),
    )
    def test_hierarchical_matches_dense_oracle_property(
        seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs,
        clustered,
    ):
        _check_hierarchical_vs_oracle(
            seed, q_tiles, w_tiles, ragged, theta, lam, tile_k, max_pairs,
            clustered,
        )


@pytest.mark.parametrize("seed,tile_k,theta", [
    (0, 3, 0.4), (1, 16, 0.8), (2, 1024, 0.4),
])
def test_kernel_candidates_match_jnp_mirrors(seed, tile_k, theta):
    """The Pallas level-1 select (interpret mode) emits buffers identical
    to both jnp mirrors: same indices, uids, counts; scores to kernel
    accumulation tolerance."""
    rng = np.random.default_rng(seed)
    q, w, tq, tw, uq, uw = _stream(rng, 64, 96, 64, clustered=True)
    kw = dict(theta=theta, lam=0.1, tile_k=tile_k, block_q=32, block_w=32,
              chunk_d=32)
    ref = sssj_join_candidates(q, w, tq, tw, uq, uw, impl="dense", **kw)
    for impl in ("scan", "pallas"):
        got = sssj_join_candidates(q, w, tq, tw, uq, uw, impl=impl, **kw)
        for name in ("uid_a", "uid_b"):
            np.testing.assert_array_equal(
                _slots(getattr(got.cands, name), tile_k),
                _slots(getattr(ref.cands, name), tile_k),
                err_msg=f"{impl}/{name}",
            )
        for name in ("kept", "emitted"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got.cands, name)),
                np.asarray(getattr(ref.cands, name)),
                err_msg=f"{impl}/{name}",
            )
        np.testing.assert_allclose(
            _slots(got.cands.score, tile_k), _slots(ref.cands.score, tile_k),
            atol=1e-5, err_msg=f"{impl}/score",
        )
        np.testing.assert_array_equal(
            np.asarray(got.row_mask), np.asarray(ref.row_mask)
        )


def test_tile_candidates_order_is_stream_order(rng):
    """Within a tile, survivors must be the *earliest* pairs in row-major
    (stream) order — the overflow contract's "keep the first" clause."""
    scores = np.zeros((4, 8), np.float32)
    hits = [(0, 3), (0, 6), (1, 1), (2, 0), (2, 7), (3, 4)]
    for i, (a, b) in enumerate(hits):
        scores[a, b] = 0.5 + 0.01 * i
    uq = jnp.arange(100, 104, dtype=jnp.int32)
    uw = jnp.arange(8, dtype=jnp.int32)
    cands, row_mask = tile_candidates(
        jnp.asarray(scores), uq, uw, block_q=4, block_w=8, tile_k=4
    )
    assert int(cands.emitted[0]) == 6 and int(cands.kept[0]) == 4
    kept = list(
        zip(np.asarray(cands.uid_a)[0, :4], np.asarray(cands.uid_b)[0, :4])
    )
    assert kept == [(100 + a, b) for a, b in hits[:4]]
    np.testing.assert_array_equal(
        np.asarray(row_mask), np.array([True, True, True, True])
    )
    # merge keeps segment-then-rank order and attributes the tile loss
    buf = merge_candidates(cands, max_pairs=3)
    assert int(buf.n_pairs) == 3
    assert int(buf.n_dropped) == 1 and int(buf.n_dropped_tile) == 2
    got = list(zip(np.asarray(buf.uid_a)[:3], np.asarray(buf.uid_b)[:3]))
    assert got == [(100 + a, b) for a, b in hits[:3]]


def test_scan_impl_exact_on_wrapped_ring(rng):
    """The cursor-anchored live-strip walk must stay exact when the ring
    has wrapped — the newest item sits mid-array and the live range spans
    the wrap boundary.  (The walk is derived from the max uid, so this is
    the case where ``dist`` actually wraps modulo n_strips.)"""
    d, W, Q = 64, 256, 32
    # ring layout: uids [200..391] written cyclically → newest at slot 103
    uw_np = np.roll(np.arange(200, 200 + W, dtype=np.int32), 104)
    tw_np = np.roll(np.linspace(0.0, 25.6, W).astype(np.float32), 104)
    w = rng.standard_normal((W, d)).astype(np.float32)
    q = w[np.roll(np.arange(W), -104)[-Q:]].copy()   # dup the newest items
    q += 0.01 * rng.standard_normal((Q, d)).astype(np.float32)
    w /= np.linalg.norm(w, axis=1, keepdims=True)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    tq = jnp.full((Q,), 25.7)
    uq = jnp.arange(1000, 1000 + Q, dtype=jnp.int32)
    kw = dict(theta=0.6, lam=0.5, tile_k=64, block_q=32, block_w=32,
              chunk_d=32)
    ref = sssj_join_candidates(
        jnp.asarray(q), jnp.asarray(w), tq, jnp.asarray(tw_np), uq,
        jnp.asarray(uw_np), impl="dense", **kw,
    )
    got = sssj_join_candidates(
        jnp.asarray(q), jnp.asarray(w), tq, jnp.asarray(tw_np), uq,
        jnp.asarray(uw_np), impl="scan", **kw,
    )
    assert int(np.asarray(ref.cands.emitted).sum()) > 0   # non-trivial case
    for name in ("uid_a", "uid_b", "kept", "emitted"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got.cands, name)),
            np.asarray(getattr(ref.cands, name)), err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(got.cands.score), np.asarray(ref.cands.score), atol=1e-5
    )
    # the walk only visited strips near the cursor: expired strips (far
    # behind slot 103 in ring age) report zero executed chunks
    assert int((np.asarray(got.iters)[0] > 0).sum()) < np.asarray(got.iters).shape[1]


@pytest.mark.parametrize("impl", ["dense", "scan", "pallas"])
def test_stream_lanes_without_per_row_params(impl, rng):
    """Uniform tenants pass stream lanes alone (theta_q/lam_q = None) —
    every impl must accept that and mask cross-stream pairs under the
    static (θ, λ).  Regression: the pallas call once appended None inputs
    for the missing per-row lanes."""
    Q, W, d = 32, 64, 64
    q, w, tq, tw, uq, uw = _stream(rng, Q, W, d, clustered=True)
    sq = jnp.asarray(rng.integers(0, 2, Q).astype(np.int32))
    sw = jnp.asarray(rng.integers(0, 2, W).astype(np.int32))
    kw = dict(theta=0.5, lam=0.1, tile_k=1024, block_q=32, block_w=32,
              chunk_d=32, sq=sq, sw=sw)
    got = sssj_join_candidates(q, w, tq, tw, uq, uw, impl=impl, **kw)
    scores = sssj_join_ref(
        q, w, tq[:, None], tw[:, None], uq[:, None], uw[:, None],
        theta=0.5, lam=0.1, sq=sq[:, None], sw=sw[:, None],
    )
    truth = _dense_truth(scores, uq, uw)
    pairs = _buffer_pairs(merge_candidates(got.cands, max_pairs=4096))
    assert pairs.keys() == truth.keys() and len(truth) > 0
    for k in pairs:
        assert abs(pairs[k] - truth[k]) < 1e-5


@pytest.mark.parametrize("impl", ["dense", "scan", "pallas"])
def test_multi_tenant_lanes_match_across_impls(impl, rng):
    """Stream-equality masking and per-row (θ, λ) must behave identically
    in all three level-1 implementations: candidates equal the dense
    oracle's, cross-stream pairs never appear, and each row obeys its own
    tenant's threshold."""
    Q, W, d = 64, 96, 64
    q, w, tq, tw, uq, uw = _stream(rng, Q, W, d, clustered=True)
    sq = jnp.asarray(rng.integers(0, 3, Q).astype(np.int32))
    sw = jnp.asarray(rng.integers(0, 3, W).astype(np.int32))
    thetas = np.array([0.3, 0.6, 0.9], np.float32)
    lams = np.array([0.2, 0.05, 1.0], np.float32)
    theta_q = jnp.asarray(thetas[np.asarray(sq)])
    lam_q = jnp.asarray(lams[np.asarray(sq)])
    kw = dict(theta=0.5, lam=0.1, tile_k=1024, block_q=32, block_w=32,
              chunk_d=32, sq=sq, sw=sw, theta_q=theta_q, lam_q=lam_q)
    got = sssj_join_candidates(q, w, tq, tw, uq, uw, impl=impl, **kw)
    # brute-force truth with per-row parameters and the stream mask
    sims = np.asarray(q) @ np.asarray(w).T
    dt = np.abs(np.asarray(tq)[:, None] - np.asarray(tw)[None, :])
    dec = sims * np.exp(-np.asarray(lam_q)[:, None] * dt)
    ok = (np.asarray(uw)[None, :] >= 0) & (
        np.asarray(uq)[:, None] > np.asarray(uw)[None, :]
    ) & (np.asarray(sq)[:, None] == np.asarray(sw)[None, :])
    emit = ok & (dec >= np.asarray(theta_q)[:, None])
    truth = {
        (int(np.asarray(uq)[a]), int(np.asarray(uw)[b])): float(dec[a, b])
        for a, b in zip(*np.nonzero(emit))
    }
    buf = merge_candidates(got.cands, max_pairs=4096)
    pairs = _buffer_pairs(buf)
    assert int(buf.n_dropped) == 0 and int(buf.n_dropped_tile) == 0
    assert pairs.keys() == truth.keys()
    for k in pairs:
        assert abs(pairs[k] - truth[k]) < 1e-5
    np.testing.assert_array_equal(np.asarray(got.row_mask), emit.any(axis=1))


@pytest.mark.parametrize("Q", [96, 90])   # aligned and ragged query counts
def test_scan_impl_skips_expired_strips(Q, rng):
    """The scan impl's strip-level time filter must fire for a window
    entirely outside the τ-horizon — including when Q is not a block
    multiple (regression: the bound once read zero-padded timestamps,
    which pinned tq_lo to 0 and kept every strip alive)."""
    d, W = 64, 384
    q = rng.standard_normal((Q, d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    w = rng.standard_normal((W, d)).astype(np.float32)
    w /= np.linalg.norm(w, axis=1, keepdims=True)
    tq = jnp.full((Q,), 1000.0)
    tw = jnp.asarray(np.linspace(0, 10, W).astype(np.float32))
    uq = jnp.arange(10_000, 10_000 + Q, dtype=jnp.int32)
    uw = jnp.arange(W, dtype=jnp.int32)
    jc = sssj_join_candidates(
        jnp.asarray(q), jnp.asarray(w), tq, tw, uq, uw,
        theta=0.5, lam=0.1, tile_k=64, block_q=32, block_w=32, chunk_d=32,
        impl="scan",
    )
    assert int((np.asarray(jc.iters) > 0).sum()) == 0   # no strip executed
    assert int(np.asarray(jc.cands.emitted).sum()) == 0
    assert not np.asarray(jc.row_mask).any()


def test_ref_path_matches_on_subblock_inputs(rng):
    """Sub-block inputs auto-route to the dense jnp oracle and still obey
    the full contract."""
    q, w, tq, tw, uq, uw = _stream(np.random.default_rng(5), 9, 13, 16, True)
    scores = sssj_join_ref(
        q, w, tq[:, None], tw[:, None], uq[:, None], uw[:, None],
        theta=0.4, lam=0.1,
    )
    jc = sssj_join_candidates(
        q, w, tq, tw, uq, uw, theta=0.4, lam=0.1, tile_k=16,
        block_q=32, block_w=32, chunk_d=32,
    )
    buf = merge_candidates(jc.cands, max_pairs=64)
    assert _buffer_pairs(buf) == pytest.approx(_dense_truth(scores, uq, uw))
    assert int(buf.n_dropped) == 0 and int(buf.n_dropped_tile) == 0


def _ragged_source(rng, n_seg, tile_k, slab, uid0):
    """Random level-1 candidates: each segment keeps a random prefix of
    ``tile_k`` slots (sometimes none); a full segment emitted more than it
    kept, and the last one is always full; slots past ``kept`` are inert.
    ``slab`` lays the slots out as the Pallas kernel writes them,
    ``(ceil(tile_k/128), 128)``; otherwise as ``(tile_k,)`` rows."""
    n_slots = -(-tile_k // 128) * 128 if slab else tile_k
    kept = rng.integers(0, tile_k + 1, n_seg).astype(np.int32)
    kept[rng.random(n_seg) < 0.25] = 0
    kept[rng.random(n_seg) < 0.2] = tile_k
    kept[-1] = tile_k                      # every source loses some pairs
    emitted = kept + np.where(
        kept == tile_k, rng.integers(1, 5, n_seg), 0
    ).astype(np.int32)
    live = np.arange(n_slots)[None, :] < kept[:, None]
    uids = uid0 + np.arange(n_seg * n_slots, dtype=np.int32)
    uid_a = np.where(live, uids.reshape(n_seg, n_slots), -1)
    uid_b = np.where(live, uid_a // 3, -1)
    score = np.where(live, rng.random((n_seg, n_slots)), 0.0)
    shape = (n_seg, n_slots // 128, 128) if slab else (n_seg, n_slots)
    return PairCandidates(
        uid_a=jnp.asarray(uid_a.astype(np.int32).reshape(shape)),
        uid_b=jnp.asarray(uid_b.astype(np.int32).reshape(shape)),
        score=jnp.asarray(score.astype(np.float32).reshape(shape)),
        kept=jnp.asarray(kept),
        emitted=jnp.asarray(emitted),
    )


def _concat_then_merge(sources, max_pairs):
    """The single-buffer semantics in numpy: flatten every segment's slots,
    concatenate the sources' segments, keep the earliest ``max_pairs``
    entries in (segment, slot) order."""
    ua, ub, sc, tile_loss = [], [], [], 0
    for c in sources:
        n_seg = c.kept.shape[0]
        flat = [np.asarray(x).reshape(n_seg, -1) for x in c[:3]]
        kept = np.minimum(np.asarray(c.kept), flat[0].shape[1])
        tile_loss += int((np.asarray(c.emitted) - kept).sum())
        for seg in range(n_seg):
            ua.extend(flat[0][seg, :kept[seg]])
            ub.extend(flat[1][seg, :kept[seg]])
            sc.extend(flat[2][seg, :kept[seg]])
    n_pairs = min(len(ua), max_pairs)
    return (np.asarray(ua[:n_pairs], np.int32),
            np.asarray(ub[:n_pairs], np.int32),
            np.asarray(sc[:n_pairs], np.float32),
            n_pairs, len(ua) - n_pairs, tile_loss)


# sources as (n_seg, tile_k, slab); ``cut`` places max_pairs this many
# entries into the last source (None: a budget that holds everything)
_MERGE_CASES = {
    "slab": ([(12, 256, True), (1, 256, True)], None),
    "flat": ([(9, 64, False), (1, 64, False)], None),
    "mixed": ([(10, 128, True), (2, 128, False), (3, 128, True)], None),
    "tile_k_not_lane_multiple": ([(8, 200, True), (1, 200, False)], None),
    "overflow_in_second": ([(6, 130, True), (4, 130, True)], 37),
    "single_bare_source": ([(7, 96, False)], None),
}


@pytest.mark.parametrize("case", sorted(_MERGE_CASES))
def test_multi_source_merge_equals_concat_then_merge(case):
    """Merging the sources where their joins wrote them equals merging
    their concatenation, pair for pair, with the same pair and drop
    counts — across slot layouts, source counts and a budget that runs out
    inside a later source."""
    import jax

    specs, cut = _MERGE_CASES[case]
    rng = np.random.default_rng(sorted(_MERGE_CASES).index(case))
    sources, uid0 = [], 0
    for n_seg, tile_k, slab in specs:
        sources.append(_ragged_source(rng, n_seg, tile_k, slab, uid0))
        uid0 += 1 << 20
    if cut is None:
        max_pairs = 4096
    else:
        before = sum(int(np.asarray(c.kept).sum()) for c in sources[:-1])
        assert int(np.asarray(sources[-1].kept).sum()) > cut
        max_pairs = before + cut
    arg = sources[0] if len(sources) == 1 else tuple(sources)
    buf = jax.jit(lambda s: merge_candidates(s, max_pairs=max_pairs))(arg)
    ua, ub, sc, n_pairs, n_dropped, tile_loss = _concat_then_merge(
        sources, max_pairs
    )
    assert int(buf.n_pairs) == n_pairs > 0
    assert int(buf.n_dropped) == n_dropped
    assert int(buf.n_dropped_tile) == tile_loss
    if cut is not None:
        assert n_dropped > 0
    np.testing.assert_array_equal(np.asarray(buf.uid_a)[:n_pairs], ua)
    np.testing.assert_array_equal(np.asarray(buf.uid_b)[:n_pairs], ub)
    np.testing.assert_array_equal(np.asarray(buf.score)[:n_pairs], sc)
    assert (np.asarray(buf.uid_a)[n_pairs:] == -1).all()
    assert (np.asarray(buf.uid_b)[n_pairs:] == -1).all()
    assert (np.asarray(buf.score)[n_pairs:] == 0.0).all()
