"""The sparse Tweets stream's data side: its law of rows, rows remade by
index, its stories, and its plain reference against a dense brute force."""

import json
import os

import numpy as np
import pytest

from bench import gen, reference
from bench.rows import sparse
from bench.run import ROOT, _sample

# the law of the paper's Tweets stream (arXiv:1601.04814, Table 1)
TWEETS = {"rows": "sparse", "dims": 2**20, "nnz_mean": 9.46, "nnz_max": 32,
          "nnz_sigma": 0.6, "zipf_s": 1.3, "stop_words": 128}
KEEP = 0.83
SEED = 2**31 + 23


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def _cfg(**kw):
    cfg = _load("configs", "trend-d384.json")
    del cfg["d"]
    cfg.update(TWEETS, **kw)
    return cfg


def _mix(traffic, **kw):
    mix = _load("traffic", traffic + ".json")
    mix.update(keep=KEEP, **kw)
    return mix


@pytest.fixture(scope="module")
def stream():
    """10^5 rows of the Tweets law, 2% of them near-duplicates."""
    plan = sparse.make_plan(_cfg(capacity=2**17), _mix("iso-sat"), SEED,
                            10**5)
    return plan, sparse.host_rows(plan, 0, plan.n, 1 << 14)


def test_rows_keep_the_tweets_law(stream):
    plan, r = stream
    assert abs(r.nnz.mean() / 9.46 - 1) < 0.03
    assert r.nnz.min() >= 1 and r.nnz.max() <= 32
    slot = np.arange(32)[None, :] < r.nnz[:, None]
    assert (r.ids[slot] < 2**20).all() and (r.ids[slot] >= 0).all()
    assert (r.ids[~slot] == 2**20).all() and (r.weights[~slot] == 0).all()
    # ascending and distinct within a row
    assert (np.diff(r.ids, axis=1)[slot[:, 1:]] > 0).all()
    assert (r.weights >= 0).all() and (r.weights[slot] > 0).all()
    norm = np.sqrt((r.weights.astype(np.float64) ** 2).sum(axis=1))
    np.testing.assert_allclose(norm, 1.0, atol=1e-6)
    # no stop word: the 128 most popular ranks never appear
    stop = (np.arange(128) * sparse.ID_MULT) % 2**20
    assert not np.isin(r.ids[slot], stop).any()


def test_row_remade_by_index_equals_row_made_in_sequence(stream):
    plan, r = stream
    idx = np.random.default_rng(5).choice(plan.n, 3000, replace=False)
    again = sparse.rows_at(plan, idx, 1 << 14)
    for a, b in zip(again, r):
        np.testing.assert_array_equal(a, b[idx])
    # and in another block size, from another start
    part = sparse.host_rows(plan, 12345, 12345 + 700, 256)
    for a, b in zip(part, r):
        np.testing.assert_array_equal(a, b[12345:12345 + 700])


def _cos(r, a, b):
    wa = dict(zip(r.ids[a, :r.nnz[a]].tolist(),
                  r.weights[a, :r.nnz[a]].astype(np.float64)))
    return sum(wa.get(t, 0.0) * w for t, w in zip(
        r.ids[b, :r.nnz[b]].tolist(), r.weights[b, :r.nnz[b]]))


def test_in_story_cosines_straddle_theta():
    plan = sparse.make_plan(_cfg(capacity=2**17), _mix("burst-sat"), SEED,
                            2**16)
    r = sparse.host_rows(plan, 0, plan.n, 1 << 14)
    rng = np.random.default_rng(3)
    stories = np.unique(plan.anchor[plan.anchored])
    cos = []
    for s in rng.choice(stories, min(400, stories.size), replace=False):
        posts = np.flatnonzero(plan.anchor == s)
        if posts.size >= 2:
            cos.append(_cos(r, *rng.choice(posts, 2, replace=False)))
    cos = np.asarray(cos)
    assert cos.size >= 100
    assert 0.3 < (cos >= 0.7).mean() < 0.7, np.quantile(cos, [.25, .5, .75])
    # posts of no story share little
    plain = rng.choice(np.flatnonzero(~plan.anchored), (300, 2))
    assert max(_cos(r, a, b) for a, b in plain) < 0.5


# stories within an eighth of the horizon; reposts up to a whole horizon
# back, dense enough to cross the reach of the decay
SMALL = {"burst-sat": {"story_sizes": [2, 64]},
         "iso-sat": {"anchored_share": 0.3}}


def _small(traffic="burst-sat"):
    """4,096 rows at 2^12 dims: 4 tenants at θ 0.7, a horizon of half the
    ring, and some arrivals refused."""
    cfg = _cfg(capacity=4096, dims=2**12, stop_words=8)
    mix = _mix(traffic, **SMALL[traffic])
    plan = sparse.make_plan(cfg, mix, SEED, 4096)
    admitted = np.random.default_rng(9).random(plan.n) > 0.05
    took = np.flatnonzero(admitted[3072:]) + 3072
    rows = _sample(np.random.default_rng(10), took, plan.anchored[took], 256)
    ref = sparse.reference_pairs(plan, cfg, rows, admitted, 512, 256)
    return cfg, plan, admitted, rows, ref


def _dense(plan, r):
    x = np.zeros((r.ids.shape[0], plan.dims + 1))
    np.put_along_axis(x, r.ids, r.weights.astype(np.float64), axis=1)
    return x[:, :plan.dims]


def _brute(cfg, plan, admitted, rows, vecs):
    """Decayed scores of each query row against every earlier admitted row
    of its tenant, densely, in float64: ``(len(rows), n)`` with -inf where
    no pair can be."""
    lam = gen.lam_of(cfg)
    j = np.arange(plan.n)
    s = vecs[rows] @ vecs.T * np.exp(-lam * (rows[:, None] - j[None, :]))
    can = ((plan.tenant[rows][:, None] == plan.tenant[None, :])
           & (j[None, :] < rows[:, None]) & admitted[None, :])
    return np.where(can, s, -np.inf)


@pytest.mark.parametrize("traffic", sorted(SMALL))
def test_reference_equals_dense_brute_force(traffic):
    cfg, plan, admitted, rows, ref = _small(traffic)
    vecs = _dense(plan, sparse.host_rows(plan, 0, plan.n, 512))
    s = _brute(cfg, plan, admitted, rows, vecs)
    th = np.asarray(cfg["thetas"])[plan.tenant[rows]]
    want = s >= th[:, None] - reference.WIDE
    assert (s >= th[:, None]).sum() > 50
    for q, g in enumerate(rows.tolist()):
        js = np.flatnonzero(want[q])
        assert sorted(ref[g]) == js.tolist()
        np.testing.assert_allclose([ref[g][j] for j in js.tolist()],
                                   s[q, js], rtol=0, atol=1e-6)


def test_candidates_without_verify_are_not_correct():
    """A bucket sketch ``s(x)_b = Σ_{h(t)=b} x_t`` bounds every dot product
    from above, so the pairs whose sketches reach θ are a superset of the
    true pairs.  Scored by sketch, without the exact verify, that answer
    misses nothing and is not correct."""
    cfg, plan, admitted, rows, ref = _small()
    r = sparse.host_rows(plan, 0, plan.n, 512)
    vecs = _dense(plan, r)
    buckets = 64
    sketch = np.zeros((plan.n, buckets))
    for b in range(buckets):
        sketch[:, b] = vecs[:, np.arange(plan.dims) % buckets == b].sum(1)
    exact = _brute(cfg, plan, admitted, rows, vecs)
    bound = _brute(cfg, plan, admitted, rows, sketch)
    assert (bound >= exact - 1e-12).all()
    th = np.asarray(cfg["thetas"])[plan.tenant[rows]]
    got = {int(g): {int(j): float(bound[q, j])
                    for j in np.flatnonzero(bound[q] >= th[q])}
           for q, g in enumerate(rows)}
    nums = reference.compare(got, ref, dict(zip(rows.tolist(), th)))
    limits = _load("limits", "trend-d384.json")
    assert nums["missing"] == 0 and nums["ref_pairs"] > 0
    failed = {k for k, v in nums.items()
              if not reference.within(v, limits[k])}
    assert {"extra", "score_gap"} <= failed, nums
