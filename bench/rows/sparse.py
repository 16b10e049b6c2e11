"""Sparse TF-IDF rows, the paper's Tweets stream: the data side of the
``sparse`` representation.

Tweets (arXiv:1601.04814, Table 1): 18,266,589 tweets over 1,048,576
dimensions, 9.46 nonzeros per tweet, timestamps by publishing date.  A row
is a list of term ids below ``dims`` with nonnegative weights of unit l2
norm, held in three arrays (:class:`Rows`): ``ids (n, nnz_max)`` i32,
ascending and padded with ``dims``; ``weights (n, nnz_max)`` f32, padded
with 0; ``nnz (n,)`` i32.

The configuration gives the law of the rows: ``dims`` (a power of two),
``nnz_mean``, ``nnz_max``, ``nnz_sigma``, ``zipf_s`` and ``stop_words``;
the traffic mix gives ``keep``.  Tenants, requests and anchors come from
:func:`bench.gen.schedule`, as for dense rows; the timestamp is the
arrival index.  Row ``i`` is made from the draws of ``fold_in(key_row,
i)``:

* its length: a log-normal of log-spread ``nnz_sigma``, rounded to an
  integer in ``[1, nnz_max]``, with the log-mean that makes the mean length
  ``nnz_mean``;
* a term rank in each of its ``nnz_max`` slots, from a Zipf(``zipf_s``)
  popularity law over ``dims`` ranks whose ``stop_words`` most popular
  ranks are dropped as stop words; rank ``r`` is term id ``r * ID_MULT mod
  dims`` (a bijection), so that ids do not follow popularity;
* an anchored row (a story post; a near-duplicate) has its anchor's
  length and takes each slot's rank from its anchor's draw (the story's
  core; the source row's own terms) with probability ``keep``, and its own
  draw otherwise: it keeps each core term with probability ``keep`` and
  draws the rest fresh.

The first ``length`` slots are the row's terms.  Equal ids merge, their
count the term frequency; a term's weight is its frequency times its idf,
``-ln df`` with ``df = 1 - (1 - p)^nnz_mean`` the share of rows that hold a
term of popularity ``p`` under the law; the weights are scaled to unit
norm.  Every integer choice is a comparison of random bits against a table
made on the host, and every row is made by one compiled program
(:func:`make_block`, a fixed block of arbitrary indices): a row is the same
bits wherever it is made.  A service side that makes rows on the device
calls :func:`make_block` too.

The plain reference scores a sample of query rows against every earlier
admitted row of the same tenant, remade in blocks, exactly: an inverted
index over the queries' (tenant, term) postings meets each block row's
terms, and the products are summed per (row, query) in float64.  Rows too
old to reach ``θ - WIDE`` with a dot product of 1 are skipped.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from functools import partial
from typing import NamedTuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import gen  # noqa: E402
from bench.reference import WIDE  # noqa: E402

__all__ = ["ID_MULT", "Plan", "Rows", "block_rows", "host_rows", "law",
           "make_block", "make_plan", "reference_pairs", "rows_at"]

# odd, so that ``r * ID_MULT mod dims`` is a bijection of [0, dims)
ID_MULT = 0x9E3B5


class Rows(NamedTuple):
    ids: np.ndarray       # (n, nnz_max) i32, ascending, padded with dims
    weights: np.ndarray   # (n, nnz_max) f32, padded with 0
    nnz: np.ndarray       # (n,) i32


def _u32(cdf: np.ndarray) -> np.ndarray:
    """Thresholds of a cumulative distribution on 32 random bits."""
    return np.minimum(np.floor(cdf * 2.0**32), 2.0**32 - 1).astype(np.uint32)


def _lengths(mean: float, sigma: float, nnz_max: int) -> np.ndarray:
    """P(length = k), k = 1 .. nnz_max: a log-normal rounded and clipped
    into ``[1, nnz_max]``, its log-mean set by bisection so that the mean
    length is ``mean``."""
    cuts = np.log(np.arange(1, nnz_max) + 0.5)
    erf = np.vectorize(math.erf)

    def probs(mu):
        cdf = 0.5 * (1.0 + erf((cuts - mu) / (sigma * math.sqrt(2.0))))
        return np.diff(np.concatenate([[0.0], cdf, [1.0]]))

    lo, hi = -5.0, math.log(nnz_max) + 5.0
    k = np.arange(1, nnz_max + 1)
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if probs(mid) @ k < mean else (lo, mid)
    return probs(0.5 * (lo + hi))


def law(cfg: dict) -> dict:
    """The host tables of the row law: length and rank thresholds on 32
    random bits, and the idf of each rank."""
    dims, s, stop = cfg["dims"], cfg["zipf_s"], cfg["stop_words"]
    if dims & (dims - 1):
        raise ValueError(f"dims {dims} is not a power of two")
    p_len = _lengths(cfg["nnz_mean"], cfg["nnz_sigma"], cfg["nnz_max"])
    mass = np.arange(1, dims + 1, dtype=np.float64) ** -float(s)
    mass[:stop] = 0.0
    p = mass / mass.sum()
    df = -np.expm1(cfg["nnz_mean"] * np.log1p(-np.minimum(p, 1 - 1e-12)))
    idf = -np.log(np.where(p > 0, df, 1.0))
    return {"len_t": _u32(np.cumsum(p_len)[:-1]),
            "rank_t": _u32(np.cumsum(p)[:-1]),
            "idf": idf.astype(np.float32)}


@dataclasses.dataclass
class Plan:
    n: int
    tenant: np.ndarray       # (n,) i32
    starts: np.ndarray       # (r,) first arrival of each request
    anchor: np.ndarray       # (n,) i64; -1 plain
    key_row: np.ndarray      # (2,) u32 raw threefry key of the row draws
    key_anchor: np.ndarray   # (2,) u32 key of anchor draws ("row": key_row)
    keep: float
    dims: int
    nnz_max: int
    tables: dict             # law(cfg)

    @property
    def anchored(self) -> np.ndarray:
        return self.anchor >= 0


def make_plan(cfg: dict, mix: dict, seed: int, n: int) -> Plan:
    return Plan(**gen.schedule(cfg, mix, seed, n), keep=float(mix["keep"]),
                dims=int(cfg["dims"]), nnz_max=int(cfg["nnz_max"]),
                tables=law(cfg))


def block_rows(cfg: dict) -> int:
    """2^16 rows per call, and no more than the ring holds."""
    return min(1 << 16, cfg["capacity"])


@partial(jax.jit, static_argnames=("dims", "nnz_max"))
def _make(len_t, rank_t, idf, key_row, key_anchor, idx, anchor, keep_t, *,
          dims, nnz_max):
    kr = jax.random.wrap_key_data(key_row)
    ka = jax.random.wrap_key_data(key_anchor)

    def draw(key, i):
        kl, kt, kk = jax.random.split(jax.random.fold_in(key, i), 3)
        return (jax.random.bits(kl, (), jnp.uint32),
                jax.random.bits(kt, (nnz_max,), jnp.uint32),
                jax.random.bits(kk, (nnz_max,), jnp.uint32))

    own_len, own, coin = jax.vmap(lambda i: draw(kr, i))(idx)
    core_len, core, _ = jax.vmap(lambda i: draw(ka, i))(
        jnp.maximum(anchor, 0).astype(jnp.uint32))
    anchored = anchor >= 0
    take = anchored[:, None] & (coin < keep_t)
    rank = jnp.searchsorted(rank_t, jnp.where(take, core, own), side="right")
    length = 1 + jnp.searchsorted(
        len_t, jnp.where(anchored, core_len, own_len), side="right")
    slot = jnp.arange(nnz_max)[None, :] < length[:, None]
    term = (rank.astype(jnp.uint32) * jnp.uint32(ID_MULT)) & jnp.uint32(
        dims - 1)
    ids = jnp.where(slot, term.astype(jnp.int32), dims)
    w = jnp.where(slot, idf[rank], 0.0)
    ids, w = jax.lax.sort((ids, w), dimension=1, num_keys=1)
    # equal ids sit side by side: the first of each run keeps the count
    tf = jnp.sum(ids[:, :, None] == ids[:, None, :], axis=2)
    first = jnp.concatenate(
        [jnp.ones_like(ids[:, :1], bool), ids[:, 1:] != ids[:, :-1]], axis=1)
    first &= ids < dims
    ids = jnp.where(first, ids, dims)
    w = jnp.where(first, tf.astype(jnp.float32) * w, 0.0)
    ids, w = jax.lax.sort((ids, w), dimension=1, num_keys=1)
    w = w / jnp.sqrt(jnp.sum(w * w, axis=1, keepdims=True))
    return ids, w, jnp.sum(ids < dims, axis=1).astype(jnp.int32)


def make_block(plan: Plan, idx: np.ndarray, anchor: np.ndarray):
    """Rows at arrival indices ``idx (block,)`` with anchors ``anchor
    (block,)`` (-1 plain), on the default device: ``(ids, weights, nnz)``.
    One compiled program per block size."""
    t = plan.tables
    return _make(t["len_t"], t["rank_t"], t["idf"], plan.key_row,
                 plan.key_anchor, np.asarray(idx, np.uint32),
                 np.asarray(anchor, np.int32),
                 _u32(np.float64(plan.keep)), dims=plan.dims,
                 nnz_max=plan.nnz_max)


def rows_at(plan: Plan, idx: np.ndarray, block: int) -> Rows:
    """Rows at arrival indices ``idx`` (any order) on the host, made in
    blocks of ``block``."""
    idx = np.asarray(idx, np.int64)
    parts = []
    for a in range(0, idx.size, block):
        pick = np.zeros(block, np.int64)
        anchor = np.full(block, -1, np.int64)
        k = min(block, idx.size - a)
        pick[:k] = idx[a:a + k]
        anchor[:k] = plan.anchor[pick[:k]]
        out = make_block(plan, pick, anchor)
        parts.append([np.asarray(x)[:k] for x in out])
    return Rows(*(np.concatenate(x) for x in zip(*parts)))


def host_rows(plan: Plan, lo: int, hi: int, block: int) -> Rows:
    """Rows of arrivals ``[lo, hi)`` on the host, made in blocks."""
    return rows_at(plan, np.arange(lo, hi), block)


def _postings(plan: Plan, q: Rows, kq: np.ndarray):
    """The queries' postings sorted by (tenant, term): keys, query, weight."""
    valid = q.ids < plan.dims
    qi = np.nonzero(valid)[0]
    key = kq[qi].astype(np.int64) * plan.dims + q.ids[valid]
    order = np.argsort(key, kind="stable")
    return key[order], qi[order], q.weights[valid][order].astype(np.float64)


def reference_pairs(plan: Plan, cfg: dict, rows: np.ndarray,
                    admitted: np.ndarray, block: int, n_query: int,
                    devices=None) -> dict:
    """``{g: {j: score}}`` for each query row ``g`` of ``rows``: every
    earlier admitted arrival ``j`` of its tenant whose decayed score
    ``dot(x_g, x_j) · exp(-λ (g - j))``, in float64, is at least θ - WIDE.

    ``admitted (m,)`` marks the arrivals ``[0, m)`` the service took; at
    most ``n_query`` query rows.  Rows are made on JAX's default device;
    ``devices`` is taken for the dense reference's signature."""
    rows = np.sort(np.asarray(rows, np.int64))
    if rows.size > n_query:
        raise ValueError(f"{rows.size} query rows, room for {n_query}")
    out = {int(g): {} for g in rows}
    if not rows.size:
        return out
    lam = gen.lam_of(cfg)
    kq = plan.tenant[rows]
    th = np.asarray(cfg["thetas"], np.float64)[kq]
    pkey, pq, pw = _postings(plan, rows_at(plan, rows, block), kq)
    # a dot product of unit rows is at most 1 (and a rounding): rows older
    # than the widest horizon of θ - WIDE score below it for every query
    reach = math.log((1.0 + 1e-6) / (th.min() - WIDE)) / lam
    first = max(0, int(rows[0] - reach) - 1)
    top = int(rows[-1]) + 1
    for lo in range(first - first % block, top, block):
        hi = min(lo + block, top)
        b = host_rows(plan, lo, hi, block)
        ri, ci = np.nonzero(b.ids < plan.dims)
        j = lo + ri
        key = plan.tenant[j].astype(np.int64) * plan.dims + b.ids[ri, ci]
        left = np.searchsorted(pkey, key, "left")
        cnt = np.searchsorted(pkey, key, "right") - left
        cnt[~admitted[j]] = 0
        total = int(cnt.sum())
        if not total:
            continue
        src = np.repeat(np.arange(cnt.size), cnt)
        pos = left[src] + np.arange(total) - np.repeat(np.cumsum(cnt) - cnt,
                                                       cnt)
        jj, qq = j[src], pq[pos]
        prod = b.weights[ri, ci][src].astype(np.float64) * pw[pos]
        early = jj < rows[qq]
        pair, inv = np.unique(jj[early] * rows.size + qq[early],
                              return_inverse=True)
        dot = np.bincount(inv, weights=prod[early], minlength=pair.size)
        jj, qq = pair // rows.size, pair % rows.size
        score = dot * np.exp(-lam * (rows[qq] - jj))
        hit = score >= th[qq] - WIDE
        for g, jv, s in zip(rows[qq[hit]].tolist(), jj[hit].tolist(),
                            score[hit].tolist()):
            out[g][jv] = s
    return out


def main(argv=None) -> int:
    """Times the plain reference at a configuration's own scale, as a run
    would call it after its window."""
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", required=True, help="configuration JSON")
    ap.add_argument("--mix", required=True, help="traffic mix JSON")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--window", type=int, default=16384,
                    help="arrivals after the ring that the sample is from")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.run import _sample, enable_cache

    enable_cache()
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.mix) as f:
        mix = json.load(f)
    cap, block = cfg["capacity"], block_rows(cfg)
    t0 = time.perf_counter()
    plan = make_plan(cfg, mix, args.seed, cap + args.window)
    window = np.arange(cap, plan.n)
    sample = _sample(np.random.default_rng([args.seed, 2]), window,
                     plan.anchored[window], mix["sample_rows"])
    # a run has compiled the row maker for its pool before the reference
    rows_at(plan, sample[:1], block)
    t1 = time.perf_counter()
    ref = reference_pairs(plan, cfg, sample, np.ones(plan.n, bool), block,
                          mix["sample_rows"])
    t2 = time.perf_counter()
    th = np.asarray(cfg["thetas"])[plan.tenant]
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "seed": args.seed,
        "ring": cap, "sample": int(sample.size), "prepare_s": t1 - t0,
        "reference_s": t2 - t1,
        "pairs": sum(1 for g, want in ref.items() for s in want.values()
                     if s >= th[g]),
        "entries": sum(len(want) for want in ref.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
