"""The stream a cell sends, made from ``--seed``: one general generator.

A traffic mix is a data file (``bench/traffic/<mix>.json``) of parameters;
this module reads it, together with the cell's configuration, and makes

* a host **plan** (:class:`Plan`): the stream as **requests** — each one
  ``submit`` call of ``request_items`` consecutive arrivals of one tenant,
  tenants drawn with Zipf(``tenant_zipf``) weights over the configuration's
  tenants (0: uniform), interleaved in arrival order — and for every
  arrival its *anchor*: ``-1`` for a plain isotropic unit vector, otherwise
  the id of the vector it lies near.  ``"anchor": "row"`` plants near-
  duplicates (the anchor is an earlier row of the same tenant, the
  SemDeDup setting); ``"anchor": "story"`` plants bursty stories (the
  anchor is a story centroid, the trend-detection setting).
  :func:`schedule` makes this part, which every row representation's
  plan shares (``bench/rows/``);
* the **vectors**, on the device, from the plan and the seed: row ``i`` is
  ``unit(g_i)`` with ``g_i ~ N(0, I_d)`` drawn from ``fold_in(key, i)``,
  and an anchored row is ``unit(unit(a) + noise · unit(g_i))`` where ``a``
  is the anchor's own draw.  A near-duplicate's anchor is its source row's
  ``g_src``, so the pair's cosine is set by ``noise`` alone.

Request sizes and story sizes come from the mix's ``schedule_seed``, the
same for every ``--seed``; the seed draws tenants, anchors and vectors.
Arrival index = admission order = timestamp.  Any row can be rebuilt from
``(seed, plan, index)``, which is what lets the prefill, the timed pool and
the reference draw the same rows independently.
"""

from __future__ import annotations

import dataclasses
import math
import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Plan", "block_args", "horizons", "host_rows", "lam_of", "make_plan",
    "request_starts", "rows", "rows_at", "schedule", "tenant_weights",
]


def lam_of(cfg: dict) -> float:
    """One λ for all tenants: θ_min's horizon is ``horizon_share`` of the
    ring, in arrivals."""
    return math.log(1.0 / min(cfg["thetas"])) / (
        cfg["horizon_share"] * cfg["capacity"]
    )


def horizons(cfg: dict) -> np.ndarray:
    """Each tenant's horizon ln(1/θ_k)/λ, in arrivals."""
    th = np.asarray(cfg["thetas"], np.float64)
    return np.log(1.0 / th) / lam_of(cfg)


@dataclasses.dataclass
class Plan:
    n: int                 # arrivals planned (prefill history + timed pool)
    tenant: np.ndarray     # (n,) i32
    starts: np.ndarray     # (r,) first arrival of each request
    anchor: np.ndarray     # (n,) i64; -1 plain
    key_row: np.ndarray    # (2,) u32 raw threefry key of the row draws
    key_anchor: np.ndarray  # (2,) u32 key of anchor draws ("row": key_row)
    noise: float
    d: int

    @property
    def anchored(self) -> np.ndarray:
        return self.anchor >= 0


def _keys(seed: int):
    ss = np.random.SeedSequence(int(seed))
    host, dev = ss.spawn(2)
    words = dev.generate_state(4, np.uint32)
    return np.random.default_rng(host), words[:2], words[2:]


def request_starts(mix: dict, n: int) -> np.ndarray:
    """Arrival index of each request's first item, over arrivals ``[0,
    n)``: sizes uniform over ``request_items`` (both ends included), from
    the mix's ``schedule_seed``, in blocks drawn apart so that a longer
    stream keeps a shorter one's requests."""
    lo, hi = mix["request_items"]
    per = 1 << 16
    sizes = np.concatenate([
        np.random.default_rng([mix["schedule_seed"], 3, b]).integers(
            lo, hi + 1, per)
        for b in range(n // (lo * per) + 1)])
    starts = np.concatenate([[0], np.cumsum(sizes)])
    return starts[starts < n]


def tenant_weights(k_n: int, s: float) -> np.ndarray:
    """Zipf(s) request shares over ``k_n`` tenants (s = 0: uniform)."""
    w = np.arange(1, k_n + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


def _row_anchors(rng, tenant, cfg, mix):
    """Near-duplicates: each anchored row copies an earlier row of its own
    tenant at a lag up to ``lag_horizons`` × θ_min's horizon, or, for a
    ``short_lag_share`` of them, up to ``short_lag_max`` arrivals (the same
    micro-batch or span); a source is copied once and is never a copy
    itself."""
    n = tenant.size
    lag_max = mix["lag_horizons"] * horizons(cfg).max()
    dst = np.flatnonzero(rng.random(n) < mix["anchored_share"])
    lag = rng.uniform(1.0, lag_max, dst.size)
    short = rng.random(dst.size) < mix["short_lag_share"]
    lag[short] = rng.uniform(1.0, mix["short_lag_max"], short.sum())
    src = np.full(dst.size, -1, np.int64)
    for k in range(len(cfg["thetas"])):
        # the lag in the tenant's own arrivals, so that a tenant's sources
        # spread over its rows whatever its share of the stream
        idx_k = np.flatnonzero(tenant == k)
        sel = tenant[dst] == k
        own = np.maximum(np.rint(lag[sel] * idx_k.size / n), 1).astype(int)
        pos = np.searchsorted(idx_k, dst[sel]) - own
        src[sel] = np.where(pos >= 0, idx_k[np.maximum(pos, 0)], -1)
    ok = (src >= 0) & (src < dst)
    dst, src = dst[ok], src[ok]
    is_dst = np.zeros(n, bool)
    is_dst[dst] = True
    ok = ~is_dst[src]
    dst, src = dst[ok], src[ok]
    _, first = np.unique(src, return_index=True)
    dst, src = dst[first], src[first]
    anchor = np.full(n, -1, np.int64)
    anchor[dst] = src
    return anchor


def _story_anchors(rng, tenant, cfg, mix, weights):
    """Stories start as a Poisson process over the stream; sizes are
    Zipf(α) over ``story_sizes``, drawn from ``schedule_seed`` so that every
    run has the same stories; each story belongs to one tenant, drawn with
    the tenants' weights, and its items arrive within
    ``story_span_horizons`` of θ_min's horizon, on that tenant's arrivals.
    Returns per-arrival story id (-1 background)."""
    n = tenant.size
    lo, hi = mix["story_sizes"]
    sizes = np.arange(lo, hi + 1)
    p = sizes.astype(np.float64) ** -mix["zipf_alpha"]
    p /= p.sum()
    span = mix["story_span_horizons"] * horizons(cfg).max()
    fixed = np.random.default_rng([mix["schedule_seed"], 2])
    n_story = fixed.poisson(mix["anchored_share"] * n / float(p @ sizes)
                            * (n + span) / n)
    size = fixed.choice(sizes, n_story, p=p)
    owner = rng.choice(weights.size, n_story, p=weights)
    start = rng.uniform(-span, n, n_story)
    sid = np.repeat(np.arange(n_story), size)
    t = np.repeat(start, size) + rng.uniform(0.0, span, sid.size)
    keep = (t >= 0) & (t < n)
    t, sid = t[keep], sid[keep]
    anchor = np.full(n, -1, np.int64)
    for k in range(weights.size):
        # a story's items take its tenant's arrivals in time order, merged
        # with that tenant's background in the tenant's own arrival count
        slots = np.flatnonzero(tenant == k)
        mine = owner[sid] == k
        n_bg = max(slots.size - int(mine.sum()), 0)
        t_all = np.concatenate([t[mine] * slots.size / n,
                                rng.uniform(0.0, slots.size, n_bg)])
        s_all = np.concatenate([sid[mine], np.full(n_bg, -1, np.int64)])
        anchor[slots] = s_all[np.argsort(t_all, kind="stable")[:slots.size]]
    return anchor


def schedule(cfg: dict, mix: dict, seed: int, n: int) -> dict:
    """What every row representation's plan shares: ``n``, each arrival's
    ``tenant``, the request ``starts``, each arrival's ``anchor`` and the
    raw keys of the row and anchor draws."""
    rng, key_row, key_anchor = _keys(seed)
    weights = tenant_weights(len(cfg["thetas"]), mix["tenant_zipf"])
    starts = request_starts(mix, n)
    req_tenant = rng.choice(weights.size, starts.size, p=weights)
    tenant = np.repeat(req_tenant, np.diff(np.append(starts, n)))
    if mix["anchor"] == "row":
        anchor = _row_anchors(rng, tenant, cfg, mix)
        key_anchor = key_row
    elif mix["anchor"] == "story":
        anchor = _story_anchors(rng, tenant, cfg, mix, weights)
    else:
        raise ValueError(f"unknown anchor kind {mix['anchor']!r}")
    return {"n": n, "tenant": tenant.astype(np.int32), "starts": starts,
            "anchor": anchor.astype(np.int64), "key_row": key_row,
            "key_anchor": key_anchor}


def make_plan(cfg: dict, mix: dict, seed: int, n: int) -> Plan:
    return Plan(**schedule(cfg, mix, seed, n), noise=float(mix["noise"]),
                d=int(cfg["d"]))


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def rows(key_row, key_anchor, idx, anchor, noise, *, d):
    """Traceable row maker: ``idx (m,)`` u32 arrival indices, ``anchor
    (m,)`` i32 anchor ids (-1 plain) → ``(m, d)`` f32 unit rows."""
    kr = jax.random.wrap_key_data(key_row)
    ka = jax.random.wrap_key_data(key_anchor)

    def draw(k, i):
        return jax.random.normal(jax.random.fold_in(k, i), (d,), jnp.float32)

    g = _unit(jax.vmap(lambda i: draw(kr, i))(idx))
    a = _unit(jax.vmap(lambda i: draw(ka, i))(
        jnp.maximum(anchor, 0).astype(jnp.uint32)))
    near = _unit(a + noise * g)
    return jnp.where((anchor >= 0)[:, None], near, g)


_rows_jit = jax.jit(rows, static_argnames=("d",))


def block_args(plan: Plan, lo: int, size: int):
    """Inputs of :func:`rows` for arrivals ``[lo, lo + size)``; indices past
    the plan are plain rows that callers drop.  A fixed ``size`` keeps one
    compiled program for every block."""
    idx = np.arange(lo, lo + size, dtype=np.int64)
    anchor = np.full(size, -1, np.int64)
    hi = min(lo + size, plan.n)
    anchor[:hi - lo] = plan.anchor[lo:hi]
    return (plan.key_row, plan.key_anchor, idx.astype(np.uint32),
            anchor.astype(np.int32), np.float32(plan.noise))


def rows_at(plan: Plan, idx: np.ndarray) -> jax.Array:
    """Rows at arrival indices ``idx`` (any order), on the device."""
    idx = np.asarray(idx, np.int64)
    return _rows_jit(plan.key_row, plan.key_anchor, idx.astype(np.uint32),
                     plan.anchor[idx].astype(np.int32),
                     np.float32(plan.noise), d=plan.d)


def host_rows(plan: Plan, lo: int, hi: int, block: int) -> np.ndarray:
    """Rows ``[lo, hi)`` on the host, made on the device in blocks."""
    out = np.empty((hi - lo, plan.d), np.float32)
    for a in range(lo, hi, block):
        b = min(a + block, hi)
        rows_ab = _rows_jit(*block_args(plan, a, block), d=plan.d)
        out[a - lo:b - lo] = np.asarray(rows_ab)[:b - a]
    return out
