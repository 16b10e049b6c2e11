"""The plain reference and the comparison that decides ``correct``.

Semantics (the configuration's guarantee): arrival ``g`` of tenant ``k``
pairs with every earlier admitted arrival ``j`` of the same tenant whose
decayed score ``dot(x_g, x_j) · exp(-λ_k (t_g - t_j))`` reaches ``θ_k``;
every such pair is emitted once, with its score, and none is dropped.

The reference is a brute force over the whole stream the ring has seen —
prefill history included — for a sample of query rows: rows are remade
from the seed by :mod:`bench.gen` in blocks, scored against the queries in
one matmul per block at ``HIGHEST`` precision, and every entry within
``WIDE`` of θ is kept.  It uses nothing of the program.
"""

from __future__ import annotations

import collections
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

__all__ = ["BAND", "WIDE", "compare", "reference_pairs", "within"]

# A pair whose reference score lies within BAND of θ may be emitted or not:
# f32 rounding of two independent dot products decides it.  Its score is
# still compared when both sides have it.
BAND = 1e-5
# Reference entries kept around θ, so that an emitted pair just below θ in
# the reference still finds its reference score.
WIDE = 1e-2
_FEW_ROWS = 1 << 13   # block rows with any reference entry, first try
_MAX_HITS = 1 << 18   # reference entries kept per block


@partial(jax.jit, static_argnames=("d", "precision", "max_rows"))
def _block_hits(key_row, key_anchor, idx, anchor, noise, q, gq, kq, thq,
                lamq, tenant_w, admitted_w, *, d, precision, max_rows):
    w = gen.rows(key_row, key_anchor, idx, anchor, noise, d=d)
    if precision == "bf16":
        s = jnp.dot(w.astype(jnp.bfloat16), q.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
    else:
        s = jnp.dot(w, q.T, precision=jax.lax.Precision.HIGHEST)
    j = idx.astype(jnp.int32)
    dt = (gq[None, :] - j[:, None]).astype(jnp.float32)
    score = s * jnp.exp(-lamq[None, :] * dt)
    keep = ((dt > 0) & (tenant_w[:, None] == kq[None, :])
            & admitted_w[:, None] & (score >= thq[None, :] - WIDE))
    # few rows of a block hold any entry: gather those before the 2-D
    # nonzero, which costs in proportion to the elements it scans
    row_any = jnp.any(keep, axis=1)
    (ri,) = jnp.nonzero(row_any, size=max_rows, fill_value=0)
    sub = keep[ri] & (jnp.arange(max_rows) < jnp.sum(row_any))[:, None]
    r, c = jnp.nonzero(sub, size=_MAX_HITS, fill_value=0)
    rows = ri[r]
    return jnp.sum(row_any), jnp.sum(sub), j[rows], c, score[rows, c]


def reference_pairs(plan: gen.Plan, cfg: dict, rows: np.ndarray,
                    admitted: np.ndarray, block: int,
                    n_query: int, precision: str = "f32",
                    devices=None) -> dict:
    """``{g: {j: score}}`` for each query row ``g`` of ``rows``: every
    earlier admitted arrival of its tenant scoring at least θ - WIDE.

    ``admitted (m,)`` marks the arrivals ``[0, m)`` the service took;
    ``n_query`` fixes the padded query count (one compiled program).
    ``precision="bf16"`` is the control: the same brute force with
    bfloat16 inputs.  ``devices`` share the blocks of earlier arrivals
    round-robin, each scoring its blocks while the others score theirs
    (default: JAX's default device alone)."""
    rows = np.sort(np.asarray(rows, np.int64))
    if rows.size > n_query:
        raise ValueError(f"{rows.size} query rows, room for {n_query}")
    lam = np.float32(gen.lam_of(cfg))
    th = np.asarray(cfg["thetas"], np.float32)
    pad = np.full(n_query - rows.size, rows[0] if rows.size else 0)
    gq = np.concatenate([rows, pad])
    kq = plan.tenant[gq]
    q = gen.rows_at(plan, gq)
    qs = [q] if devices is None else [jax.device_put(q, d) for d in devices]
    thq = th[kq]
    lamq = np.full(n_query, lam, np.float32)
    m = admitted.size
    out = {int(g): {} for g in rows}
    few = min(_FEW_ROWS, block)

    def launch(lo, q_dev, max_rows):
        key_row, key_anchor, idx, anchor, noise = gen.block_args(plan, lo,
                                                                 block)
        hi = min(lo + block, m)
        tw = np.full(block, -1, np.int32)
        aw = np.zeros(block, bool)
        tw[:hi - lo] = plan.tenant[lo:hi]
        aw[:hi - lo] = admitted[lo:hi]
        return _block_hits(
            key_row, key_anchor, idx, anchor, noise, q_dev,
            gq.astype(np.int32), kq, thq, lamq, tw, aw, d=plan.d,
            precision=precision, max_rows=max_rows)

    def collect(lo, q_dev, hits):
        n_row, n_hit, j, c, s = hits
        if int(n_row) > few:
            n_row, n_hit, j, c, s = launch(lo, q_dev, block)
        n_hit = int(n_hit)
        if n_hit > _MAX_HITS:
            raise RuntimeError(f"{n_hit} reference entries in one block; "
                               f"room for {_MAX_HITS}")
        j, c, s = (np.asarray(x)[:n_hit] for x in (j, c, s))
        for jj, cc, ss in zip(j.tolist(), c.tolist(), s.tolist()):
            if cc < rows.size:
                out[int(gq[cc])][jj] = ss

    top = int(rows.max()) + 1 if rows.size else 0
    pending = collections.deque()
    for b, lo in enumerate(range(0, top, block)):
        q_dev = qs[b % len(qs)]
        pending.append((lo, q_dev, launch(lo, q_dev, few)))
        if len(pending) == len(qs):
            collect(*pending.popleft())
    while pending:
        collect(*pending.popleft())
    return out


def compare(got: dict, ref: dict, theta_of: dict) -> dict:
    """The numbers that decide ``correct``, from ``got`` (the service's
    pairs of the sampled rows, ``{g: {j: score}}``) and ``ref``.

    * ``missing``: reference pairs at least BAND above θ that the service
      did not emit;
    * ``extra``: emitted pairs whose reference score lies BAND or more
      below θ (or is not within WIDE of it);
    * ``score_gap``: the widest gap between an emitted score and the
      reference's;
    * ``ref_pairs``: reference pairs at least BAND above θ (the check has
      something to check only where this is above 0)."""
    missing = extra = ref_pairs = 0
    gap = 0.0
    for g, want in ref.items():
        th = float(theta_of[g])
        have = got.get(g, {})
        for j, s in want.items():
            if s >= th + BAND:
                ref_pairs += 1
                if j not in have:
                    missing += 1
        for j, s in have.items():
            r = want.get(j)
            if r is None or r < th - BAND:
                extra += 1
            if r is not None:
                gap = max(gap, abs(float(s) - r))
    return {"missing": missing, "extra": extra, "score_gap": gap,
            "ref_pairs": ref_pairs}


def within(value, limit: dict) -> bool:
    """Whether a compared number keeps its limit (``{"max": x}`` or
    ``{"min": x}``)."""
    return value >= limit["min"] if "min" in limit else value <= limit["max"]
