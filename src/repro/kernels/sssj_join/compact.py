"""Hierarchical on-device pair compaction: tile candidates → packed pairs.

Level 2 of the two-level compaction pipeline (DESIGN.md §3).  Level 1 lives
with the join itself (the Pallas kernel, or its jnp mirrors in ops.py):
each (block_q, block_w) tile selects its own ≥θ entries into a fixed
``(tile_k,)`` candidate buffer plus a true-emit count — dead tiles (the
common case under time filtering) contribute a zero count and nothing
else.  This module merges those ragged per-segment buffers into the global
fixed-capacity :class:`PairBuffer` with a **segmented exclusive scan over
per-segment counts plus one gather** — there is no element-wise sort over
``Q·W`` anywhere, and the dense score matrix is never an input.

The same merge primitive is applied twice in the sharded engine: per-tile
buffers → per-shard buffer inside ``shard_map``, then per-shard buffers →
one global ``(max_pairs,)`` buffer after the gather, which is what makes
``max_pairs`` a *global* budget (DESIGN.md §5).

Drop accounting is per level and never silent:

  * ``PairCandidates.emitted - PairCandidates.kept`` — entries lost to the
    ``tile_k`` (or per-shard) candidate capacity;
  * ``PairBuffer.n_dropped`` — entries lost to the global ``max_pairs``
    budget at the merge;
  * ``PairBuffer.n_dropped_tile`` — upstream per-segment losses, carried so
    the lossless contract stays auditable end to end
    (``true pairs == n_pairs + n_dropped + n_dropped_tile``).

``compact_pairs`` (the PR-1 dense-matrix global-top-k compaction) is kept
verbatim as the test oracle for the ``emit_dense=True`` engine path.

Everything is shape-static and jit-safe, so join → select → merge → fetch
fuses into one XLA program and only ``O(max_pairs)`` bytes ever cross the
PCIe boundary.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "PairBuffer",
    "PairCandidates",
    "compact_pairs",
    "merge_candidates",
    "tile_candidates",
    "tile_emit_counts",
]


class PairCandidates(NamedTuple):
    """Ragged per-segment candidate buffers (level-1 output, a pytree).

    A *segment* is a kernel tile (or, at the sharded engine's second merge
    level, one device's compacted buffer).  Each segment holds its first
    ``kept ≤ K`` emitted pairs in stream (row-major) order; slots past
    ``kept`` are inert (``uid = -1``, ``score = 0``).  A segment's K slots
    are its trailing dims in row-major order: ``(K,)`` from the jnp joins,
    ``(n_rows, 128)`` slabs from the Pallas kernel.
    """

    uid_a: jax.Array    # (S, *slots) i32 — query-side uid, -1 in unused slots
    uid_b: jax.Array    # (S, *slots) i32 — window-side uid
    score: jax.Array    # (S, *slots) f32 — decayed similarity, 0 in unused
    kept: jax.Array     # (S,) i32 — valid entries per segment (≤ K slots)
    emitted: jax.Array  # (S,) i32 — true ≥θ count per segment (≥ kept)


class PairBuffer(NamedTuple):
    """Fixed-capacity compacted pair emission (a pytree of device arrays)."""

    uid_a: jax.Array     # (max_pairs,) i32 — query-side uid, -1 beyond n_pairs
    uid_b: jax.Array     # (max_pairs,) i32 — window-side uid, -1 beyond n_pairs
    score: jax.Array     # (max_pairs,) f32 — decayed similarity, 0 beyond n_pairs
    n_pairs: jax.Array   # () i32 — valid entries = min(total kept, max_pairs)
    n_dropped: jax.Array       # () i32 — entries lost to max_pairs (this merge)
    n_dropped_tile: jax.Array  # () i32 — entries lost upstream to per-segment
    #                                     (tile_k / per-shard) capacity

    @property
    def overflowed(self) -> jax.Array:
        return (self.n_dropped + self.n_dropped_tile) > 0


def _segmented_take(counts: jax.Array, out_cap: int):
    """Destination plan for packing ragged segments into a dense prefix.

    Given per-segment valid counts, returns ``(seg, rank, valid, total)``:
    the s-th surviving entry is slot ``rank[s]`` of segment ``seg[s]``,
    ``valid[s]`` marks ``s < min(total, out_cap)``, and ``total`` is the
    sum of counts.  Pure scan + binary search + gather — O(S + out_cap·log
    S), no sort, regardless of how many elements the segments describe.
    Ranks past the survivors point at slot 0 of the last segment.
    """
    counts = counts.astype(jnp.int32)
    n_seg = counts.shape[0]
    cum = jnp.cumsum(counts)                                   # inclusive
    total = cum[-1]
    s = jnp.arange(out_cap, dtype=jnp.int32)
    # segment holding global rank s = first seg whose inclusive cum > s
    seg = jnp.clip(
        jnp.searchsorted(cum, s, side="right"), 0, n_seg - 1
    ).astype(jnp.int32)
    base = cum[seg] - counts[seg]                              # exclusive scan
    valid = s < jnp.minimum(total, out_cap)
    return seg, jnp.where(valid, s - base, 0), valid, total


def _gather_slots(x: jax.Array, seg: jax.Array, rank: jax.Array) -> jax.Array:
    """``x[seg, slot rank]`` for a ``(S, *slots)`` buffer, the within-segment
    rank unravelled row-major over the trailing dims as they are: ``(K,)``
    from the jnp joins and the sharded merge, ``(n_rows, 128)`` slabs from
    the Pallas kernel — never reshaped, so a slab is read where the kernel
    wrote it."""
    idx = jnp.unravel_index(rank, x.shape[1:])
    return x[(seg,) + tuple(i.astype(jnp.int32) for i in idx)]


def merge_candidates(
    sources: PairCandidates | Sequence[PairCandidates], *, max_pairs: int
) -> PairBuffer:
    """Level-2 merge: ragged per-segment candidates → packed pair buffer.

    ``sources`` is one :class:`PairCandidates` or a sequence of them in
    segment order (the window join's tiles, then the self-join's); each
    keeps the slot layout its join wrote.  The plan runs over the
    concatenated per-segment counts; every output rank gathers from each
    source and keeps the one that holds it.  Survivors are the earliest
    pairs in (segment, within-segment) order; everything lost — here to
    ``max_pairs`` or upstream to per-segment capacity — is counted, never
    silent.
    """
    if isinstance(sources, PairCandidates):
        sources = (sources,)
    kept = jnp.concatenate([
        jnp.minimum(c.kept.astype(jnp.int32), math.prod(c.uid_a.shape[1:]))
        for c in sources
    ])
    emitted = jnp.concatenate([c.emitted for c in sources])
    seg, rank, valid, total = _segmented_take(kept, max_pairs)
    uid_a = jnp.full((max_pairs,), -1, jnp.int32)
    uid_b = jnp.full((max_pairs,), -1, jnp.int32)
    score = jnp.zeros((max_pairs,), jnp.float32)
    lo = 0
    for c in sources:
        n_seg = c.uid_a.shape[0]
        here = valid & (seg >= lo) & (seg < lo + n_seg)
        local = jnp.clip(seg - lo, 0, n_seg - 1)
        uid_a = jnp.where(here, _gather_slots(c.uid_a, local, rank), uid_a)
        uid_b = jnp.where(here, _gather_slots(c.uid_b, local, rank), uid_b)
        score = jnp.where(here, _gather_slots(c.score, local, rank), score)
        lo += n_seg
    n_pairs = jnp.minimum(total, max_pairs).astype(jnp.int32)
    return PairBuffer(
        uid_a=uid_a,
        uid_b=uid_b,
        score=score,
        n_pairs=n_pairs,
        n_dropped=(total - n_pairs).astype(jnp.int32),
        n_dropped_tile=jnp.sum(emitted - kept).astype(jnp.int32),
    )


# --------------------------------------------------------------------- #
# jnp mirror of the kernel's level-1 tile selection (ref path + oracle)
# --------------------------------------------------------------------- #
def tile_candidates(
    scores: jax.Array,   # (Q, W) f32 — 0 where no pair, ≥ θ where emitted
    uq: jax.Array,       # (Q,) i32 query uids
    uw: jax.Array,       # (W,) i32 window uids aligned with score columns
    *,
    block_q: int,
    block_w: int,
    tile_k: int,
) -> tuple[PairCandidates, jax.Array]:
    """Per-(block_q, block_w)-tile candidate selection from a dense matrix.

    The jnp mirror of the kernel's level-1 stage, bit-compatible with it
    (same row-major within-tile order, same tile order), used by the dense
    ref path and as the oracle in tests.  Returns ``(candidates, row_mask)``
    with ``row_mask (Q,)`` = "row has ≥1 emitted entry" (exact even when
    ``tile_k`` overflows — it is derived from counts, not survivors).
    Scan + binary search + gather per tile; no sort.
    """
    Q, W = scores.shape
    pq, pw = (-Q) % block_q, (-W) % block_w
    s = jnp.pad(scores, ((0, pq), (0, pw)))
    uqp = jnp.pad(uq.astype(jnp.int32), (0, pq), constant_values=-1)
    uwp = jnp.pad(uw.astype(jnp.int32), (0, pw), constant_values=-1)
    nq, nw = (Q + pq) // block_q, (W + pw) // block_w
    n = block_q * block_w
    # (nq, nw, block_q, block_w) tiles, flattened row-major within the tile
    tiles = s.reshape(nq, block_q, nw, block_w).transpose(0, 2, 1, 3)
    flat = tiles.reshape(nq * nw, n)
    mask = flat > 0.0
    cum = jnp.cumsum(mask.astype(jnp.int32), axis=1)           # (S, n)
    emitted = cum[:, -1]
    kept = jnp.minimum(emitted, tile_k)
    target = jnp.arange(tile_k, dtype=jnp.int32) + 1
    # src[s, k] = first in-tile flat position with inclusive count ≥ k+1
    src = jax.vmap(lambda c: jnp.searchsorted(c, target, side="left"))(cum)
    src = jnp.minimum(src, n - 1).astype(jnp.int32)
    valid = target[None, :] <= kept[:, None]
    sel_score = jnp.where(valid, jnp.take_along_axis(flat, src, axis=1), 0.0)
    # in-tile (i, j) → global (qi, wi) → uids
    ti = jnp.arange(nq * nw, dtype=jnp.int32)[:, None] // nw
    tj = jnp.arange(nq * nw, dtype=jnp.int32)[:, None] % nw
    qi = ti * block_q + src // block_w
    wi = tj * block_w + src % block_w
    uid_a = jnp.where(valid, uqp[qi], -1)
    uid_b = jnp.where(valid, uwp[wi], -1)
    cands = PairCandidates(
        uid_a=uid_a, uid_b=uid_b, score=sel_score.astype(jnp.float32),
        kept=kept, emitted=emitted,
    )
    row_mask = jnp.any(
        (s > 0.0).reshape(nq * block_q, nw * block_w), axis=1
    )[:Q]
    return cands, row_mask


# --------------------------------------------------------------------- #
# PR-1 dense-matrix compaction — retained as the emit_dense test oracle
# --------------------------------------------------------------------- #
def compact_pairs(
    scores: jax.Array,   # (Q, W) f32 — 0 where no pair, ≥ θ where emitted
    uq: jax.Array,       # (Q,) i32 query uids
    uw: jax.Array,       # (W,) i32 window uids aligned with score columns
    *,
    max_pairs: int,
) -> PairBuffer:
    """Dense-oracle compaction: one stable ``lax.top_k`` over the whole
    emit mask (ties break toward the lower index, i.e. stream order).

    This is the path the hierarchical pipeline replaced — it materializes
    the dense matrix and sorts ``Q·W`` elements — kept only behind
    ``emit_dense=True`` so tests can assert the two paths agree
    pair-for-pair whenever no drop counter fires.
    """
    Q, W = scores.shape
    mask = scores > 0.0
    counts = jnp.sum(mask, axis=1, dtype=jnp.int32)            # (Q,)
    total = jnp.sum(counts)
    k = min(max_pairs, Q * W)
    hit, idx = jax.lax.top_k(mask.ravel().astype(jnp.float32), k)
    valid = hit > 0.0
    qi = (idx // W).astype(jnp.int32)
    wi = (idx % W).astype(jnp.int32)
    uid_a = jnp.where(valid, uq.astype(jnp.int32)[qi], -1)
    uid_b = jnp.where(valid, uw.astype(jnp.int32)[wi], -1)
    score = jnp.where(valid, scores.ravel().astype(jnp.float32)[idx], 0.0)
    if k < max_pairs:
        pad = max_pairs - k
        uid_a = jnp.concatenate([uid_a, jnp.full((pad,), -1, jnp.int32)])
        uid_b = jnp.concatenate([uid_b, jnp.full((pad,), -1, jnp.int32)])
        score = jnp.concatenate([score, jnp.zeros((pad,), jnp.float32)])
    n_pairs = jnp.minimum(total, max_pairs).astype(jnp.int32)
    return PairBuffer(
        uid_a, uid_b, score, n_pairs,
        (total - n_pairs).astype(jnp.int32), jnp.zeros((), jnp.int32),
    )


def tile_emit_counts(scores: jax.Array, block_q: int, block_w: int) -> jax.Array:
    """Per-(block_q, block_w)-tile emit counts from a dense score matrix —
    the jnp mirror of the kernel's stage-1 count output, for the ref path."""
    Q, W = scores.shape
    pq, pw = (-Q) % block_q, (-W) % block_w
    s = jnp.pad(scores, ((0, pq), (0, pw)))
    nq, nw = (Q + pq) // block_q, (W + pw) // block_w
    m = (s > 0.0).reshape(nq, block_q, nw, block_w)
    return jnp.sum(m, axis=(1, 3), dtype=jnp.int32)
