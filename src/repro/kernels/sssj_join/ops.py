"""Public jit'd wrappers for the SSSJ blocked-join kernel.

Handles padding to block multiples, the jnp definition of the ℓ2 suffix
norms (:func:`suffix_chunk_norms`; the kernel computes its own from the
tiles in VMEM), the kernel's lane layouts (:func:`window_rows`),
backend auto-detection (interpret mode off-TPU), routing of sub-block
inputs through the jnp reference (a `pallas_call` on a smaller-than-one-
block problem only pays padding + launch overhead), and unpadding of the
outputs.  ``chunk_d`` is capped at ``d``: a feature width below one chunk
is one chunk, so every input of at least one block runs the kernel.

Two join surfaces:

  * :func:`sssj_join_tiles` — dense emission: the thresholded ``(Q, W)``
    score matrix plus per-tile telemetry.  This is the PR-1 path, retained
    as the ``emit_dense`` oracle; it materializes O(Q·W) bytes.
  * :func:`sssj_join_candidates` — hierarchical emission (DESIGN.md §3):
    per-tile ``(tile_k,)`` candidate buffers with true-emit counts and a
    per-row hit mask.  Three interchangeable implementations produce
    bit-identical candidate buffers (the Pallas kernel's slots as the
    ``(ceil(tile_k / 128), 128)`` slabs it writes, the others' as rows):

      - ``"pallas"`` — the level-1 select inside the TPU kernel
        (``kernel.sssj_join_candidates_kernel_call``); the dense tile
        never leaves VMEM.
      - ``"scan"``   — a ``lax.scan`` over window tiles in plain jnp: one
        ``(Q, block_w)`` score block live at a time, selected per tile and
        discarded.  The compiled CPU/GPU default — no interpret-mode
        overhead and still no ``(Q, W)`` allocation.
      - ``"dense"``  — the jnp oracle: full ``(Q, W)`` ref scores, then
        :func:`repro.kernels.sssj_join.compact.tile_candidates`.  Used for
        sub-block inputs and as the ground truth in tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .compact import PairCandidates, tile_candidates, tile_emit_counts
from .gate import StripSummary, strip_gate
from .kernel import (
    NEG_UID,
    ROW_GROUP,
    sssj_join_candidates_kernel_call,
    sssj_join_kernel_call,
)
from .ref import sssj_join_ref

__all__ = [
    "JoinCandidates",
    "sssj_join_candidates",
    "sssj_join_scores",
    "sssj_join_tiles",
    "suffix_chunk_norms",
    "window_rows",
    "NEG_UID",
]


def suffix_chunk_norms(x: jax.Array, chunk_d: int) -> jax.Array:
    """``out[i, k] = ‖x_i restricted to chunks > k‖`` (f32, (n, n_chunks)).

    This is the per-vector data the paper's L2 index stores in its posting
    entries (prefix magnitudes ‖x'_j‖), reorganized for chunked evaluation:
    after the kernel has accumulated chunks 0..k, the unseen remainder of
    the dot product is bounded by ``out_q[i, k] * out_w[j, k]``.
    """
    n, d = x.shape
    n_chunks = d // chunk_d
    sq = (x.astype(jnp.float32) ** 2).reshape(n, n_chunks, chunk_d).sum(-1)
    # reverse-exclusive cumulative sum over chunks
    suffix_sq = jnp.flip(jnp.cumsum(jnp.flip(sq, axis=1), axis=1), axis=1)
    suffix_excl = jnp.concatenate(
        [suffix_sq[:, 1:], jnp.zeros((n, 1), jnp.float32)], axis=1
    )
    return jnp.sqrt(suffix_excl)


def _pad_rows(x: jax.Array, mult: int, fill=0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), constant_values=fill)


def window_rows(x: jax.Array, block_w: int, fill) -> jax.Array:
    """A block-padded per-row window lane ``(Wp,)`` in the kernel's row
    layout: ``(n_w_tiles, block_w)``, padded to a multiple of 8 tiles with
    ``fill``, so a window tile reads its lane as one ``(1, block_w)`` row.
    A ``(Wp, 1)`` column would pad every value to a 128-lane row in HBM."""
    return _pad_rows(x.reshape(-1, block_w), ROW_GROUP, fill=fill)


@functools.partial(
    jax.jit,
    static_argnames=(
        "theta", "lam", "block_q", "block_w", "chunk_d", "interpret", "use_ref"
    ),
)
def sssj_join_tiles(
    q: jax.Array,
    w: jax.Array,
    tq: jax.Array,
    tw: jax.Array,
    uq: jax.Array,
    uw: jax.Array,
    *,
    theta: float,
    lam: float,
    block_q: int = 128,
    block_w: int = 128,
    chunk_d: int = 128,
    interpret: Optional[bool] = None,
    use_ref: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked time-decayed similarity join with per-tile telemetry.

    Args:
      q:  (Q, d) query vectors (unit-normalized; f32 or bf16).
      w:  (W, d) window vectors.
      tq: (Q,) or (Q, 1) query timestamps.
      tw: (W,) window timestamps.
      uq: (Q,) query uids (monotone stream counters).
      uw: (W,) window uids; negative marks empty ring slots.
      theta, lam: SSSJ parameters.
      use_ref: route through the pure-jnp oracle instead of the kernel.
        Inputs smaller than one block (Q < block_q or W < block_w) are
        auto-routed through the reference as well — the kernel would spend
        its time on padding for them.

    Returns:
      scores: (Q, W) f32 — decayed similarity where ≥ θ (masked by uid
        order), 0 elsewhere.
      iters:  (nQ, nW) i32 — d-chunks executed per tile (pruning telemetry);
        all-`n_chunks` on the ref path.
      counts: (nQ, nW) i32 — emitted (≥ θ) entries per tile, stage 1 of the
        on-device pair compaction (see compact.py).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tq = tq.reshape(-1, 1).astype(jnp.float32)
    tw = tw.reshape(-1, 1).astype(jnp.float32)
    uq = uq.reshape(-1, 1).astype(jnp.int32)
    uw = uw.reshape(-1, 1).astype(jnp.int32)

    Q, d = q.shape
    W, _ = w.shape
    chunk_d = min(chunk_d, d)
    # ref fallback for unaligned tiny inputs: anything smaller than a single
    # kernel block would be all padding, so the dense jnp oracle is cheaper
    if Q < block_q or W < block_w:
        use_ref = True
    if use_ref:
        scores = sssj_join_ref(q, w, tq, tw, uq, uw, theta=theta, lam=lam)
        n_chunks = -(-d // chunk_d)
        iters = jnp.full(
            ((Q + block_q - 1) // block_q, (W + block_w - 1) // block_w),
            n_chunks,
            jnp.int32,
        )
        counts = tile_emit_counts(scores, block_q, block_w)
        return scores, iters, counts

    if d % chunk_d != 0:
        pad_d = (-d) % chunk_d
        q = jnp.pad(q, ((0, 0), (0, pad_d)))
        w = jnp.pad(w, ((0, 0), (0, pad_d)))
        d += pad_d

    qp = _pad_rows(q, block_q)
    wp = _pad_rows(w, block_w)
    tqp = _pad_rows(tq, block_q)
    uqp = _pad_rows(uq, block_q, fill=NEG_UID)
    tw_rows = window_rows(_pad_rows(tw[:, 0], block_w), block_w, 0.0)
    uw_rows = window_rows(
        _pad_rows(uw[:, 0], block_w, fill=NEG_UID), block_w, NEG_UID
    )

    scores, iters, counts = sssj_join_kernel_call(
        qp, wp, tqp, tw_rows, uqp, uw_rows,
        theta=theta, lam=lam,
        block_q=block_q, block_w=block_w, chunk_d=chunk_d,
        interpret=interpret,
    )
    return scores[:Q, :W], iters, counts


def sssj_join_scores(*args, **kw) -> tuple[jax.Array, jax.Array]:
    """Back-compat wrapper of :func:`sssj_join_tiles` without tile counts."""
    scores, iters, _ = sssj_join_tiles(*args, **kw)
    return scores, iters


# --------------------------------------------------------------------- #
# hierarchical emission
# --------------------------------------------------------------------- #
class JoinCandidates(NamedTuple):
    """Level-1 join output: per-tile candidates + exact per-row hit mask.

    ``cands`` segments are tiles in (q-tile, w-tile) row-major order, each
    holding its first ``kept`` ≥ θ pairs in within-tile row-major (stream)
    order.  ``row_mask (Q,)`` is exact even when ``tile_k`` overflows: it
    derives from counts, not survivors.  ``iters (nQ, nW)`` is the pruning
    telemetry (d-chunks executed; full count on the jnp impls, which do
    not prune).
    """

    cands: PairCandidates
    row_mask: jax.Array
    iters: jax.Array
    gate_stats: Optional[jax.Array] = None  # (3,) i32 [skipped_time,
    #                                         skipped_l2, strips_survived];
    #                                         zeros when no gate ran


@functools.partial(
    jax.jit,
    static_argnames=(
        "theta", "lam", "tile_k", "block_q", "block_w", "chunk_d",
        "impl", "interpret",
    ),
)
def sssj_join_candidates(
    q: jax.Array,
    w: jax.Array,
    tq: jax.Array,
    tw: jax.Array,
    uq: jax.Array,
    uw: jax.Array,
    *,
    theta: float,
    lam: float,
    tile_k: int = 256,
    block_q: int = 128,
    block_w: int = 128,
    chunk_d: int = 128,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
    sq: Optional[jax.Array] = None,
    sw: Optional[jax.Array] = None,
    theta_q: Optional[jax.Array] = None,
    lam_q: Optional[jax.Array] = None,
    summary: Optional[StripSummary] = None,
) -> JoinCandidates:
    """Blocked join with hierarchical (level-1) emission — no dense matrix.

    Args mirror :func:`sssj_join_tiles`; ``tile_k`` caps the candidates a
    single (block_q, block_w) tile may keep (overflow is counted in
    ``cands.emitted - cands.kept``, never silent).  ``impl`` picks the
    implementation (``"pallas"`` / ``"scan"`` / ``"dense"``, see module
    docstring); ``None`` auto-selects: the Pallas kernel on TPU, the
    compiled tile-scan elsewhere.  Sub-block inputs always take the dense
    jnp oracle — same candidate buffers, and the dense matrix they briefly
    materialize is smaller than one kernel tile.

    Multi-tenant lanes (DESIGN.md §9, honored identically by all three
    implementations):

      * ``sq (Q,)`` / ``sw (W,)`` — stream ids; a stream-equality mask is
        folded into the uid-order mask, so cross-stream pairs never emit;
      * ``theta_q (Q,)`` / ``lam_q (Q,)`` — optional per-query-row (θ, λ)
        looked up from the tenant table (pass both or neither).  The
        stream-equality mask makes the query row's stream the pair's
        stream, so query-side values govern the pair; the static
        ``theta``/``lam`` then only seed pruning defaults.

    L2/prefix gate (DESIGN.md §13): ``summary`` optionally carries the
    window's per-strip :class:`~repro.kernels.sssj_join.gate.StripSummary`
    (``n_strips = ceil(W / block_w)`` rows, maintained by the engine's
    write path).  When present, an admissible pre-launch bound gates every
    (query-tile × strip): the ``"scan"`` impl walks only surviving strips
    (a compacted gather — interior dead strips cost nothing), and the
    ``"pallas"`` impl folds the gate into the kernel's tile-alive predicate
    so gated-off programs skip the chunk loop.  The ``"dense"`` oracle
    ignores it.  Gating never changes emitted candidates — the bound
    certifies that a skipped tile cannot reach any row's θ.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    if (theta_q is None) != (lam_q is None):
        raise ValueError("theta_q and lam_q must be passed together")
    if (sq is None) != (sw is None):
        raise ValueError("sq and sw must be passed together")
    if theta_q is not None and sq is None:
        raise ValueError("per-row (theta_q, lam_q) requires stream lanes")
    tq = tq.reshape(-1).astype(jnp.float32)
    tw = tw.reshape(-1).astype(jnp.float32)
    uq = uq.reshape(-1).astype(jnp.int32)
    uw = uw.reshape(-1).astype(jnp.int32)
    if sq is not None:
        sq = sq.reshape(-1).astype(jnp.int32)
        sw = sw.reshape(-1).astype(jnp.int32)
    if theta_q is not None:
        theta_q = theta_q.reshape(-1).astype(jnp.float32)
        lam_q = lam_q.reshape(-1).astype(jnp.float32)
    # pruning scalars must come from the UNPADDED per-row tables: row
    # padding below uses inert fills (θ=2 can never emit, λ=0 never decays)
    # which would otherwise loosen the min-based strip/tile bounds.  Under
    # the sharded engine this call runs inside shard_map with q/theta_q/
    # lam_q REPLICATED and only w/sw sharded — every shard therefore
    # derives the same (min θ, min λ) over the same rows, and a strip
    # skipped on one shard is skipped because it is provably below every
    # row's threshold, exactly as on a single device (DESIGN.md §10)
    th_min = theta if theta_q is None else jnp.min(theta_q)
    lam_min = lam if lam_q is None else jnp.min(lam_q)
    # time extremes for the strip filters/gate, also from the UNPADDED
    # batch: _pad_rows fills tq with 0.0, which would pin tq_lo to 0 and
    # disable the older-than-horizon bound for any ragged Q (padded rows
    # carry uid = -1 and can never emit, so excluding them is sound)
    tq_lo, tq_hi = jnp.min(tq), jnp.max(tq)
    no_gate_stats = jnp.zeros((3,), jnp.int32)

    Q, d = q.shape
    W, _ = w.shape
    chunk_d = min(chunk_d, d)
    # sub-block inputs take the dense oracle (a kernel/scan launch would be
    # all padding)
    if Q < block_q or W < block_w:
        impl = "dense"

    if impl == "dense":
        scores = sssj_join_ref(
            q, w, tq[:, None], tw[:, None], uq[:, None], uw[:, None],
            theta=theta, lam=lam,
            sq=None if sq is None else sq[:, None],
            sw=None if sw is None else sw[:, None],
            theta_q=None if theta_q is None else theta_q[:, None],
            lam_q=None if lam_q is None else lam_q[:, None],
        )
        cands, row_mask = tile_candidates(
            scores, uq, uw, block_q=block_q, block_w=block_w, tile_k=tile_k
        )
        n_chunks = -(-d // chunk_d)
        iters = jnp.full(
            ((Q + block_q - 1) // block_q, (W + block_w - 1) // block_w),
            n_chunks,
            jnp.int32,
        )
        return JoinCandidates(
            cands=cands, row_mask=row_mask, iters=iters,
            gate_stats=no_gate_stats,
        )

    if d % chunk_d != 0:
        pad_d = (-d) % chunk_d
        q = jnp.pad(q, ((0, 0), (0, pad_d)))
        w = jnp.pad(w, ((0, 0), (0, pad_d)))
        d += pad_d
    qp = _pad_rows(q, block_q)
    wp = _pad_rows(w, block_w)
    tqp = _pad_rows(tq, block_q)
    twp = _pad_rows(tw, block_w)
    uqp = _pad_rows(uq, block_q, fill=NEG_UID)
    uwp = _pad_rows(uw, block_w, fill=NEG_UID)
    # inert fills: padded rows carry uid = -1 so they can never emit; the
    # θ/λ fills are chosen so they can't loosen any bound either
    sqp = None if sq is None else _pad_rows(sq, block_q, fill=NEG_UID)
    swp = None if sw is None else _pad_rows(sw, block_w, fill=NEG_UID)
    thp = None if theta_q is None else _pad_rows(theta_q, block_q, fill=2.0)
    lmp = None if lam_q is None else _pad_rows(lam_q, block_q, fill=0.0)
    Qp, Wp = qp.shape[0], wp.shape[0]
    nq, nw = Qp // block_q, Wp // block_w

    # L2/prefix pre-launch gate: one (Qp, n_strips) bound evaluation —
    # ~block_w× cheaper than scoring the strips it can kill
    gate = None
    gate_stats = no_gate_stats
    if summary is not None:
        gate, gate_stats = strip_gate(
            qp, summary, block_q=block_q, chunk_d=chunk_d,
            tq_lo=tq_lo, tq_hi=tq_hi, th_min=th_min, lam_min=lam_min,
            impl="pallas" if impl == "pallas" else "jnp",
            interpret=interpret,
        )

    if impl == "pallas":
        ua, ub, score, emitted, row_hits, iters = (
            sssj_join_candidates_kernel_call(
                qp, wp, tqp[:, None], window_rows(twp, block_w, 0.0),
                uqp[:, None], window_rows(uwp, block_w, NEG_UID),
                theta=theta, lam=lam, block_q=block_q, block_w=block_w,
                chunk_d=chunk_d, tile_k=tile_k, interpret=interpret,
                sq=None if sqp is None else sqp[:, None],
                sw=None if swp is None else window_rows(swp, block_w, NEG_UID),
                theta_q=None if thp is None else thp[:, None],
                lam_q=None if lmp is None else lmp[:, None],
                gate=gate,
            )
        )
        # merging the leading (nq, nw) dims keeps each (n_rows, 128) slab
        # where the kernel wrote it: a bitcast under the TPU's tiling
        t = nq * nw
        cands = PairCandidates(
            uid_a=ua.reshape((t,) + ua.shape[2:]),
            uid_b=ub.reshape((t,) + ub.shape[2:]),
            score=score.reshape((t,) + score.shape[2:]),
            kept=jnp.minimum(emitted, tile_k).reshape(t),
            emitted=emitted.reshape(t),
        )
        row_mask = (row_hits > 0).reshape(Qp)[:Q]
        return JoinCandidates(
            cands=cands, row_mask=row_mask, iters=iters,
            gate_stats=gate_stats,
        )

    if impl != "scan":
        raise ValueError(f"unknown sssj_join_candidates impl {impl!r}")

    # --- "scan": one (Qp, block_w) score block live at a time ----------- #
    w_tiles = wp.reshape(nw, block_w, d)
    tw_tiles = twp.reshape(nw, block_w)
    uw_tiles = uwp.reshape(nw, block_w)
    sw_tiles = None if swp is None else swp.reshape(nw, block_w)
    qf = qp.astype(jnp.float32)
    tq2 = tqp.astype(jnp.float32)
    n_chunks = d // chunk_d

    def strip(s):
        """Score one window column strip and select its tile candidates."""
        wt = jax.lax.dynamic_index_in_dim(w_tiles, s, 0, keepdims=False)
        twt = jax.lax.dynamic_index_in_dim(tw_tiles, s, 0, keepdims=False)
        uwt = jax.lax.dynamic_index_in_dim(uw_tiles, s, 0, keepdims=False)
        sims = qf @ wt.astype(jnp.float32).T                       # (Qp, BW)
        lam_col = lam if lmp is None else lmp[:, None]
        dec = sims * jnp.exp(-lam_col * jnp.abs(tq2[:, None] - twt[None, :]))
        order = (uwt[None, :] >= 0) & (uqp[:, None] > uwt[None, :])
        if sw_tiles is not None:
            swt = jax.lax.dynamic_index_in_dim(sw_tiles, s, 0, keepdims=False)
            order &= sqp[:, None] == swt[None, :]
        thr = theta if thp is None else thp[:, None]
        dec = jnp.where(order & (dec >= thr), dec, 0.0)
        return tile_candidates(
            dec, uqp, uwt, block_q=block_q, block_w=block_w, tile_k=tile_k
        )

    # Strip-level time filter (paper §3, the kernel's first prune, at
    # column-strip granularity): a lower bound on min |Δt| from the strips'
    # time extremes.  Empty ring slots carry t = +3e30, so a fully-empty
    # strip is dead by construction; unit vectors ⇒ dot ≤ 1 ⇒
    # score ≤ exp(-λ·Δt).  With per-row (θ, λ) the scalar bound uses
    # (min θ, min λ), which upper-bounds every row's score requirement.
    uw_max = jnp.max(uw_tiles, axis=1)
    newest = jnp.argmax(uw_max).astype(jnp.int32)
    dist = (newest - jnp.arange(nw, dtype=jnp.int32)) % nw
    if gate is None:
        tw_min = jnp.min(tw_tiles, axis=1)                         # (nw,)
        tw_max = jnp.max(tw_tiles, axis=1)
        dt_lb = jnp.maximum(0.0, jnp.maximum(tq_lo - tw_max, tw_min - tq_hi))
        alive = (jnp.exp(-lam_min * dt_lb) >= th_min) & (uw_max >= 0)
        # Cursor-anchored live range (ROADMAP strip-skipping item): ring
        # writes are sequential and uids monotone, so the newest strip is
        # the one holding the max uid and live strips cluster within the
        # τ-horizon just behind it.  Walking ``dist`` strips back from the
        # newest covers every flagged-alive strip (``n_live`` is defined as
        # exactly that cover), so the sweep costs O(live strips), not
        # O(n_strips) — an all-dead batch runs zero strip iterations
        # instead of n_strips `lax.cond` dispatches.  Correctness never
        # depends on the time-ordering: a strip outside the walk has
        # ``alive = False``, i.e. it is provably below θ for every row.
        alive_walk = alive
        iters = jnp.broadcast_to(
            jnp.where(alive, n_chunks, 0)[None, :], (nq, nw)
        ).astype(jnp.int32)
    else:
        # Gated walk: the L2/prefix gate subsumes the raw time filter
        # (its live-masked time extremes are at least as tight) and adds
        # the value bounds, at (q-tile × strip) granularity.  A strip is
        # scored iff ANY query tile admits it.  The walk itself keeps the
        # exact cursor-anchored shape of the ungated branch — do NOT
        # "optimize" this into an argsort-compacted visit list with a
        # ``sum(alive)`` trip count: under ``shard_map`` (check_vma=False)
        # that graph shape miscompiles, silently replicating one shard's
        # walk onto the others (pairs vanish; caught by the sharded quota
        # conformance cells).  Gate-killed strips inside the live range
        # are skipped by the ``lax.cond`` in ``body`` instead — their
        # matmul never runs, they cost one branch dispatch.
        alive_walk = jnp.any(gate, axis=0)                         # (nw,)
        iters = jnp.where(gate, n_chunks, 0).astype(jnp.int32)

    # Cursor-anchored live range (ROADMAP strip-skipping item): ring
    # writes are sequential and uids monotone, so the newest strip is
    # the one holding the max uid and live strips cluster within the
    # τ-horizon just behind it.  Walking ``dist`` strips back from the
    # newest covers every flagged-alive strip (``n_live`` is defined as
    # exactly that cover), so the sweep costs O(live strips), not
    # O(n_strips) — an all-dead batch runs zero strip iterations.
    # Correctness never depends on the time-ordering: a strip outside
    # the walk has ``alive_walk = False``, i.e. it is provably below θ
    # for every row.
    n_live = jnp.max(jnp.where(alive_walk, dist + 1, 0))

    def body(i, acc):
        s = (newest - i) % nw                    # walk newest-first

        def score(acc):
            cands_acc, mask_acc = acc
            cands_t, rm = strip(s)
            cands_acc = jax.tree.map(
                lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, s, 0),
                cands_acc, cands_t,
            )
            return cands_acc, mask_acc | rm

        if gate is None:
            # interior dead strips are rare on the sequential ring — a
            # branch per strip costs more than the occasional wasted score
            return score(acc)
        return jax.lax.cond(alive_walk[s], score, lambda a: a, acc)

    zeros_seg = jnp.zeros((nw, nq), jnp.int32)
    cands0 = PairCandidates(
        uid_a=jnp.full((nw, nq, tile_k), -1, jnp.int32),
        uid_b=jnp.full((nw, nq, tile_k), -1, jnp.int32),
        score=jnp.zeros((nw, nq, tile_k), jnp.float32),
        kept=zeros_seg, emitted=zeros_seg,
    )
    col_cands, any_mask = jax.lax.fori_loop(
        0, n_live, body, (cands0, jnp.zeros((Qp,), bool))
    )
    # accumulated leaves are (nw, nq, ...): reorder segments to (nq, nw)
    # tile-row-major so all impls emit identical buffers
    def reorder(x):
        return jnp.swapaxes(
            x.reshape((nw, nq) + x.shape[2:]), 0, 1
        ).reshape((nq * nw,) + x.shape[2:])

    cands = jax.tree.map(reorder, col_cands)
    row_mask = any_mask[:Q]
    # ``iters`` (set above per walk flavor) keeps the kernel's telemetry
    # granularity: dead strips/tiles execute zero d-chunks (the strip
    # bound is coarser than the kernel's per-pair decay max, so this may
    # overcount live tiles)
    return JoinCandidates(
        cands=cands, row_mask=row_mask, iters=iters, gate_stats=gate_stats
    )
