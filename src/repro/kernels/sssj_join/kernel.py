"""Pallas TPU kernel: blocked time-decayed similarity join with pruning.

This is the TPU-native re-derivation of the paper's STR-L2 hot loop
(candidate generation, §5.3–§5.4): for a tile of Q query vectors and a tile
of W window (candidate) vectors it computes the thresholded, time-decayed
score matrix

    S[i, j] = dot(q_i, w_j) · exp(-λ |t_qi - t_wj|)    if ≥ θ and uid order
              0                                         otherwise

with the paper's two pruning mechanisms lifted from item granularity to
tile granularity (see DESIGN.md §2):

  * **time filtering** — if ``max_ij exp(-λΔt_ij) < θ`` the whole tile is
    dead (``dot ≤ 1``) and the k-loop is never entered; this also covers
    ring-buffer slots that are empty (uid < 0) and pairs excluded by the
    uid order mask, which are folded into the decay matrix as zeros;
  * **ℓ2 suffix bound (Cauchy–Schwarz)** — the feature dimension is
    processed in chunks; after chunk k, the unseen remainder is bounded by
    ``‖q_i^{>k}‖ · ‖w_j^{>k}‖`` (precomputed suffix norms); when the bound
    says no pair in the tile can reach θ, the k-loop exits early.  This is
    exactly the paper's ``rs2``/``l2bound`` pruning, applied per tile.

Grid: ``(n_q_tiles, n_w_tiles)``.  Each program owns one (BQ, BW) output
tile; the full feature dimension of both tiles is staged in VMEM and
consumed chunk by chunk so the early exit saves real MXU work.

Two emission variants share the score computation (``_tile_scores``):

  * :func:`sssj_join_kernel_call` — the PR-1 dense variant: writes the full
    thresholded ``(Q, W)`` score tile to HBM plus per-tile emit counts.
    Retained as the ``emit_dense`` oracle path.
  * :func:`sssj_join_candidates_kernel_call` — level 1 of the hierarchical
    compaction (DESIGN.md §3): each program selects its own ≥ θ entries
    into a fixed ``(tile_k,)`` candidate buffer of (uid, uid, score)
    triples via prefix counts on the MXU (products with triangular ones
    matrices) and a two-level one-hot select — **no sort, no scan, and no
    dense tile ever leaves VMEM**.  Dead tiles
    (the common case under time filtering) write only a zero count and the
    inert-slot fill, so HBM output is ``O(n_tiles · tile_k)`` instead of
    ``4·Q·W`` bytes.  A per-row hit bitmap (exact even when ``tile_k``
    overflows) rides along for the O(B) match-mask consumers.

Layouts follow the TPU's (8, 128) tiling so every block is lane-dense and
no per-row lane is padded to 128 lanes in HBM: query-side per-row values
ride as ``(Q, 1)`` columns (a micro-batch is small), window-side per-row
values as ``(n_w_tiles, BW)`` rows, and candidate buffers as ``(…,
tile_k / 128, 128)`` slabs.  Per-tile scalars (gate bit, emit count,
chunk count) live in SMEM.  Suffix norms are computed from the tiles in
VMEM, only for tiles that pass the time filter, so the window is read
once per join.

VMEM footprint per program ≈ 2·(BQ + BW)·d·4 bytes of double-buffered
input blocks, a few (BQ, BW) f32 temporaries and 2·3·tile_k·4 bytes of
candidate slabs: about 1 MB at BQ = BW = 64, d = 768, tile_k = 4096,
inside the 16 MiB scoped VMEM of a v5e core.  The window grows only the
grid, never a block.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "CANDIDATE_KERNEL",
    "sssj_join_candidates_kernel_call",
    "sssj_join_kernel_call",
    "tpu_kernels",
]

CANDIDATE_KERNEL = "sssj_candidates"   # pallas_call name, kept stable so
#                                        compiled HLO and traces find it
NEG_UID = -1   # uid marking empty / padded slots
LANES = 128    # lane width of a candidate slab row
ROW_GROUP = 8  # window-tile rows per (8, BW) block of a row-layout lane

_TPU_CALL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"'
)


def tpu_kernels(hlo_text: str) -> set:
    """Names of the compiled Mosaic kernels (``tpu_custom_call``) in a
    compiled program's HLO text — a kernel's instruction is named after
    its ``pallas_call``."""
    return set(_TPU_CALL.findall(hlo_text))


_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # (m, k) · (k, n)
_NT = (((1,), (1,)), ((), ()))   # (m, k) · (n, k)ᵀ


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, dimension_numbers=dims, precision=_HI,
        preferred_element_type=jnp.float32,
    )


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _tile_row(ref):
    """This window tile's ``(1, BW)`` row of a ``(ROW_GROUP, BW)`` block.
    Call it outside any branch: the interpreter resolves ``program_id``
    only at the kernel's top level."""
    return ref[pl.ds(pl.program_id(1) % ROW_GROUP, 1), :]


def _eye(n: int):
    return _iota((n, n), 0) == _iota((n, n), 1)


def _to_row(col, eye):
    """``(n, 1)`` column → ``(1, n)`` lane row, exactly: a sum through the
    identity mask adds only zeros to each entry."""
    return jnp.sum(jnp.where(eye, col, jnp.zeros((), col.dtype)), axis=0,
                   keepdims=True)


def _suffix_norms(q, w, chunk_d: int, n_chunks: int):
    """``‖x restricted to chunks > k‖`` for both tiles, k = 0 … n_chunks-1:
    query rows as ``(BQ, 1)`` columns, window rows as ``(1, BW)`` lane
    rows — the paper's ℓ2 prefix magnitudes, computed from the tiles
    already in VMEM (the jnp definition is ``ops.suffix_chunk_norms``;
    suffixes are summed from the last chunk backwards, as there)."""
    eye = _eye(w.shape[0])
    qsq, wsq = [], []
    for c in range(n_chunks):
        cols = slice(c * chunk_d, (c + 1) * chunk_d)
        qsq.append(jnp.sum(jnp.square(q[:, cols]), axis=1, keepdims=True))
        wsq.append(_to_row(
            jnp.sum(jnp.square(w[:, cols]), axis=1, keepdims=True), eye
        ))
    sq = [None] * n_chunks
    sw = [None] * n_chunks
    acc_q = jnp.zeros_like(qsq[0])
    acc_w = jnp.zeros_like(wsq[0])
    for k in reversed(range(n_chunks)):
        sq[k], sw[k] = jnp.sqrt(acc_q), jnp.sqrt(acc_w)
        acc_q = acc_q + qsq[k]
        acc_w = acc_w + wsq[k]
    return tuple(sq), tuple(sw)


def _tile_scores(
    q_ref, w_ref, tq_ref, tw_ref, uq_ref, uw_ref,
    *, theta: float, lam: float, chunk_d: int, n_chunks: int,
    bq: int, bw: int,
    sid_q_ref=None, sid_w_ref=None, th_ref=None, lm_ref=None,
    gate_ref=None,
):
    """Shared per-tile score computation: thresholded decayed similarities
    for one (BQ, BW) tile, with tile-level time filtering and the chunked
    ℓ2 early exit.  Returns ``(emitted (BQ, BW) f32, k_final () i32)``.

    The optional multi-tenant refs (DESIGN.md §9) fold a stream-equality
    mask into the order mask (``sid_q == sid_w``; cross-stream pairs never
    score) and replace the static (θ, λ) with per-query-row values looked
    up from the tenant table — the query row's stream is the pair's stream,
    so query-side values govern the pair.  Both prunes survive: the decay
    matrix uses the row's λ, and every "≥ θ" check becomes row-wise
    (``any(x ≥ θ_row)``), which for a scalar θ is the same predicate the
    single-tenant kernel used.

    The chunk loop is unrolled (``n_chunks`` is static) and each chunk
    runs under a scalar branch on the previous chunk's bound, so a pruned
    tile issues no further MXU work; a dead tile never computes its
    suffix norms either.
    """
    f32 = jnp.float32
    tq = tq_ref[...].astype(f32)               # (BQ, 1)
    tw = _tile_row(tw_ref).astype(f32)         # (1, BW)
    uq = uq_ref[...]                           # (BQ, 1) int32
    uw = _tile_row(uw_ref)                     # (1, BW) int32
    if th_ref is None:
        th = theta                             # scalar broadcast
        lam_col = lam
    else:
        th = th_ref[...].astype(f32)           # (BQ, 1)
        lam_col = lm_ref[...].astype(f32)

    decay = jnp.exp(-lam_col * jnp.abs(tq - tw))   # (BQ, BW)
    # uid-order mask: join each pair once (query strictly newer), and drop
    # empty ring slots / padding (uid < 0).  Folded into the decay matrix so
    # the tile-level time filter below covers all masking at once.
    order = (uw >= 0) & (uq > uw)
    if sid_q_ref is not None:
        order &= sid_q_ref[...] == _tile_row(sid_w_ref)
    decay = jnp.where(order, decay, 0.0)

    # --- time filtering at tile granularity (paper §3 / §6.2) ---
    live = jnp.any(decay >= th)                # dot ≤ 1 ⇒ decayed ≤ decay
    if gate_ref is not None:
        # pre-launch L2/prefix gate (DESIGN.md §13): the strip-summary
        # bound already proved this tile cannot reach any row's θ, so the
        # chunk loop never starts (k_final = 0, like a time-dead tile)
        live &= gate_ref[0, 0, 0] > 0

    sq, sw = jax.lax.cond(
        live,
        lambda: _suffix_norms(
            q_ref[...].astype(f32), w_ref[...].astype(f32), chunk_d, n_chunks
        ),
        lambda: ((jnp.zeros((bq, 1), f32),) * n_chunks,
                 (jnp.zeros((1, bw), f32),) * n_chunks),
    )
    acc = jnp.zeros((bq, bw), dtype=f32)
    k_final = jnp.int32(0)
    for k in range(n_chunks):
        def chunk(carry, k=k):
            acc, _, _ = carry
            cols = slice(k * chunk_d, (k + 1) * chunk_d)
            acc = acc + _dot(
                q_ref[:, cols].astype(f32), w_ref[:, cols].astype(f32), _NT
            )
            # --- ℓ2 suffix bound (paper's rs2 / l2bound per tile) ---
            ub = (acc + sq[k] * sw[k]) * decay
            return acc, jnp.any(ub >= th), jnp.int32(k + 1)

        acc, live, k_final = jax.lax.cond(
            live, chunk, lambda carry: carry, (acc, live, k_final)
        )

    scores = acc * decay
    emitted = jnp.where(scores >= th, scores, 0.0)
    return emitted, k_final


def _kernel(
    q_ref, w_ref, tq_ref, tw_ref, uq_ref, uw_ref,
    out_ref, iters_ref, counts_ref,
    *, theta: float, lam: float, chunk_d: int, n_chunks: int,
):
    bq, bw = out_ref.shape
    emitted, k_final = _tile_scores(
        q_ref, w_ref, tq_ref, tw_ref, uq_ref, uw_ref,
        theta=theta, lam=lam, chunk_d=chunk_d, n_chunks=n_chunks,
        bq=bq, bw=bw,
    )
    out_ref[...] = emitted
    iters_ref[0, 0, 0] = k_final
    # stage 1 of pair compaction: how many entries this tile will emit
    counts_ref[0, 0, 0] = jnp.sum((emitted > 0.0).astype(jnp.int32))


def _select_slab_row(r, emitted, crow, row_end, row_base, uq, uw, kept):
    """Slots ``r·128 … r·128+127`` of this tile's candidate buffer.

    Slot ``s`` holds the ``(s+1)``-th emitted entry in row-major order.
    Its row is the number of rows whose inclusive prefix count ends at or
    before ``s``; inside that row, its column is the number of entries
    whose within-row prefix count is at most ``s − row_base``.  Both
    counts are compares and lane sums; the row is fetched with a one-hot
    matmul, exact at HIGHEST precision because each output sums a single
    product.  ``uq``/``uw`` are the tile's uids as ``(1, BQ)``/``(1, BW)``
    lane rows.  Returns ``(uid_a, uid_b, score)``, each a ``(1, 128)``
    lane row.
    """
    bq, bw = emitted.shape
    s = r * LANES + _iota((LANES, 1), 0)                       # (128, 1)
    row = jnp.sum((row_end <= s).astype(jnp.int32), axis=1, keepdims=True)
    at_row = _iota((LANES, bq), 1) == jnp.minimum(row, bq - 1)
    onehot = at_row.astype(jnp.float32)
    base = jnp.sum(onehot * row_base, axis=1, keepdims=True)
    rank = s.astype(jnp.float32) - base                        # within row
    col = jnp.sum(
        (_dot(onehot, crow, _NN) <= rank).astype(jnp.int32),
        axis=1, keepdims=True,
    )
    at_col = _iota((LANES, bw), 1) == jnp.minimum(col, bw - 1)
    score = jnp.sum(
        jnp.where(at_col, _dot(onehot, emitted, _NN), 0.0),
        axis=1, keepdims=True,
    )
    uid_a = jnp.sum(jnp.where(at_row, uq, 0), axis=1, keepdims=True)
    uid_b = jnp.sum(jnp.where(at_col, uw, 0), axis=1, keepdims=True)
    valid = s < kept
    eye = _eye(LANES)
    return (
        _to_row(jnp.where(valid, uid_a, -1), eye),
        _to_row(jnp.where(valid, uid_b, -1), eye),
        _to_row(jnp.where(valid, score, 0.0), eye),
    )


def _cand_kernel(
    q_ref, w_ref, tq_ref, tw_ref, uq_ref, uw_ref,
    *refs,
    theta: float, lam: float, chunk_d: int, n_chunks: int, tile_k: int,
    multi: bool = False,
    gated: bool = False,
):
    """Level-1 hierarchical compaction: select this tile's ≥ θ entries.

    Ranks are prefix counts computed on the MXU — the hit matrix times an
    upper-triangular ones matrix gives within-row counts, and the row
    totals times another gives each row's offset — and slot filling walks
    only the ``ceil(kept / 128)`` slab rows that receive entries (see
    :func:`_select_slab_row`), writing each slot's uids and score.  Dead
    tiles skip the select entirely.

    With ``multi=True`` four extra input refs precede the outputs —
    per-row stream ids (query/window) and per-query-row (θ, λ) — and the
    stream-equality mask joins the masking stack (see ``_tile_scores``).
    ``row_hits`` is one ``(1, BQ)`` block per query tile, accumulated
    across the window-tile axis of the grid.
    """
    if multi:
        sid_q_ref, sid_w_ref, th_ref, lm_ref = refs[:4]
        refs = refs[4:]
    else:
        sid_q_ref = sid_w_ref = th_ref = lm_ref = None
    if gated:
        gate_ref, *refs = refs
    else:
        gate_ref = None
    ua_ref, ub_ref, score_ref, emitted_ref, rowhits_ref, iters_ref = refs
    bq = q_ref.shape[0]
    bw = w_ref.shape[0]
    f32 = jnp.float32
    emitted, k_final = _tile_scores(
        q_ref, w_ref, tq_ref, tw_ref, uq_ref, uw_ref,
        theta=theta, lam=lam, chunk_d=chunk_d, n_chunks=n_chunks,
        bq=bq, bw=bw,
        sid_q_ref=sid_q_ref, sid_w_ref=sid_w_ref, th_ref=th_ref,
        lm_ref=lm_ref, gate_ref=gate_ref,
    )
    iters_ref[0, 0, 0] = k_final
    uw = _tile_row(uw_ref)                         # (1, BW)

    m = (emitted > 0.0).astype(f32)                # (BQ, BW)
    count = jnp.sum(m.astype(jnp.int32))
    emitted_ref[0, 0, 0] = count
    ua_ref[...] = jnp.full(ua_ref.shape, -1, jnp.int32)
    ub_ref[...] = jnp.full(ub_ref.shape, -1, jnp.int32)
    score_ref[...] = jnp.zeros(score_ref.shape, f32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        rowhits_ref[...] = jnp.zeros(rowhits_ref.shape, jnp.int32)

    @pl.when(count > 0)
    def _():
        upper_w = (_iota((bw, bw), 0) <= _iota((bw, bw), 1)).astype(f32)
        upper_q = (_iota((bq, bq), 0) <= _iota((bq, bq), 1)).astype(f32)
        crow = _dot(m, upper_w, _NN)               # inclusive within-row
        # row totals as a lane row (every sublane equal), then their
        # inclusive prefix over rows
        row_tot = _dot(jnp.ones((8, bw), f32), m, _NT)      # (8, BQ)
        row_end = _dot(row_tot, upper_q, _NN)[0:1, :]       # (1, BQ)
        row_tot = row_tot[0:1, :]
        row_base = row_end - row_tot
        rowhits_ref[0] = jnp.maximum(
            rowhits_ref[0], (row_tot > 0.0).astype(jnp.int32)
        )
        kept = jnp.minimum(count, tile_k)
        uq = _to_row(uq_ref[...], _eye(bq))        # (1, BQ)

        def fill(r, carry):
            ua, ub, score = _select_slab_row(
                r, emitted, crow, row_end.astype(jnp.int32), row_base,
                uq, uw, kept,
            )
            ua_ref[0, 0, pl.ds(r, 1), :] = ua
            ub_ref[0, 0, pl.ds(r, 1), :] = ub
            score_ref[0, 0, pl.ds(r, 1), :] = score
            return carry

        jax.lax.fori_loop(0, (kept + LANES - 1) // LANES, fill, 0)


def _smem_tile_scalar(nw: int):
    """``(nq·nw, 1, 1)`` per-tile scalar, one SMEM word per grid step."""
    return pl.BlockSpec(
        (1, 1, 1), lambda i, j: (i * nw + j, 0, 0),
        memory_space=pltpu.SMEM,
    )


def _join_in_specs(block_q: int, block_w: int, d: int):
    def rows(i, j):
        return (j // ROW_GROUP, 0)

    return [
        pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),        # q
        pl.BlockSpec((block_w, d), lambda i, j: (j, 0)),        # w
        pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),        # tq
        pl.BlockSpec((ROW_GROUP, block_w), rows),               # tw rows
        pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),        # uq
        pl.BlockSpec((ROW_GROUP, block_w), rows),               # uw rows
    ]


def sssj_join_kernel_call(
    q: jax.Array,        # (Q, d)
    w: jax.Array,        # (W, d)
    tq: jax.Array,       # (Q, 1) f32
    tw: jax.Array,       # (nW8, BW) f32 window rows, nW8 a multiple of 8
    uq: jax.Array,       # (Q, 1) i32
    uw: jax.Array,       # (nW8, BW) i32
    *,
    theta: float,
    lam: float,
    block_q: int,
    block_w: int,
    chunk_d: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Dense-emission pallas_call; shapes must be padded to block multiples
    and window lanes laid out as ``ops.window_rows`` does.

    Returns ``(scores (Q, W), iters (nQ, nW), counts (nQ, nW))`` where
    ``counts`` is the per-tile number of emitted (≥ θ) entries.
    """
    Q, d = q.shape
    W, _ = w.shape
    n_chunks = d // chunk_d
    nq, nw = Q // block_q, W // block_w
    grid = (nq, nw)

    kernel = functools.partial(
        _kernel, theta=theta, lam=lam, chunk_d=chunk_d, n_chunks=n_chunks
    )
    scalar = jax.ShapeDtypeStruct((nq * nw, 1, 1), jnp.int32)
    scores, iters, counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_join_in_specs(block_q, block_w, d),
        out_specs=[
            pl.BlockSpec((block_q, block_w), lambda i, j: (i, j)),
            _smem_tile_scalar(nw),
            _smem_tile_scalar(nw),
        ],
        out_shape=[jax.ShapeDtypeStruct((Q, W), jnp.float32), scalar, scalar],
        interpret=interpret,
        name="sssj_dense_join",
    )(q, w, tq, tw, uq, uw)
    return scores, iters.reshape(grid), counts.reshape(grid)


def sssj_join_candidates_kernel_call(
    q: jax.Array,        # (Q, d)
    w: jax.Array,        # (W, d)
    tq: jax.Array,       # (Q, 1) f32
    tw: jax.Array,       # (nW8, BW) f32 window rows, nW8 a multiple of 8
    uq: jax.Array,       # (Q, 1) i32
    uw: jax.Array,       # (nW8, BW) i32
    *,
    theta: float,
    lam: float,
    block_q: int,
    block_w: int,
    chunk_d: int,
    tile_k: int,
    interpret: bool,
    sq: jax.Array = None,       # (Q, 1) i32 stream ids (multi-tenant)
    sw: jax.Array = None,       # (nW8, BW) i32
    theta_q: jax.Array = None,  # (Q, 1) f32 per-row θ
    lam_q: jax.Array = None,    # (Q, 1) f32 per-row λ
    gate: jax.Array = None,     # (nQ, nW) pre-launch gate (0 = dead)
) -> tuple[jax.Array, ...]:
    """Hierarchical (level-1) pallas_call; no dense ``(Q, W)`` output exists.

    Returns ``(uid_a, uid_b (nQ, nW, n_rows, 128) i32 — -1 in unused
    slots, score (nQ, nW, n_rows, 128) f32, emitted (nQ, nW) i32 true
    per-tile ≥ θ counts, row_hits (nQ, BQ) i32 0/1, iters (nQ, nW) i32)``
    with ``n_rows = ceil(tile_k / 128)``.  Each tile's slab holds its first
    ``min(emitted, tile_k)`` pairs in within-tile row-major order; slots
    past ``tile_k`` are inert.  The slabs are returned as the kernel wrote
    them: flattening their rows would relayout them under the (8, 128)
    HBM tiling.

    The multi-tenant lanes (all four or none) ride as extra inputs in the
    layouts of their timestamp counterparts.
    """
    Q, d = q.shape
    W, _ = w.shape
    n_chunks = d // chunk_d
    nq, nw = Q // block_q, W // block_w
    grid = (nq, nw)
    n_rows = -(-tile_k // LANES)
    multi = sq is not None
    if multi and theta_q is None:
        # stream lanes without per-row (θ, λ) — uniform tenants: the kernel
        # takes the four lanes together, so broadcast the static scalars
        # (numerically identical to the scalar path)
        theta_q = jnp.full((Q, 1), theta, jnp.float32)
        lam_q = jnp.full((Q, 1), lam, jnp.float32)

    kernel = functools.partial(
        _cand_kernel, theta=theta, lam=lam, chunk_d=chunk_d,
        n_chunks=n_chunks, tile_k=tile_k, multi=multi,
        gated=gate is not None,
    )
    in_specs = _join_in_specs(block_q, block_w, d)
    inputs = [q, w, tq, tw, uq, uw]
    if multi:
        in_specs += [
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),  # sq
            in_specs[5],                                      # sw rows
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),  # theta_q
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),  # lam_q
        ]
        inputs += [sq, sw, theta_q, lam_q]
    if gate is not None:
        in_specs += [_smem_tile_scalar(nw)]
        inputs += [gate.astype(jnp.int32).reshape(nq * nw, 1, 1)]
    slab_spec = pl.BlockSpec((1, 1, n_rows, LANES), lambda i, j: (i, j, 0, 0))
    scalar = jax.ShapeDtypeStruct((nq * nw, 1, 1), jnp.int32)
    ua, ub, score, emitted, row_hits, iters = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            slab_spec,
            slab_spec,
            slab_spec,
            _smem_tile_scalar(nw),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, 0)),
            _smem_tile_scalar(nw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, nw, n_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nq, nw, n_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nq, nw, n_rows, LANES), jnp.float32),
            scalar,
            jax.ShapeDtypeStruct((nq, 1, block_q), jnp.int32),
            scalar,
        ],
        interpret=interpret,
        name=CANDIDATE_KERNEL,
    )(*inputs)

    return (
        ua, ub, score,
        emitted.reshape(grid),
        row_hits.reshape(nq, block_q),
        iters.reshape(grid),
    )
