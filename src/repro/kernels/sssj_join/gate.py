"""Device-resident strip summaries: the L2/prefix candidate-generation gate.

The paper's L2 index wins by never *scoring* most candidates: prefix
filtering and ℓ2-norm bounds kill a candidate before its dot product is
computed.  The device engine so far only pruned *inside* a launched tile
(the kernel's tile-level time filter + chunked suffix bound); every
(query-tile × window-strip) program still launched.  This module lifts the
paper's index-side bounds to strip granularity so dead tiles are never
launched at all:

  * :class:`StripSummary` — per-strip aggregates carried in the engine's
    ``lax.scan`` state alongside the ring buffer: top-weight coordinate
    prefixes (``vmax``: per-dimension max |w| over the strip, the paper's
    max-vector m̂ restricted to a strip), per-chunk max row norms
    (``cnorm``: the ℓ2/suffix-bound aggregate at chunk granularity), and
    the strip's live time extremes + max uid (the time-filter aggregate).
  * :func:`summarize_strips` / :func:`refresh_strip_summary` — full and
    incremental maintenance.  The refresh is what the write-slot policy
    layer calls after every ring write: it recomputes exactly the strips
    the write touched (a gather of ``block_w`` slots per written row —
    capacity-independent), under any eviction policy, because it keys off
    the *destination slots*, not off any policy-specific structure.
  * :func:`strip_gate` — the admissible pre-launch gate: for each
    (query-tile, strip) it bounds every pair's decayed score by
    ``min(prefix_bound, l2_bound) · exp(-λ_min · Δt_min)`` and compares
    against the unpadded per-batch min-θ (the same scalars the tenant-table
    pruning uses, DESIGN.md §10) — so a gated-off tile provably cannot emit
    for *any* row, under per-row (θ, λ) and on every shard.

Admissibility (DESIGN.md §13): for a query row x and a window row y in
strip s,

    dot(x, y) ≤ Σ_i |x_i| · vmax_s[i]                 (prefix bound)
    dot(x, y) ≤ Σ_c ‖x_c‖ · cnorm_s[c]                (chunked ℓ2 bound)
    |Δt|      ≥ max(0, tq_lo − tmax_s, tmin_s − tq_hi) = Δt_min

with λ_row ≥ λ_min and θ_row ≥ θ_min over the *unpadded* batch, so

    score = dot · exp(-λ_row |Δt|) ≤ ub · exp(-λ_min Δt_min) < θ_min ≤ θ_row

whenever the gate says dead.  Both value bounds hold with absolute values
(the bounds are ≥ 0 while emission needs score ≥ θ > 0), and the chunked
ℓ2 bound is itself ≤ ‖x‖·‖y‖ by Cauchy–Schwarz on the chunk-norm vectors
— never looser than the whole-vector bound the host index implies.

Empty / padded slots are inert by construction: ``vmax = cnorm = 0``,
``umax = -1``, ``tmin = +3e30``, ``tmax = -3e30`` — an empty strip is
gated off by both the uid check and the time bound.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "GATE_KERNEL",
    "StripSummary",
    "init_strip_summary",
    "refresh_strip_summary",
    "strip_gate",
    "summarize_strips",
]

_EMPTY_TS = jnp.float32(3.0e30)
GATE_KERNEL = "sssj_strip_gate"   # pallas_call name (stable, for HLO/traces)


class StripSummary(NamedTuple):
    """Per-strip index aggregates (a pytree; one row per window strip).

    Shapes for a window of ``capacity`` slots summarized at ``block_w``
    granularity with ``n_strips = ceil(capacity / block_w)`` and
    ``n_chunks = ceil(d / chunk_d)``:
    """

    vmax: jax.Array   # (n_strips, d) f32 — per-dim max |w| over live slots
    cnorm: jax.Array  # (n_strips, n_chunks) f32 — per-chunk max row norm
    tmin: jax.Array   # (n_strips,) f32 — min live ts (+3e30 when empty)
    tmax: jax.Array   # (n_strips,) f32 — max live ts (-3e30 when empty)
    umax: jax.Array   # (n_strips,) i32 — max uid (-1 when empty)


def init_strip_summary(
    capacity: int, d: int, *, block_w: int, chunk_d: int
) -> StripSummary:
    """Summary of an all-empty window (matches ``summarize_strips`` on
    a fresh :func:`~repro.engine.window.init_window` state)."""
    ns = -(-capacity // block_w)
    nc = -(-d // chunk_d)
    return StripSummary(
        vmax=jnp.zeros((ns, d), jnp.float32),
        cnorm=jnp.zeros((ns, nc), jnp.float32),
        tmin=jnp.full((ns,), _EMPTY_TS, jnp.float32),
        tmax=jnp.full((ns,), -_EMPTY_TS, jnp.float32),
        umax=jnp.full((ns,), -1, jnp.int32),
    )


def _strip_stats(v, t, u, chunk_d: int):
    """Shared reduction: ``(g, block_w, ·)`` slot groups → per-group
    aggregates.  ``v`` must already be zero-padded to a chunk multiple."""
    g, bw, dp = v.shape
    nc = dp // chunk_d
    live = u >= 0                                        # (g, bw)
    lv = live[:, :, None].astype(jnp.float32)
    vmax = jnp.max(jnp.abs(v) * lv, axis=1)              # (g, dp)
    cn = jnp.sqrt((v * v).reshape(g, bw, nc, chunk_d).sum(-1))
    cnorm = jnp.max(cn * lv, axis=1)                     # (g, nc)
    tmin = jnp.min(jnp.where(live, t, _EMPTY_TS), axis=1)
    tmax = jnp.max(jnp.where(live, t, -_EMPTY_TS), axis=1)
    umax = jnp.max(u, axis=1)
    return vmax, cnorm, tmin, tmax, umax


def summarize_strips(
    vecs: jax.Array, ts: jax.Array, uids: jax.Array,
    *, block_w: int, chunk_d: int,
) -> StripSummary:
    """Full (re)build: summarize every strip of a window from scratch.

    Ragged tails are handled on both axes: a capacity that is not a
    ``block_w`` multiple pads the last strip with inert empty slots, and a
    feature dim that is not a ``chunk_d`` multiple pads with zeros —
    exactly the padding the join applies, so the bounds line up with what
    the kernel actually computes.
    """
    cap, d = vecs.shape
    ns = -(-cap // block_w)
    nc = -(-d // chunk_d)
    pad_r = ns * block_w - cap
    pad_c = nc * chunk_d - d
    v = jnp.pad(vecs.astype(jnp.float32), ((0, pad_r), (0, pad_c)))
    t = jnp.pad(ts.astype(jnp.float32), (0, pad_r), constant_values=_EMPTY_TS)
    u = jnp.pad(uids.astype(jnp.int32), (0, pad_r), constant_values=-1)
    vmax, cnorm, tmin, tmax, umax = _strip_stats(
        v.reshape(ns, block_w, nc * chunk_d),
        t.reshape(ns, block_w),
        u.reshape(ns, block_w),
        chunk_d,
    )
    return StripSummary(
        vmax=vmax[:, :d], cnorm=cnorm, tmin=tmin, tmax=tmax, umax=umax
    )


def refresh_strip_summary(
    summary: StripSummary,
    vecs: jax.Array, ts: jax.Array, uids: jax.Array,
    dest: jax.Array,
    *, block_w: int, chunk_d: int,
) -> StripSummary:
    """Incremental maintenance: recompute the strips a write touched.

    ``vecs/ts/uids`` are the **post-write** window arrays and ``dest (b,)``
    the slots the write-slot policy selected (``capacity`` is the drop
    sentinel, see :func:`~repro.engine.window.select_write_slots`), so this
    works identically under all eviction policies — including ``"quota"``,
    where the victim strip is the writer's own sub-ring.  Cost is
    ``O(b · block_w · d)`` per micro-batch, independent of capacity.

    Rows writing into the same strip recompute identical aggregates, so
    the duplicate scatter indices below are value-deterministic; sentinel
    rows map to strip id ``n_strips`` and are dropped by the scatter mode.
    """
    cap, d = vecs.shape
    ns = summary.umax.shape[0]
    nc = summary.cnorm.shape[1]
    pad_c = nc * chunk_d - d
    dest = dest.astype(jnp.int32)
    # NOT a bare dest // block_w: the drop sentinel (dest == cap) would
    # collide with the last real strip whenever cap % block_w != 0
    sid = jnp.where(dest < cap, dest // block_w, ns)
    base = jnp.clip(sid, 0, ns - 1) * block_w
    idx = base[:, None] + jnp.arange(block_w, dtype=jnp.int32)[None, :]
    ok = idx < cap                                       # ragged last strip
    idx_c = jnp.minimum(idx, cap - 1)
    v = vecs[idx_c].astype(jnp.float32) * ok[:, :, None]
    t = jnp.where(ok, ts[idx_c].astype(jnp.float32), _EMPTY_TS)
    u = jnp.where(ok, uids[idx_c].astype(jnp.int32), -1)
    if pad_c:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_c)))
    vmax, cnorm, tmin, tmax, umax = _strip_stats(v, t, u, chunk_d)
    return StripSummary(
        vmax=summary.vmax.at[sid].set(vmax[:, :d], mode="drop"),
        cnorm=summary.cnorm.at[sid].set(cnorm, mode="drop"),
        tmin=summary.tmin.at[sid].set(tmin, mode="drop"),
        tmax=summary.tmax.at[sid].set(tmax, mode="drop"),
        umax=summary.umax.at[sid].set(umax, mode="drop"),
    )


# --------------------------------------------------------------------- #
# the pre-launch gate
# --------------------------------------------------------------------- #
_STRIP_BLOCK_BYTES = 2 << 20   # VMEM for one vmax block of the gate grid


def _gate_ub_kernel(qa_ref, qcn_ref, vmax_ref, cnorm_ref, ub_ref):
    """One query tile vs one block of strips:
    ``ub[j] = max_i min(pb, lb)[i, j]``.  HIGHEST precision keeps the
    bound an f32 bound — a bf16-rounded one could fall below the score
    it must cover."""
    dims = (((1,), (1,)), ((), ()))
    pb = jax.lax.dot_general(
        qa_ref[...], vmax_ref[...], dimension_numbers=dims,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    lb = jax.lax.dot_general(
        qcn_ref[...], cnorm_ref[...], dimension_numbers=dims,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    ub_ref[0] = jnp.max(jnp.minimum(pb, lb), axis=0, keepdims=True)


def _tile_ub_pallas(qa, qcn, vmax, cnorm, *, block_q: int, interpret: bool):
    """Grid ``(query tiles, strip blocks)``: each program stages one
    ``(sb, d)`` block of the summary, so VMEM use is set by ``d`` and never
    by the window's capacity.  ``sb`` is a multiple of 128 (or all strips,
    when fewer); padded strips carry zero aggregates and are cut off."""
    Qp, d = qa.shape
    ns, nc = cnorm.shape
    nq = Qp // block_q
    sb = max(128, min(512, _STRIP_BLOCK_BYTES // (4 * d) // 128 * 128))
    if ns <= sb:
        sb = ns
    nsp = -(-ns // sb) * sb
    if nsp != ns:
        vmax = jnp.pad(vmax, ((0, nsp - ns), (0, 0)))
        cnorm = jnp.pad(cnorm, ((0, nsp - ns), (0, 0)))
    ub = pl.pallas_call(
        _gate_ub_kernel,
        grid=(nq, nsp // sb),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, s: (i, 0)),
            pl.BlockSpec((block_q, nc), lambda i, s: (i, 0)),
            pl.BlockSpec((sb, d), lambda i, s: (s, 0)),
            pl.BlockSpec((sb, nc), lambda i, s: (s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, sb), lambda i, s: (i, 0, s)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, nsp), jnp.float32),
        interpret=interpret,
        name=GATE_KERNEL,
    )(qa, qcn, vmax, cnorm)
    return ub[:, 0, :ns]


def _chunk_norms(x: jax.Array, chunk_d: int) -> jax.Array:
    """``out[i, c] = ‖x_i restricted to chunk c‖`` (f32, (n, n_chunks))."""
    n, d = x.shape
    nc = d // chunk_d
    sq = (x.astype(jnp.float32) ** 2).reshape(n, nc, chunk_d).sum(-1)
    return jnp.sqrt(sq)


@functools.partial(
    jax.jit, static_argnames=("block_q", "chunk_d", "impl", "interpret")
)
def strip_gate(
    qp: jax.Array,
    summary: StripSummary,
    *,
    block_q: int,
    chunk_d: int,
    tq_lo: jax.Array,
    tq_hi: jax.Array,
    th_min,
    lam_min,
    impl: str = "jnp",
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Admissible per-(query-tile × strip) launch gate.

    Args:
      qp: (Qp, d_pad) padded query block — ``d_pad`` a ``chunk_d`` multiple
        (padded rows carry zero vectors, which only loosen the tile max).
      summary: strip aggregates for the window being joined; ``vmax`` may
        be narrower than ``d_pad`` (the join zero-pads features) and is
        zero-padded here to match.
      tq_lo/tq_hi, th_min/lam_min: extremes over the **unpadded** batch
        (padding fills would loosen / corrupt the bounds, ops.py contract).
      impl: ``"jnp"`` or ``"pallas"`` for the value-bound matmuls (the
        Pallas variant keeps the (Qp, n_strips) bound matrices in VMEM,
        worth it when the join itself runs as the Pallas kernel).
      interpret: Pallas interpret mode; ``None`` runs the compiled kernel
        on a TPU and the interpreter elsewhere.

    Returns:
      gate:  (nq, n_strips) bool — True where the tile must launch.
      stats: (3,) i32 — ``[tiles_skipped_time, tiles_skipped_l2,
        strips_survived]`` (tiles_total is ``gate.size``, already counted
        by the engine's ``tiles`` telemetry).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Qp, d_pad = qp.shape
    nq = Qp // block_q
    ns, d_s = summary.vmax.shape
    vmax = summary.vmax
    if d_s < d_pad:
        vmax = jnp.pad(vmax, ((0, 0), (0, d_pad - d_s)))
    qa = jnp.abs(qp.astype(jnp.float32))
    qcn = _chunk_norms(qp, chunk_d)
    if impl == "pallas":
        ub_tile = _tile_ub_pallas(
            qa, qcn, vmax, summary.cnorm, block_q=block_q, interpret=interpret
        )
    else:
        pb = qa @ vmax.T                                  # (Qp, ns)
        lb = qcn @ summary.cnorm.T                        # (Qp, ns)
        ub_tile = jnp.max(
            jnp.minimum(pb, lb).reshape(nq, block_q, ns), axis=1
        )
    dt_lb = jnp.maximum(
        0.0, jnp.maximum(tq_lo - summary.tmax, summary.tmin - tq_hi)
    )
    decay_ub = jnp.exp(-lam_min * dt_lb)                  # (ns,)
    time_alive = (decay_ub >= th_min) & (summary.umax >= 0)
    gate = time_alive[None, :] & (ub_tile * decay_ub[None, :] >= th_min)
    skipped_time = nq * jnp.sum(jnp.logical_not(time_alive).astype(jnp.int32))
    skipped_l2 = jnp.sum(
        (time_alive[None, :] & jnp.logical_not(gate)).astype(jnp.int32)
    )
    survived = jnp.sum(jnp.any(gate, axis=0).astype(jnp.int32))
    stats = jnp.stack([skipped_time, skipped_l2, survived]).astype(jnp.int32)
    return gate, stats
