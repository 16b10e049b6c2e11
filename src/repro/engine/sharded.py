"""Sharded fan-out: each device owns a ring-buffer shard of the window.

The single-device engine's capacity wall is memory: one ring of
``capacity`` vectors.  Here the window is sharded over the mesh axis the
``"window"`` logical axis resolves to (:data:`repro.distributed.sharding
.DEFAULT_RULES` maps it to ``data``), so global capacity grows linearly
with device count — the inverse of the paper's Table-2 result, where STR's
single-host window was the failure mode.

Schedule per micro-batch (inside one ``shard_map`` + ``lax.scan``, reusing
the engine's shared scan body — :func:`repro.engine.engine.make_micro_step`):

  * queries are **broadcast** (replicated) — every device joins the full
    micro-batch against its own window shard only; no ring permutes, no
    raw-vector traffic between devices after the initial broadcast;
  * within-batch pairs are computed everywhere (inputs are replicated) but
    emitted by shard 0 only, so each pair appears exactly once globally;
  * compaction is **three-level hierarchical** (DESIGN.md §3/§5): kernel
    tiles select ``(tile_k,)`` candidates (level 1, inside the join), each
    device merges its tiles into a ``(shard_k,)`` buffer (level 2, inside
    ``shard_map``), and after the ``out_specs`` gather one more segmented
    merge packs the per-shard buffers into a single global ``(max_pairs,)``
    buffer — so ``max_pairs`` is a **global** budget, not per-shard, and
    host traffic per micro-batch is O(max_pairs) however many shards exist.
    Per-row match masks are OR-reduced over shards the same way;
  * arrivals are dealt round-robin (item *i* lands on shard ``i mod P``),
    so each shard's ring ages uniformly and eviction stays time-ordered
    per shard.

Multi-tenant composition (DESIGN.md §10): with a
:class:`~repro.runtime.tenants.TenantTable`, the scan inputs gain the
``sqs`` stream-id lane, every ring shard carries its slice of the
``sids`` lane (``WindowState.sids``, dealt round-robin with the vectors),
and the per-tenant ``(θ_k, λ_k)`` tables ride the ``shard_map`` in_specs
**replicated** — each shard looks its query rows' parameters up locally,
and because queries are replicated, every shard derives the *same*
unpadded ``(min θ, min λ)`` pruning scalars, so the bounds stay admissible
shard-for-shard (ops.py contract).  The stream-equality mask is folded
into the join on every shard by the level-1 impls themselves; nothing
about the three-level merge or the global ``max_pairs`` budget changes.

Every drop stays attributed to its level: ``tile_k`` overflow accumulates
in-scan (``dropped_tile``), ``shard_k`` overflow accumulates in-scan
(``dropped``), and global-merge losses accumulate after the gather in a
**dedicated telemetry lane** (lane ``n_shards``; the ``pairs`` counter is
corrected down there too), so ``pairs_emitted`` always equals what the
drain actually delivers while lanes ``0..n_shards-1`` stay honest
per-shard counters (:func:`shard_stats`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import AxisRules, DEFAULT_RULES, auto_axes, shard_map
from ..kernels.sssj_join import PairBuffer, PairCandidates, merge_candidates
from ..kernels.sssj_join.gate import StripSummary, init_strip_summary
from ..obs import merge_disjoint, publish_flat
from .engine import (
    EngineConfig,
    EngineTelemetry,
    StreamEngineBase,
    init_telemetry,
    make_micro_step,
)
from .window import WindowState, init_window, push_with_overflow

__all__ = [
    "ShardedStreamEngine",
    "init_sharded_window",
    "make_sharded_batch_step",
    "shard_metrics",
    "shard_stats",
    "shard_view",
    "window_axis",
]


def window_axis(mesh: Mesh, rules: AxisRules = DEFAULT_RULES) -> str:
    """Mesh axis the logical ``"window"`` axis resolves to under ``rules``."""
    axes = rules.lookup("window")
    if isinstance(axes, str):
        axes = (axes,)
    for a in axes or ():
        if a in mesh.axis_names:
            return a
    raise ValueError(
        f"no mesh axis for logical 'window' (rules {axes!r}, mesh {mesh.axis_names})"
    )


def init_sharded_window(
    cfg: EngineConfig, mesh: Mesh, axis: str, n_lanes: Optional[int] = None
) -> WindowState:
    """Global window of ``cfg.capacity`` per-shard slots × axis size.

    The ``sids`` stream-id lane is always materialized (sharded like
    ``uids``) so the same state pytree serves both the single-tenant
    engine and the multi-tenant runtime's sharded path.  ``n_lanes``
    materializes the per-stream policy lanes (DESIGN.md §11) as
    ``(n_shards, n_lanes)`` replicated-in-lane arrays: each shard owns
    its row — quota sub-rings (and their cursors) are **shard-local**.
    """
    mesh = auto_axes(mesh)
    n = mesh.shape[axis]
    if n_lanes is None:
        n_lanes = cfg.n_lanes

    def lanes():
        return None if n_lanes is None else jnp.zeros((n, n_lanes), jnp.int32)

    def summary():
        if not cfg.gate_enabled:
            return None
        # per-shard summaries must be built at per-shard geometry: a
        # ragged per-shard capacity (capacity % block_w != 0) pads INSIDE
        # each shard, which a global summarize over capacity·n slots would
        # mis-align.  Strip rows concatenate along the shard axis exactly
        # like the ring slots they summarize.
        s1 = init_strip_summary(
            cfg.capacity, cfg.d, block_w=cfg.block_w, chunk_d=cfg.chunk_d
        )
        return jax.tree.map(
            lambda x: jnp.tile(x, (n,) + (1,) * (x.ndim - 1)), s1
        )

    def build() -> WindowState:
        state = init_window(cfg.capacity * n, cfg.d)
        return state._replace(
            cursor=jnp.zeros((n,), jnp.int32),
            overflow=jnp.zeros((n,), jnp.int32),
            lane_cursor=lanes() if cfg.eviction == "quota" else None,
            lane_overflow=lanes(),
            summary=summary(),
        )

    # every leaf is sharded along its leading axis, and the state is built
    # in place by one jitted program: no device ever holds the global
    # window (at 2^20 rows × 768 per shard that is 3 GiB per device), and
    # every leaf is its own buffer, as the donating step requires
    def leading(x):
        return NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))

    return jax.jit(
        build, out_shardings=jax.tree.map(leading, jax.eval_shape(build))
    )()


def make_sharded_batch_step(cfg: EngineConfig, mesh: Mesh, axis: str, table=None):
    """Jitted shard_map step with the same signature as
    :func:`repro.engine.engine.make_batch_step`: per-shard buffers are
    merged into one global ``(max_pairs,)`` buffer per micro-batch and
    masks are OR-reduced over shards before anything reaches the host.

    With a :class:`~repro.runtime.tenants.TenantTable` the signature
    mirrors :func:`repro.runtime.runtime.make_tenant_batch_step` instead —
    ``(state, telem, qs, tqs, uqs, sqs, nvs)`` — and the step becomes
    stream-tagged: the ``sqs`` lane is dealt into each shard's ``sids``
    ring lane, the stream-equality mask rides the level-1 join on every
    shard, and per-query-row ``(theta_q, lam_q)`` are looked up inside the
    ``shard_map`` from the table's device arrays (broadcast replicated
    through the in_specs).

    The step runs on the :func:`~repro.distributed.sharding.auto_axes`
    view of ``mesh``: the post-gather merge is ordinary jnp on replicated
    buffers, which an ``Explicit``-axis mesh (the ``jax.make_mesh``
    default) would refuse to gather from a sharded operand.
    """
    mesh = auto_axes(mesh)
    if cfg.emit_dense:
        raise ValueError(
            "emit_dense is the single-device test oracle; the sharded engine "
            "runs the hierarchical path only"
        )
    p = mesh.shape[axis]
    if cfg.micro_batch % p != 0:
        raise ValueError(f"micro_batch {cfg.micro_batch} not divisible by {p} shards")
    multi = table is not None
    quota = cfg.eviction == "quota"
    lanes = multi or cfg.n_lanes is not None
    tau = table.tau_max if multi else cfg.tau
    per_row = multi and not table.is_uniform
    bl = cfg.micro_batch // p         # arrivals per shard per micro-batch
    shard_k = cfg.shard_k or cfg.max_pairs
    # level-2 (per-shard) merge capacity: the in-scan micro step merges this
    # shard's tiles into a (shard_k,) buffer; the global budget is applied
    # after the gather
    local_cfg = dataclasses.replace(cfg, max_pairs=shard_k)

    def local_core(state, telem, xs, th_t, lm_t, quo_t):
        me = jax.lax.axis_index(axis)

        def ingest(st, q, tq, uq, n_valid, t_max, sq=None):
            # round-robin deal: this shard ingests items me, me+p, me+2p, …
            idx = me + p * jnp.arange(bl, dtype=jnp.int32)
            n_valid_l = jnp.sum((idx < n_valid).astype(jnp.int32))
            return push_with_overflow(
                st, q[idx], tq[idx], uq[idx], n_valid_l, t_max, tau,
                sq=None if sq is None else sq[idx],
                eviction=cfg.eviction, quotas=quo_t,
                summary_block_w=cfg.block_w, summary_chunk_d=cfg.chunk_d,
            )

        # replicated inputs ⇒ every shard computes the same self candidates;
        # only shard 0 keeps them so each pair appears once globally (counts
        # are zeroed, not dropped — suppression is not an overflow).  Row
        # masks stay unmasked: they are identical on every shard and OR'd.
        def self_mask(c: PairCandidates) -> PairCandidates:
            keep = (me == 0).astype(jnp.int32)
            return c._replace(kept=c.kept * keep, emitted=c.emitted * keep)

        lookup = None
        if multi:
            def lookup(sq):
                # replicated queries ⇒ identical per-row lanes (and identical
                # unpadded min-θ/min-λ pruning scalars) on every shard
                if not per_row:
                    return None
                return table.lookup_rows(th_t, lm_t, sq)

        micro = make_micro_step(
            local_cfg, ingest, self_mask=self_mask, tenant_lookup=lookup
        )

        # per-shard scalars travel as (1,) slices of the P(axis) arrays
        # (and the policy lanes as (1, n_lanes) rows)
        def lane0(x):
            return None if x is None else x[0]

        sub = state._replace(
            cursor=state.cursor[0], overflow=state.overflow[0],
            lane_cursor=lane0(state.lane_cursor),
            lane_overflow=lane0(state.lane_overflow),
        )
        tl = jax.tree.map(lambda x: x[0], telem)
        (sub, tl), (bufs, masks) = jax.lax.scan(micro, (sub, tl), xs)
        state = sub._replace(
            cursor=sub.cursor[None], overflow=sub.overflow[None],
            lane_cursor=None if sub.lane_cursor is None
            else sub.lane_cursor[None],
            lane_overflow=None if sub.lane_overflow is None
            else sub.lane_overflow[None],
        )
        telem = jax.tree.map(lambda x: x[None], tl)
        # scalar leaves come out of the scan as (n_micro,); give them a
        # trailing axis so out_specs can concatenate shards along it, and
        # masks a middle axis so shards gather side by side
        bufs = bufs._replace(
            n_pairs=bufs.n_pairs[:, None],
            n_dropped=bufs.n_dropped[:, None],
            n_dropped_tile=bufs.n_dropped_tile[:, None],
        )
        return state, telem, bufs, masks[:, None, :]

    # replicated broadcast args: query lanes, then the optional device
    # tables — tenant (θ, λ) and, under quota eviction, the per-shard
    # quota table (in_specs P() like the tenant tables, DESIGN.md §11) —
    # then the valid-row counts
    def local_batch(state, telem, *rest):
        if multi:
            qs, tqs, uqs, sqs, th_t, lm_t, *rest = rest
        else:
            qs, tqs, uqs, *rest = rest
            sqs = th_t = lm_t = None
        quo_t, (nvs,) = (rest[0], rest[1:]) if quota else (None, rest)
        xs = (
            (qs, tqs, uqs, sqs, nvs) if multi else (qs, tqs, uqs, nvs)
        )
        return local_core(state, telem, xs, th_t, lm_t, quo_t)

    n_bcast = 4 + (3 if multi else 0) + (1 if quota else 0)

    state_specs = WindowState(
        vecs=P(axis, None), ts=P(axis), uids=P(axis),
        cursor=P(axis), overflow=P(axis), sids=P(axis),
        lane_cursor=P(axis, None) if (lanes and quota) else None,
        lane_overflow=P(axis, None) if lanes else None,
        # strip summaries shard along their strip axis, like the ring
        # slots they summarize (each shard gates against its own window)
        summary=StripSummary(
            vmax=P(axis, None), cnorm=P(axis, None),
            tmin=P(axis), tmax=P(axis), umax=P(axis),
        ) if cfg.gate_enabled else None,
    )
    telem_specs = EngineTelemetry(*(P(axis) for _ in EngineTelemetry._fields))
    buf_specs = PairBuffer(
        uid_a=P(None, axis), uid_b=P(None, axis), score=P(None, axis),
        n_pairs=P(None, axis), n_dropped=P(None, axis),
        n_dropped_tile=P(None, axis),
    )
    fn = shard_map(
        local_batch,
        mesh=mesh,
        in_specs=(state_specs, telem_specs) + (P(),) * n_bcast,
        out_specs=(state_specs, telem_specs, buf_specs, P(None, axis, None)),
        check_vma=False,
    )

    def shard_merge(ua, ub, sc, kept):
        """Level 3: gathered per-shard buffers → one global budget."""
        cands = PairCandidates(
            uid_a=ua.reshape(p, shard_k),
            uid_b=ub.reshape(p, shard_k),
            score=sc.reshape(p, shard_k),
            kept=kept,
            emitted=kept,   # shard-level losses were already counted in-scan
        )
        return merge_candidates(cands, max_pairs=cfg.max_pairs)

    def finish(state, tout, extra, bufs, masks):
        gbufs = jax.vmap(shard_merge)(
            bufs.uid_a, bufs.uid_b, bufs.score, bufs.n_pairs
        )
        # the in-scan `pairs` counter summed per-shard survivors; pairs that
        # just fell to the global budget move to `dropped`.  The correction
        # lives in the dedicated lane n (not any shard's lane), so per-shard
        # counters stay honest while the lane sums keep the global
        # invariant pairs_emitted == what the drain delivers
        merge_drops = jnp.sum(gbufs.n_dropped)
        extra = extra._replace(
            pairs=extra.pairs.at[0].add(-merge_drops),
            dropped=extra.dropped.at[0].add(merge_drops),
        )
        telem = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), tout, extra
        )
        return state, telem, gbufs, jnp.any(masks, axis=1)

    def split_lanes(telem):
        # lanes 0..p-1 ride the shard_map (one per shard); lane p carries
        # the global-merge correction and stays on the host side of it
        tin = jax.tree.map(lambda x: x[:p], telem)
        extra = jax.tree.map(lambda x: x[p:], telem)
        return tin, extra

    quo_tail = (cfg.quotas_device(),) if quota else ()
    if multi:
        th_d, lm_d = table.device_tables

        def batch_step(state, telem, qs, tqs, uqs, sqs, nvs):
            tin, extra = split_lanes(telem)
            state, tout, bufs, masks = fn(
                state, tin, qs, tqs, uqs, sqs, th_d, lm_d, *quo_tail, nvs
            )
            return finish(state, tout, extra, bufs, masks)
    else:
        def batch_step(state, telem, qs, tqs, uqs, nvs):
            tin, extra = split_lanes(telem)
            state, tout, bufs, masks = fn(
                state, tin, qs, tqs, uqs, *quo_tail, nvs
            )
            return finish(state, tout, extra, bufs, masks)

    return jax.jit(batch_step, donate_argnums=(0,))


_SHARD_FIELDS = (
    "live_slots", "cursor", "window_overflow",
    "pairs_emitted", "pairs_dropped_budget", "pairs_dropped_tile",
    "tiles_skipped_time", "tiles_skipped_l2", "strips_survived",
)


def shard_metrics(
    state: WindowState, telem: EngineTelemetry, n_shards: int
) -> dict:
    """Per-shard liveness and drop counters as a flat namespaced dict
    (``engine/shard/<i>/…``, DESIGN.md §12) — the registry form; the
    nested legacy view (:func:`shard_stats`) is derived from it, so both
    surfaces are the same numbers by construction.

    Telemetry lanes ``0..n_shards-1`` are the in-scan per-shard counters;
    lane ``n_shards`` holds the global-merge correction (see
    :func:`make_sharded_batch_step`), surfaced as
    ``pairs_dropped_global`` rather than mis-charged to any shard — so
    per-shard ``pairs_emitted`` counts that shard's merge survivors
    *before* the global budget and is never negative."""
    n = n_shards
    uids = np.asarray(state.uids).reshape(n, -1)
    pairs = np.asarray(telem.pairs).reshape(-1)
    dropped = np.asarray(telem.dropped).reshape(-1)
    dropped_tile = np.asarray(telem.dropped_tile).reshape(-1)
    lanes = {
        "live_slots": (uids >= 0).sum(axis=1),
        "cursor": np.asarray(state.cursor).reshape(-1),
        "window_overflow": np.asarray(state.overflow).reshape(-1),
        "pairs_emitted": pairs[:n],
        "pairs_dropped_budget": dropped[:n],
        "pairs_dropped_tile": dropped_tile[:n],
        # per-shard gate lanes: lane p (the global-merge correction lane)
        # never accumulates gate counters, so [:n] loses nothing
        "tiles_skipped_time": np.asarray(telem.tiles_skipped_time).reshape(-1)[:n],
        "tiles_skipped_l2": np.asarray(telem.tiles_skipped_l2).reshape(-1)[:n],
        "strips_survived": np.asarray(telem.strips_survived).reshape(-1)[:n],
    }
    out = {
        "engine/n_shards": n,
        "engine/pairs_dropped_global": int(dropped[n:].sum()),
    }
    for i in range(n):
        for f in _SHARD_FIELDS:
            out[f"engine/shard/{i}/{f}"] = int(lanes[f][i])
    return out


def shard_view(flat: dict) -> dict:
    """The nested legacy per-shard stats vocabulary, rebuilt from a flat
    metrics dict / registry snapshot containing ``engine/shard/<i>/…``."""
    n = int(flat["engine/n_shards"])
    return {
        "n_shards": n,
        "pairs_dropped_global": flat["engine/pairs_dropped_global"],
        "shards": {
            f: [flat[f"engine/shard/{i}/{f}"] for i in range(n)]
            for f in _SHARD_FIELDS
        },
    }


def shard_stats(state: WindowState, telem: EngineTelemetry, n_shards: int) -> dict:
    """Nested per-shard stats (the legacy surface) — a view over
    :func:`shard_metrics`."""
    return shard_view(shard_metrics(state, telem, n_shards))


class ShardedStreamEngine(StreamEngineBase):
    """Host facade mirroring :class:`StreamEngine` over a device mesh.

    ``cfg.capacity`` is the *per-shard* ring size; the global window holds
    ``capacity × n_shards`` items.  ``cfg.max_pairs`` is the **global**
    emission budget per micro-batch (the hierarchical merge packs shard
    buffers down to it), and ``cfg.shard_k`` bounds what a single shard may
    contribute (default: ``max_pairs``).
    """

    def __init__(
        self,
        cfg: EngineConfig,
        mesh: Mesh,
        rules: AxisRules = DEFAULT_RULES,
        axis: Optional[str] = None,
    ) -> None:
        super().__init__(cfg)
        self.mesh = mesh
        self.axis = axis or window_axis(mesh, rules)
        self.n_shards = mesh.shape[self.axis]
        self.state = init_sharded_window(cfg, mesh, self.axis)
        # lanes 0..n-1 per shard + lane n for the global-merge correction
        n = self.n_shards + 1
        self.telem = jax.tree.map(
            lambda x: jnp.zeros((n,), x.dtype), init_telemetry()
        )
        self._step = make_sharded_batch_step(cfg, mesh, self.axis)

    def _global_capacity(self) -> int:
        return self.cfg.capacity * self.n_shards

    def _publish_metrics(self, reg) -> None:
        super()._publish_metrics(reg)
        publish_flat(
            reg, shard_metrics(self.state, self.telem, self.n_shards)
        )

    def stats(self) -> dict:
        snap = self.registry.snapshot()
        return merge_disjoint(self._legacy_engine_view(snap), shard_view(snap))
