"""Multi-tenant streaming runtime: K logical streams on one engine.

The engine (DESIGN.md §4) assumes one logical stream whose arrival rate
fills 128-row micro-batches.  The ROADMAP's serving target is the
opposite shape: thousands of small independent streams, each too slow to
fill a micro-batch alone.  This runtime multiplexes them (DESIGN.md §9),
onto either the single-device engine or the sharded fan-out — the
:class:`EngineFacade` seam (construct, step, drain, stats) keeps the
runtime engine-agnostic, and :class:`ShardedFacade` composes the whole
multi-tenant machinery with :mod:`repro.engine.sharded`'s per-device ring
shards (DESIGN.md §10):

  * **stream-tagged state** — every ring slot and every drained pair
    carries a stream id; the join masks cross-stream pairs *on device*
    (all level-1 impls), optionally with per-stream (θ, λ) looked up from
    the :class:`~repro.runtime.tenants.TenantTable`;
  * **request coalescing** — the :class:`~repro.runtime.router
    .RequestRouter` packs sub-batch arrivals from many tenants into full
    micro-batches in strict admission order, so per-arrival device cost
    tracks *output* (SWOOP's invariant per tenant), not the number of
    tenants; padding waste and queue delay are telemetered;
  * **fixed-span dispatch** — the jitted step always scans exactly
    ``span`` micro-batches (short tails ride as inert empty micro-batches
    whose strips are all dead), so the runtime compiles **once** per
    payload shape no matter how ragged the traffic;
  * **fused embed→join** — with a :class:`FusedEmbedder`, submissions are
    token batches and the LM forward + pooling + normalize runs *inside*
    the same jit program as the join scan: embeddings never round-trip
    through the host.

Determinism: uids are assigned at admission (global arrival order), the
router preserves that order exactly, and the engine scan is invariant to
micro-batch splits — so the emitted pair set is invariant to coalescing
boundaries, flush timing, and span size (tested property-style in
``tests/test_runtime.py``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..engine.engine import (
    EngineConfig,
    StreamEngineBase,
    init_telemetry,
    make_micro_step,
)
from ..distributed.sharding import DEFAULT_RULES
from ..engine.sharded import (
    init_sharded_window,
    make_sharded_batch_step,
    shard_metrics,
    shard_view,
    window_axis,
)
from ..engine.window import init_window, push_with_overflow
from ..obs import merge_disjoint, publish_flat
from .router import RequestRouter, TenantBackpressure
from .tenants import TenantTable

__all__ = [
    "EngineFacade",
    "FusedEmbedder",
    "MultiTenantRuntime",
    "ShardedFacade",
    "SingleDeviceFacade",
    "make_tenant_batch_step",
    "TenantBackpressure",
]

_EMPTY_T = 3.0e30   # timestamp of inert pad rows in empty micro-batches


@dataclasses.dataclass(frozen=True)
class FusedEmbedder:
    """Embed-inside-the-join configuration for token submissions.

    ``model_cfg.d_model`` must equal ``EngineConfig.d``; ``seq_len`` fixes
    the token payload width (one compiled shape).  The embedding math is
    :func:`repro.serving.embedder.pooled_unit_embed` — the same function
    the host-side :class:`~repro.serving.embedder.LMEmbedder` jits, which
    is what makes fused and host-round-trip results bit-identical.
    """

    model_cfg: ModelConfig
    params: Any
    seq_len: int


class EngineFacade:
    """Construct/step/drain/stats seam between the runtime and an engine.

    The runtime itself is engine-agnostic: it owns admission, coalescing,
    uid→tenant attribution, and the host drain (inherited from
    :class:`~repro.engine.engine.StreamEngineBase`, whose layout contract —
    one merged :class:`~repro.kernels.sssj_join.PairBuffer` segment per
    micro-batch plus an OR-reduced row mask — both engines satisfy).  A
    facade supplies the four engine-specific pieces:

      * **construct** — :meth:`init_state` / :meth:`init_telemetry` build
        the window state (with the ``sids`` lane and the per-tenant policy
        lanes, DESIGN.md §11) and the telemetry carry;
      * **step** — :meth:`make_step` builds the jitted stream-tagged batch
        step ``(state, telem, qs, tqs, uqs, sqs, nvs) → (state, telem,
        bufs, masks)``;
      * **drain** — :meth:`global_capacity` sizes the dense-equivalent
        traffic accounting the drain reports;
      * **stats** — :meth:`metrics_extra` surfaces engine-specific
        counters (e.g. per-shard liveness) as a flat namespaced dict the
        runtime publishes into the shared registry (DESIGN.md §12).
    """

    def init_state(self, cfg: EngineConfig, table: TenantTable):
        raise NotImplementedError

    def init_telemetry(self, cfg: EngineConfig):
        raise NotImplementedError

    def make_step(
        self,
        cfg: EngineConfig,
        table: TenantTable,
        fused: Optional[FusedEmbedder],
    ):
        raise NotImplementedError

    def global_capacity(self, cfg: EngineConfig) -> int:
        raise NotImplementedError

    def metrics_extra(self, state, telem) -> dict:
        return {}


class SingleDeviceFacade(EngineFacade):
    """Default facade: one ring window on one device."""

    def init_state(self, cfg: EngineConfig, table: TenantTable):
        # per-tenant policy lanes are always materialized in the runtime:
        # overflow attribution is per-victim-stream under every policy
        return init_window(
            cfg.capacity, cfg.d, n_lanes=table.n_tenants,
            eviction=cfg.eviction,
            summary_block_w=cfg.block_w if cfg.gate_enabled else None,
            summary_chunk_d=cfg.chunk_d,
        )

    def init_telemetry(self, cfg: EngineConfig):
        return init_telemetry()

    def make_step(self, cfg, table, fused):
        return make_tenant_batch_step(cfg, table, fused)

    def global_capacity(self, cfg: EngineConfig) -> int:
        return cfg.capacity


class ShardedFacade(EngineFacade):
    """Sharded facade: one ring shard per device along the window axis.

    ``cfg.capacity`` stays the *per-shard* ring size (global window =
    ``capacity × n_shards``, same contract as
    :class:`~repro.engine.sharded.ShardedStreamEngine`); ``cfg.max_pairs``
    stays the global per-micro-batch budget.  ``cfg.micro_batch`` must be
    divisible by the shard count (round-robin deal).  The fused
    embed→join path is single-device only for now.
    """

    def __init__(self, mesh, rules=DEFAULT_RULES, axis: Optional[str] = None) -> None:
        self.mesh = mesh
        self.axis = axis or window_axis(mesh, rules)
        self.n_shards = int(mesh.shape[self.axis])

    def init_state(self, cfg: EngineConfig, table: TenantTable):
        return init_sharded_window(
            cfg, self.mesh, self.axis, n_lanes=table.n_tenants
        )

    def init_telemetry(self, cfg: EngineConfig):
        # lanes 0..n-1 per shard + lane n for the global-merge correction
        n = self.n_shards + 1
        return jax.tree.map(lambda x: jnp.zeros((n,), x.dtype), init_telemetry())

    def make_step(self, cfg, table, fused):
        if fused is not None:
            raise NotImplementedError(
                "fused embed→join is single-device only; submit vectors "
                "(or embed on the host) when running on ShardedFacade"
            )
        return make_sharded_batch_step(cfg, self.mesh, self.axis, table=table)

    def global_capacity(self, cfg: EngineConfig) -> int:
        return cfg.capacity * self.n_shards

    def metrics_extra(self, state, telem) -> dict:
        return shard_metrics(state, telem, self.n_shards)


def make_tenant_batch_step(
    cfg: EngineConfig,
    table: TenantTable,
    fused: Optional[FusedEmbedder] = None,
):
    """Jitted multi-tenant request step (single device).

    Signature: ``(state, telem, qs, tqs, uqs, sqs, nvs) → (state, telem,
    bufs, masks)`` — :func:`repro.engine.engine.make_batch_step` plus the
    ``sqs (n_micro, mb)`` stream-id lane; with ``fused``, ``qs`` is a
    token stack ``(n_micro, mb, seq_len)`` i32 and the step's signature
    gains a leading non-donated ``params`` pytree.  State and telemetry
    are donated.
    """
    tau = table.tau_max
    quo = cfg.quotas_device()

    def ingest(state, q, tq, uq, n_valid, t_max, sq):
        return push_with_overflow(
            state, q, tq, uq, n_valid, t_max, tau, sq=sq,
            eviction=cfg.eviction, quotas=quo,
            summary_block_w=cfg.block_w, summary_chunk_d=cfg.chunk_d,
        )

    if fused is None:
        def batch_step(state, telem, qs, tqs, uqs, sqs, nvs):
            micro = make_micro_step(cfg, ingest, tenant_lookup=table.lookup)
            (state, telem), (bufs, masks) = jax.lax.scan(
                micro, (state, telem), (qs, tqs, uqs, sqs, nvs)
            )
            return state, telem, bufs, masks

        return jax.jit(batch_step, donate_argnums=(0, 1))

    # imported lazily: serving.service imports this package for the
    # multi-tenant service facade, so a module-level import would cycle
    from ..serving.embedder import pooled_unit_embed

    model_cfg = fused.model_cfg

    def fused_step(params, state, telem, qs, tqs, uqs, sqs, nvs):
        def embed_fn(toks):
            return pooled_unit_embed(params, model_cfg, toks)

        micro = make_micro_step(
            cfg, ingest, tenant_lookup=table.lookup, embed_fn=embed_fn
        )
        (state, telem), (bufs, masks) = jax.lax.scan(
            micro, (state, telem), (qs, tqs, uqs, sqs, nvs)
        )
        return state, telem, bufs, masks

    return jax.jit(fused_step, donate_argnums=(1, 2))


class MultiTenantRuntime(StreamEngineBase):
    """K logical streams multiplexed onto one stream-tagged engine.

    The engine is pluggable via ``engine=`` (an :class:`EngineFacade`;
    default :class:`SingleDeviceFacade`, pass :class:`ShardedFacade(mesh)
    <ShardedFacade>` to spread the ring window over a device mesh —
    emissions are identical either way, DESIGN.md §10).

    ``submit(tenant, data, ts)`` admits a (possibly tiny) batch and
    returns its global uids; ``flush()`` coalesces everything queued into
    full micro-batches and dispatches them in fixed ``span``-sized scans
    (``flush(final=True)`` also pads out a trailing partial micro-batch);
    ``drain_by_tenant()`` returns each tenant's emitted pairs.  The
    inherited :meth:`drain_arrays` / :meth:`stats` keep working on the
    global stream.

    Timestamps should be globally non-decreasing in admission order —
    correctness never depends on it, but window eviction and the scan
    impl's live-strip walk are tuned for it (same contract as the
    single-tenant engine).
    """

    def __init__(
        self,
        cfg: EngineConfig,
        table: TenantTable,
        *,
        span: int = 4,
        max_queue_per_tenant: int = 65536,
        fused: Optional[FusedEmbedder] = None,
        engine: Optional[EngineFacade] = None,
    ) -> None:
        if cfg.emit_dense:
            raise ValueError("emit_dense is the single-tenant test oracle")
        if table.is_uniform:
            # uniform tenants keep the static-scalar join path; the table's
            # values are authoritative, so fold them into the config
            th, lm = table.spec(0)
            cfg = dataclasses.replace(cfg, theta=th, lam=lm)
        if fused is not None and fused.model_cfg.d_model != cfg.d:
            raise ValueError(
                f"fused embedder d_model ({fused.model_cfg.d_model}) must "
                f"equal EngineConfig.d ({cfg.d})"
            )
        if cfg.quotas is not None and len(cfg.quotas) != table.n_tenants:
            raise ValueError(
                f"quota table has {len(cfg.quotas)} entries but the tenant "
                f"table has {table.n_tenants} streams"
            )
        if span < 1:
            raise ValueError("span must be ≥ 1")
        super().__init__(cfg)
        self.table = table
        self.span = span
        self.fused = fused
        self.engine = engine or SingleDeviceFacade()
        self.router = RequestRouter(
            table.n_tenants, max_queue_per_tenant=max_queue_per_tenant
        )
        self.state = self.engine.init_state(cfg, table)
        self.telem = self.engine.init_telemetry(cfg)
        self._step = self.engine.make_step(cfg, table, fused)
        # observability (DESIGN.md §12): the engine registry and span tracer
        # (created by StreamEngineBase.__init__) are the single stats
        # surface — the runtime adds router/tenant collectors and
        # admission→return latency histograms to the same instance
        self._lat_hist = self.registry.histogram("latency/admit_to_emit_s")
        self._lat_by_tenant = [
            self.registry.histogram(f"tenant/{t}/latency_s")
            for t in range(table.n_tenants)
        ]
        # (sids, t_admit) per dispatch not yet returned; a drain joins every
        # pending dispatch, so it returns the rows of all of them
        self._dispatch_meta: List[Tuple[np.ndarray, np.ndarray]] = []
        self.registry.register_collector(self._publish_runtime_metrics)
        # uid → tenant map: a doubling-growth append buffer (4 B per item
        # ever admitted — see ROADMAP on tenant-aware state)
        self._uid_tenant_buf = np.empty((1024,), np.int32)
        self._uid_tenant_n = 0
        self._mask_uid0 = 0          # first uid the next drain's mask covers
        self.padded_rows = 0         # inert rows in real micro-batches
        self.empty_micro_batches = 0  # span-fill micro-batches (all dead)
        self.submitted_by_tenant: Dict[int, int] = {
            t: 0 for t in range(table.n_tenants)
        }
        self.pairs_by_tenant: Dict[int, int] = {
            t: 0 for t in range(table.n_tenants)
        }

    # ------------------------------------------------------------------ #
    def push(self, vecs, ts):  # pragma: no cover - guardrail
        raise NotImplementedError(
            "MultiTenantRuntime routes arrivals through submit()/flush()"
        )

    def submit(
        self, tenant: int, data: np.ndarray, ts: np.ndarray
    ) -> np.ndarray:
        """Admit one tenant's batch; returns its global uids.

        ``data`` is ``(b, d)`` float vectors (callers normalize), or
        ``(b, seq_len)`` int tokens in fused mode.  Nothing reaches the
        device until :meth:`flush`.  Raises
        :class:`~repro.runtime.router.TenantBackpressure` (admitting
        nothing) when the tenant's queue cap would be exceeded.
        """
        tenant = self.table.validate_id(tenant)
        ts = np.asarray(ts, np.float64).reshape(-1)
        if self.fused is not None:
            data = np.asarray(data, np.int32)
            if data.ndim != 2 or data.shape[1] != self.fused.seq_len:
                raise ValueError(
                    f"fused submissions must be (b, {self.fused.seq_len}) "
                    f"tokens, got {data.shape}"
                )
        else:
            data = np.asarray(data, np.float32)
            if data.ndim != 2 or data.shape[1] != self.cfg.d:
                raise ValueError(
                    f"submissions must be (b, {self.cfg.d}) vectors, "
                    f"got {data.shape}"
                )
        b = data.shape[0]
        if b != ts.shape[0]:
            raise ValueError(f"{b} rows but {ts.shape[0]} timestamps")
        if b == 0:
            return np.empty((0,), np.int32)
        uids = np.arange(self._next_uid, self._next_uid + b, dtype=np.int32)
        # the rows ride in the next dispatch or a later one
        with self.tracer.span("admit", self.spans_dispatched + 1):
            self.router.admit(tenant, data, ts, uids)  # all-or-nothing
        self._next_uid += b
        n = self._uid_tenant_n
        if n + b > self._uid_tenant_buf.size:
            grown = np.empty((max(2 * self._uid_tenant_buf.size, n + b),),
                             np.int32)
            grown[:n] = self._uid_tenant_buf[:n]
            self._uid_tenant_buf = grown
        self._uid_tenant_buf[n:n + b] = tenant
        self._uid_tenant_n = n + b
        self.submitted_by_tenant[tenant] += b
        return uids

    # ------------------------------------------------------------------ #
    def _dispatch(self, payload, ts, uids, sids, t_admit) -> None:
        """Pack one span of micro-batches and launch the device step."""
        cfg = self.cfg
        mb, span = cfg.micro_batch, self.span
        rows = span * mb
        n = payload.shape[0]
        assert n <= rows
        n_real = -(-n // mb)                     # micro-batches with any data
        pad = rows - n
        k = self.spans_dispatched + 1            # this dispatch's ordinal
        with self.tracer.span("coalesce", k):
            if self.fused is not None:
                pl = np.zeros((rows, self.fused.seq_len), np.int32)
            else:
                pl = np.zeros((rows, cfg.d), np.float32)
            pl[:n] = payload
            tq = np.full(rows, _EMPTY_T, np.float32)  # inert: all strips dead
            tq[:n] = ts
            if n and n_real * mb > n:
                # partial tail micro-batch: repeat its last valid timestamp
                # so the strip filter's extremes stay honest (pad_request
                # contract)
                tq[n:n_real * mb] = ts[-1]
            uq = np.full(rows, -1, np.int32)
            uq[:n] = uids
            sq = np.full(rows, -1, np.int32)
            sq[:n] = sids
            nvs = np.clip(n - mb * np.arange(span), 0, mb).astype(np.int32)

        with self.tracer.span("h2d", k):
            args = (
                jnp.asarray(pl.reshape(span, mb, -1)),
                jnp.asarray(tq.reshape(span, mb)),
                jnp.asarray(uq.reshape(span, mb)),
                jnp.asarray(sq.reshape(span, mb)),
            )
        with self.tracer.span("dispatch", k):
            if self.fused is not None:
                self.state, self.telem, bufs, masks = self._step(
                    self.fused.params, self.state, self.telem, *args, nvs
                )
            else:
                self.state, self.telem, bufs, masks = self._step(
                    self.state, self.telem, *args, nvs
                )
        self._dispatch_meta.append((sids, t_admit))
        self._pending.append(
            self._copier.submit(self._fetch, bufs, masks, nvs, k)
        )
        self.n_items += n
        # padding waste = inert rows inside *real* micro-batches (they ride
        # through the join); span-fill micro-batches are separate — their
        # strips are all dead, so they cost scan steps but no join work
        self.padded_rows += n_real * mb - n
        self.empty_micro_batches += self.span - n_real
        self.spans_dispatched = k
        # dense-equivalent traffic counts real micro-batches only (what the
        # dense path would actually have fetched for this data)
        self.bytes_dense_equiv += n_real * 4 * (
            mb * self._global_capacity() + mb * mb
        )

    def flush(self, final: bool = False) -> int:
        """Coalesce queued arrivals into micro-batches and dispatch them.

        Dispatches every *full* micro-batch (in span-sized scans; a short
        span rides out with inert empty micro-batches).  Rows short of a
        micro-batch stay queued for the next flush — unless ``final=True``,
        which pads the tail out (the end-of-stream / latency-deadline
        case).  Returns the number of real rows dispatched.
        """
        mb = self.cfg.micro_batch
        rows_span = mb * self.span
        sent = 0
        while len(self.router) >= rows_span:
            self._dispatch(*self._take(rows_span))
            sent += rows_span
        rem = len(self.router)
        take_n = rem if final else (rem // mb) * mb
        if take_n:
            self._dispatch(*self._take(take_n))
            sent += take_n
        return sent

    def _take(self, n: int):
        """Pop the next dispatch's rows from the router (the ``take``
        span)."""
        with self.tracer.span("take", self.spans_dispatched + 1):
            return self.router.take(n)

    # ------------------------------------------------------------------ #
    def _tenant_of(self, uids: np.ndarray) -> np.ndarray:
        return self._uid_tenant_buf[:self._uid_tenant_n][uids]

    def _rows(self, recs: list):
        """Assemble joined records, tracking the uid range each drain's
        masks cover so per-tenant attribution stays aligned however the
        caller mixes global and per-tenant drains."""
        ua, ub, sc, mask = self._assemble(recs)
        self._mask_uid0 += mask.shape[0]
        return ua, ub, sc, mask

    def drain_arrays(self, return_masks: bool = False):
        """As :meth:`StreamEngineBase.drain_arrays`; observes the returned
        rows' latency."""
        ua, ub, sc, mask = self._rows(self._join())
        self._observe_return()
        if return_masks:
            return ua, ub, sc, mask
        return ua, ub, sc

    def drain_by_tenant(
        self, return_masks: bool = False
    ) -> Dict[int, Tuple[np.ndarray, ...]]:
        """Everything emitted since the last drain, grouped by stream.

        Returns ``{tenant: (uid_a, uid_b, score)}`` (uids are global; map
        back with the uids :meth:`submit` returned).  With
        ``return_masks=True`` each tuple gains the tenant's per-row match
        masks, aligned with its dispatched uids in admission order.  Pair
        attribution uses ``uid_a``'s stream — the join's stream-equality
        mask guarantees ``uid_b`` agrees.

        The wait on the copy thread is the ``flush_wait`` span; ``emit``
        times what follows it: the grouping, and the latency observation
        of the rows being returned.
        """
        recs = self._join()
        with self.tracer.span("emit", self.spans_dispatched):
            out = self._group_by_tenant(*self._rows(recs), return_masks)
            self._observe_return()
        return out

    def _group_by_tenant(
        self, ua, ub, sc, mask, return_masks: bool
    ) -> Dict[int, Tuple[np.ndarray, ...]]:
        mask_uids = np.arange(
            self._mask_uid0 - mask.shape[0], self._mask_uid0, dtype=np.int64
        )
        k = self.table.n_tenants
        tids = np.arange(k)

        def group(keys, *values):
            # one stable sort + K boundary lookups — O(n log n + K), not a
            # full-array scan per tenant; stable keeps emission/admission
            # order within each tenant
            order = np.argsort(keys, kind="stable")
            ks = keys[order]
            lo = np.searchsorted(ks, tids)
            hi = np.searchsorted(ks, tids, side="right")
            return [
                tuple(v[order[a:b]] for v in values)
                for a, b in zip(lo, hi)
            ]

        pair_t = self._tenant_of(ua) if ua.size else np.empty((0,), np.int32)
        mask_t = (
            self._tenant_of(mask_uids) if mask.size else np.empty((0,), np.int32)
        )
        pair_groups = group(pair_t, ua, ub, sc)
        mask_groups = group(mask_t, mask) if return_masks else None
        out: Dict[int, Tuple[np.ndarray, ...]] = {}
        for t in range(k):
            rec: Tuple[np.ndarray, ...] = pair_groups[t]
            self.pairs_by_tenant[t] += rec[0].size
            if return_masks:
                rec = rec + mask_groups[t]
            out[t] = rec
        return out

    # ------------------------------------------------------------------ #
    def tenant_stats(self, tenant: int) -> dict:
        tenant = self.table.validate_id(tenant)
        th, lm = self.table.spec(tenant)
        by_tenant = self.overflow_by_tenant
        return {
            "theta": th,
            "lam": lm,
            "submitted": self.submitted_by_tenant[tenant],
            "queued": self.router.queued_by_tenant[tenant],
            "pairs_drained": self.pairs_by_tenant[tenant],
            # this tenant's live items lost to overwrite (victim-side
            # attribution, DESIGN.md §11) — no longer the global-only count
            "window_overflow": int(by_tenant[tenant]),
            "quota": (
                None if self.cfg.quotas is None
                else int(self.cfg.quotas[tenant])
                * self.engine.global_capacity(self.cfg) // self.cfg.capacity
            ),
        }

    def _global_capacity(self) -> int:
        return self.engine.global_capacity(self.cfg)

    # ------------------------------------------------------------------ #
    def _observe_return(self) -> None:
        """Observe admission→return latency, one observation per row, for
        the rows a drain is handing the caller now.

        A drain joins every pending dispatch (``push()`` is disabled, so
        each one queued a ``(sids, t_admit)`` entry in :meth:`_dispatch`),
        so the rows returned are those of every entry queued.
        """
        t_return = time.monotonic()      # the router's admission clock
        for sids, t_admit in self._dispatch_meta:
            lat = np.maximum(t_return - t_admit, 0.0)
            self._lat_hist.observe_many(lat)
            for t in np.unique(sids):
                self._lat_by_tenant[int(t)].observe_many(lat[sids == t])
        self._dispatch_meta.clear()

    def _publish_runtime_metrics(self, reg) -> None:
        """Snapshot-time collector: router/runtime/per-tenant counters
        under the namespaced schema (DESIGN.md §12), alongside the engine
        collector registered by :class:`StreamEngineBase`."""
        rt = self.router.telemetry
        c, g = reg.counter, reg.gauge
        c("router/items_admitted").set(rt.items_admitted)
        c("router/items_rejected").set(rt.items_rejected)
        c("router/items_dispatched").set(rt.items_dispatched)
        c("router/queue_delay_sum_s").set(rt.queue_delay_sum_s)
        g("router/queue_delay_max_s").set(rt.queue_delay_max_s)
        g("router/items_queued").set(len(self.router))
        reg.info("runtime/eviction").set(self.cfg.eviction)
        g("runtime/n_tenants").set(self.table.n_tenants)
        c("runtime/spans_dispatched").set(self.spans_dispatched)
        c("runtime/padded_rows").set(self.padded_rows)
        c("runtime/empty_micro_batches").set(self.empty_micro_batches)
        for t in range(self.table.n_tenants):
            c(f"tenant/{t}/submitted").set(self.submitted_by_tenant[t])
            g(f"tenant/{t}/queued").set(self.router.queued_by_tenant[t])
            c(f"tenant/{t}/pairs_drained").set(self.pairs_by_tenant[t])
        publish_flat(reg, self.engine.metrics_extra(self.state, self.telem))

    def stats(self) -> dict:
        """Legacy flat stats — a compatibility view derived from one
        registry snapshot, so every value equals its namespaced metric."""
        snap = self.registry.snapshot()
        disp = snap["router/items_dispatched"]
        padded = snap["runtime/padded_rows"]
        runtime_view = {
            "eviction": snap["runtime/eviction"],
            "n_tenants": snap["runtime/n_tenants"],
            "items_queued": snap["router/items_queued"],
            "items_rejected": snap["router/items_rejected"],
            "spans_dispatched": snap["runtime/spans_dispatched"],
            "padded_rows": padded,
            "empty_micro_batches": snap["runtime/empty_micro_batches"],
            "padding_waste": padded / max(padded + disp, 1),
            "queue_delay_mean_s": snap["router/queue_delay_sum_s"]
            / max(disp, 1),
            "queue_delay_max_s": snap["router/queue_delay_max_s"],
        }
        shard = shard_view(snap) if "engine/n_shards" in snap else {}
        return merge_disjoint(
            self._legacy_engine_view(snap), shard, runtime_view
        )
