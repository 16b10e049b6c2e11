"""Device-resident streaming join engine: one jit call per request batch.

The previous host driver (:class:`repro.core.blocked.BlockedStreamJoiner`)
re-entered jit once per micro-batch, fetched the dense ``(B, capacity)`` +
``(B, B)`` score matrices to the host, and extracted pairs in a Python
``np.nonzero`` loop — throughput was bounded by PCIe and the GIL, not the
MXU.  The engine restores the paper's invariant that candidate generation,
time filtering, and verification never leave the index's hot loop:

  * the ring-buffer :class:`WindowState` is carried through a single
    ``lax.scan`` over micro-batches (one jit call — and one device
    round-trip of *control*, not data — per request batch, donated state);
  * emission is **hierarchically compacted** on device (DESIGN.md §3): each
    kernel tile selects its own ≥ θ entries into a ``(tile_k,)`` candidate
    buffer (level 1, inside the join), and a segmented scan + gather merges
    the per-tile buffers into the global ``(max_pairs,)``
    :class:`~repro.kernels.sssj_join.compact.PairBuffer` (level 2) — the
    dense ``(B, capacity)`` score matrix is never written to HBM and
    nothing ever sorts ``O(B·capacity)`` elements.  The PR-1 dense pipeline
    survives behind ``emit_dense=True`` as the test oracle;
  * a per-row **match mask** (``row i has ≥ θ match``, exact even under
    candidate overflow) rides along for consumers that only need
    membership, not pairs (e.g. the dedup filter) — O(B) with no
    truncation risk;
  * the host drain is asynchronous *and off-thread*: :meth:`StreamEngine
    .push` dispatches the scan and hands the device buffers to a
    single-worker copy thread, so the D2H copies of batch *n* overlap the
    device compute of batch *n+1*; pairs materialize on the host only when
    the caller asks (:meth:`drain_arrays` / :meth:`drain_pairs`).

Telemetry (pruning iterations, emitted pair counts, and the per-level drop
counters — ``tile_k`` overflow vs ``max_pairs`` overflow) accumulates
in-carry as device scalars and is summed on the host only at
:meth:`stats` time.

The scan body (:func:`make_micro_step`) and the host facade
(:class:`StreamEngineBase`) are shared with the sharded fan-out
(:mod:`repro.engine.sharded`): the sharded variant differs only in which
rows each device ingests, in emitting self-join pairs on one shard, and in
adding a third merge level (per-shard buffers → one global budget).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.similarity import time_horizon
from ..obs import MetricsRegistry, Span, SpanTracer
from ..kernels.sssj_join import (
    PairBuffer,
    compact_pairs,
    merge_candidates,
    sssj_join_candidates,
    sssj_join_tiles,
)
from .window import (
    EVICTION_POLICIES,
    WindowState,
    init_window,
    push_with_overflow,
)

__all__ = [
    "EngineConfig",
    "EngineTelemetry",
    "StreamEngine",
    "StreamEngineBase",
    "make_batch_step",
    "make_micro_step",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    theta: float
    lam: float
    capacity: int
    d: int
    micro_batch: int = 128       # scan step size; requests are padded up
    max_pairs: int = 4096        # compacted-emission capacity per micro-batch
    tile_k: int = 256            # level-1 candidates kept per kernel tile
    shard_k: Optional[int] = None  # per-shard merge capacity (sharded engine);
    #                                None → max_pairs
    block_q: int = 128
    block_w: int = 128
    chunk_d: int = 128
    emit_dense: bool = False     # PR-1 dense-matrix compaction (test oracle)
    join_impl: Optional[str] = None  # candidate impl: pallas/scan/dense; None=auto
    use_ref: bool = False        # route joins through the jnp oracle
    interpret: Optional[bool] = None
    eviction: str = "oldest"     # write-slot policy: oldest/dead/quota (§11)
    quotas: Optional[Tuple[int, ...]] = None  # per-stream slots (quota policy);
    #                                           sums to capacity (per shard)
    l2_gate: Optional[bool] = None  # L2/prefix strip-summary gate (§13):
    #   True = on, False = off, None = auto (on for every hierarchical
    #   non-dense join path, where the gate can actually skip launches)

    def __post_init__(self) -> None:
        """Reject configurations that would only fail later as opaque shape
        or tracer errors deep inside the jitted scan."""
        if not 0.0 < self.theta <= 1.0:
            raise ValueError(f"theta must be in (0, 1], got {self.theta}")
        if self.lam < 0.0:
            raise ValueError(f"lam must be ≥ 0, got {self.lam}")
        for name in ("capacity", "d", "micro_batch", "max_pairs", "tile_k",
                     "block_q", "block_w", "chunk_d"):
            v = getattr(self, name)
            if (isinstance(v, bool) or not isinstance(v, (int, np.integer))
                    or v < 1):
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        # a feature width below one chunk is one chunk: chunk_d ≤ d always
        # holds, so the window's strip summary and every join agree on the
        # chunking and no join routes around the kernel for a narrow d
        object.__setattr__(self, "chunk_d", min(self.chunk_d, self.d))
        if self.shard_k is not None and self.shard_k < 1:
            raise ValueError(f"shard_k must be ≥ 1, got {self.shard_k}")
        if self.micro_batch > self.capacity:
            raise ValueError(
                f"micro_batch ({self.micro_batch}) exceeds window capacity "
                f"({self.capacity}): a single micro-batch would overwrite "
                f"its own arrivals; raise capacity or lower micro_batch"
            )
        # the join pads rows/features up to block multiples, so any
        # block_q/block_w/chunk_d is shape-safe — but a padded query tile
        # must still exist: blocks have to fit the padded micro-batch,
        # i.e. be at most the next block_q-multiple of micro_batch (always
        # true) and positive (checked above).  What CAN break downstream
        # is an impl contradiction:
        if self.use_ref and self.join_impl in ("pallas", "scan"):
            raise ValueError(
                f"use_ref routes joins through the dense jnp oracle and "
                f"contradicts join_impl={self.join_impl!r}; drop one"
            )
        if self.join_impl not in (None, "pallas", "scan", "dense"):
            raise ValueError(
                f"join_impl must be one of None/'pallas'/'scan'/'dense', "
                f"got {self.join_impl!r}"
            )
        if self.l2_gate is True and (
            self.emit_dense or self.use_ref or self.join_impl == "dense"
        ):
            raise ValueError(
                "l2_gate=True requires a gated join path; the dense oracle "
                "(emit_dense / use_ref / join_impl='dense') never consults "
                "the gate — drop l2_gate or leave it None"
            )
        if self.eviction not in EVICTION_POLICIES:
            raise ValueError(
                f"eviction must be one of {EVICTION_POLICIES}, "
                f"got {self.eviction!r}"
            )
        if self.quotas is not None:
            if self.eviction != "quota":
                raise ValueError(
                    f"quotas are only meaningful under eviction='quota' "
                    f"(got eviction={self.eviction!r})"
                )
            qs = tuple(self.quotas)
            for i, v in enumerate(qs):
                if (isinstance(v, bool) or not isinstance(v, (int, np.integer))
                        or v < 1):
                    raise ValueError(
                        f"quotas[{i}] must be a positive int, got {v!r}"
                    )
            if sum(int(v) for v in qs) != self.capacity:
                raise ValueError(
                    f"quotas must sum to capacity ({self.capacity}), got "
                    f"{sum(int(v) for v in qs)} over {len(qs)} streams"
                )
            object.__setattr__(self, "quotas", tuple(int(v) for v in qs))
        elif self.eviction == "quota":
            raise ValueError("eviction='quota' requires a quotas table")

    @property
    def tau(self) -> float:
        return time_horizon(self.theta, self.lam)

    @property
    def gate_enabled(self) -> bool:
        """Whether the window state carries a strip summary and the join
        runs the L2/prefix pre-launch gate (DESIGN.md §13)."""
        if self.l2_gate is not None:
            return bool(self.l2_gate)
        return not (
            self.emit_dense or self.use_ref or self.join_impl == "dense"
        )

    @property
    def n_lanes(self) -> Optional[int]:
        """Stream-lane count the window state must carry for this config
        (from the quota table; the multi-tenant runtime widens it to its
        tenant count so per-victim overflow attribution works under any
        policy)."""
        return None if self.quotas is None else len(self.quotas)

    def quotas_device(self) -> Optional[jax.Array]:
        """The quota table as a device array (``None`` off-quota) — what
        the write-slot policy consumes inside the jitted step."""
        return (
            None if self.quotas is None
            else jnp.asarray(self.quotas, jnp.int32)
        )

    @property
    def join_kwargs(self) -> dict:
        """kwargs for the dense-emission join (``emit_dense`` oracle path)."""
        return dict(
            theta=self.theta, lam=self.lam, block_q=self.block_q,
            block_w=self.block_w, chunk_d=self.chunk_d, use_ref=self.use_ref,
            interpret=self.interpret,
        )

    @property
    def candidate_kwargs(self) -> dict:
        """kwargs for the hierarchical join (default path)."""
        impl = self.join_impl
        if impl is None and self.use_ref:
            impl = "dense"
        return dict(
            theta=self.theta, lam=self.lam, tile_k=self.tile_k,
            block_q=self.block_q, block_w=self.block_w, chunk_d=self.chunk_d,
            impl=impl, interpret=self.interpret,
        )


class EngineTelemetry(NamedTuple):
    """Device-resident counters accumulated in the scan carry.

    ``chunks``/``tiles`` count the *window* join only (self-join tiles have
    near-zero time deltas and would dilute the pruning signal) — the same
    accounting the pre-engine driver used, so ``benchmarks/tile_pruning.py``
    numbers stay comparable across versions.  Drops are split by level so
    an operator can tell an undersized ``tile_k`` from an undersized
    ``max_pairs``.
    """

    chunks: jax.Array        # () i32 — d-chunks executed (pruning telemetry)
    tiles: jax.Array         # () i32 — window-join tiles visited
    pairs: jax.Array         # () i32 — pairs emitted (compacted, post-merge)
    dropped: jax.Array       # () i32 — pairs lost to the max_pairs budget
    dropped_tile: jax.Array  # () i32 — pairs lost to per-tile/per-shard caps
    tiles_skipped_time: jax.Array  # () i32 — gate kills by the time bound
    tiles_skipped_l2: jax.Array    # () i32 — gate kills by the value bounds
    strips_survived: jax.Array     # () i32 — strips the gated walk visited


def init_telemetry() -> EngineTelemetry:
    # distinct buffers: the step donates the whole pytree, and donating one
    # buffer twice is an error
    return EngineTelemetry(
        *(jnp.zeros((), jnp.int32) for _ in EngineTelemetry._fields)
    )


def pad_request(vecs, ts, next_uid: int, micro_batch: int):
    """Host-side request prep shared by both engines: assign uids, pad the
    batch to a micro-batch multiple (pad rows carry ``uid = -1`` so the
    kernel order mask silences them; pad timestamps repeat the last valid
    one), and reshape into scan inputs.

    Returns ``(uq, qs, tqs, uqs, nvs)``: the assigned uids ``(b,)`` plus
    the scan stacks ``(n_micro, mb, ·)`` and valid-row counts ``(n_micro,)``
    (``nvs`` stays a host array — the drain needs it to unpad row masks).
    """
    vecs = np.asarray(vecs, np.float32)
    ts = np.asarray(ts, np.float32).reshape(-1)
    b = vecs.shape[0]
    uq = np.arange(next_uid, next_uid + b, dtype=np.int32)
    mb = micro_batch
    n_micro = -(-b // mb)
    pad = n_micro * mb - b
    if pad:
        vecs = np.concatenate([vecs, np.zeros((pad, vecs.shape[1]), np.float32)])
        ts = np.concatenate([ts, np.full(pad, ts[-1], np.float32)])
        uq_in = np.concatenate([uq, np.full(pad, -1, np.int32)])
    else:
        uq_in = uq
    nvs = np.full(n_micro, mb, np.int32)
    nvs[-1] = mb - pad
    return (
        uq,
        jnp.asarray(vecs.reshape(n_micro, mb, -1)),
        jnp.asarray(ts.reshape(n_micro, mb)),
        jnp.asarray(uq_in.reshape(n_micro, mb)),
        nvs,
    )


def make_micro_step(
    cfg: EngineConfig,
    ingest: Callable,
    self_mask: Optional[Callable] = None,
    tenant_lookup: Optional[Callable] = None,
    embed_fn: Optional[Callable] = None,
):
    """Build the scan body shared by the single-device, sharded, and
    multi-tenant engines.

    ``ingest(state, q, tq, uq, n_valid, t_max[, sq]) → new state`` pushes
    this micro-batch (or the shard's slice of it) into the ring with
    overflow accounting; ``self_mask`` optionally suppresses the
    within-batch candidates (``PairCandidates → PairCandidates``; the
    sharded engine emits them on one shard only).  The step emits
    ``(PairBuffer, row_mask (mb,) bool)`` per micro-batch.

    Multi-tenant mode (DESIGN.md §9): when ``tenant_lookup`` is given, the
    scan inputs gain a ``sq (mb,)`` stream-id lane (xs becomes a 5-tuple),
    the window's ``sids`` lane is threaded into both joins as the
    stream-equality mask, and ``tenant_lookup(sq) → (theta_q, lam_q) |
    None`` supplies the per-row thresholds from the tenant table (return
    ``None`` for uniform tenants).  ``embed_fn`` optionally maps the raw
    per-micro-batch payload (e.g. token ids) to unit vectors *inside* the
    same program — the fused embed→join path.
    """
    kw = cfg.join_kwargs
    ckw = cfg.candidate_kwargs
    multi = tenant_lookup is not None
    if cfg.emit_dense and self_mask is not None:
        raise ValueError("emit_dense oracle path is single-device only")
    if cfg.emit_dense and (multi or embed_fn is not None):
        raise ValueError(
            "the emit_dense oracle path is single-tenant and takes vectors; "
            "multi-tenant / fused-embed runs use the hierarchical path"
        )

    def micro_step(carry, xs):
        state, telem = carry
        if multi:
            q, tq, uq, sq, n_valid = xs
            sq = sq.astype(jnp.int32)
        else:
            q, tq, uq, n_valid = xs
            sq = None
        if embed_fn is not None:
            q = embed_fn(q)
        tq = tq.astype(jnp.float32)
        uq = uq.astype(jnp.int32)
        # join vs the window and within the micro-batch; padded rows carry
        # uid = -1 so the kernel's order mask silences them everywhere
        if cfg.emit_dense:
            # PR-1 oracle: dense (mb, capacity+mb) scores + global top-k
            s_win, it_win, _ = sssj_join_tiles(
                q, state.vecs, tq, state.ts, uq, state.uids, **kw
            )
            s_self, _, _ = sssj_join_tiles(q, q, tq, tq, uq, uq, **kw)
            scores = jnp.concatenate([s_win, s_self], axis=1)
            uw_all = jnp.concatenate([state.uids, uq])
            buf = compact_pairs(scores, uq, uw_all, max_pairs=cfg.max_pairs)
            row_mask = jnp.any(scores > 0.0, axis=1)
            gate_stats = jnp.zeros((3,), jnp.int32)
        else:
            # hierarchical: per-tile level-1 candidates → segmented merge;
            # no dense score matrix exists anywhere on this path
            if multi:
                per_row = tenant_lookup(sq)
                theta_q, lam_q = per_row if per_row is not None else (None, None)
                win_kw = dict(sq=sq, sw=state.sids,
                              theta_q=theta_q, lam_q=lam_q)
                self_kw = dict(sq=sq, sw=sq, theta_q=theta_q, lam_q=lam_q)
            else:
                win_kw = self_kw = {}
            # the window join consults the strip summary (None = ungated);
            # the self-join never does — its strips are this micro-batch,
            # freshly scored either way
            jw = sssj_join_candidates(
                q, state.vecs, tq, state.ts, uq, state.uids,
                summary=state.summary, **ckw, **win_kw
            )
            js = sssj_join_candidates(q, q, tq, tq, uq, uq, **ckw, **self_kw)
            cs = js.cands if self_mask is None else self_mask(js.cands)
            # window tiles first, then the self-join's: each source keeps
            # the slot layout its join wrote (no slab is copied)
            buf = merge_candidates((jw.cands, cs), max_pairs=cfg.max_pairs)
            row_mask = jw.row_mask | js.row_mask
            it_win = jw.iters
            gate_stats = (
                jw.gate_stats if jw.gate_stats is not None
                else jnp.zeros((3,), jnp.int32)
            )

        # newest valid arrival — the reference point for live-slot overflow
        lanes = jnp.arange(q.shape[0], dtype=jnp.int32)
        t_max = jnp.max(jnp.where(lanes < n_valid, tq, -jnp.inf))
        if multi:
            new_state = ingest(state, q, tq, uq, n_valid, t_max, sq)
        else:
            new_state = ingest(state, q, tq, uq, n_valid, t_max)
        new_telem = EngineTelemetry(
            chunks=telem.chunks + it_win.sum(),
            tiles=telem.tiles + it_win.size,
            pairs=telem.pairs + buf.n_pairs,
            dropped=telem.dropped + buf.n_dropped,
            dropped_tile=telem.dropped_tile + buf.n_dropped_tile,
            tiles_skipped_time=telem.tiles_skipped_time + gate_stats[0],
            tiles_skipped_l2=telem.tiles_skipped_l2 + gate_stats[1],
            strips_survived=telem.strips_survived + gate_stats[2],
        )
        return (new_state, new_telem), (buf, row_mask)

    return micro_step


def make_batch_step(cfg: EngineConfig):
    """Build the jitted request-batch step (single device).

    Signature: ``(state, telem, qs, tqs, uqs, nvs) → (state, telem, bufs,
    masks)`` with ``qs (n_micro, mb, d)``, ``tqs/uqs (n_micro, mb)``,
    ``nvs (n_micro,)`` valid-row counts, ``bufs`` a :class:`PairBuffer`
    whose leaves are stacked over micro-batches, and ``masks (n_micro, mb)``
    the per-row match masks.  State and telemetry are donated.
    """
    tau = cfg.tau
    quo = cfg.quotas_device()

    def ingest(state, q, tq, uq, n_valid, t_max):
        return push_with_overflow(
            state, q, tq, uq, n_valid, t_max, tau,
            eviction=cfg.eviction, quotas=quo,
            summary_block_w=cfg.block_w, summary_chunk_d=cfg.chunk_d,
        )

    micro_step = make_micro_step(cfg, ingest)

    def batch_step(state, telem, qs, tqs, uqs, nvs):
        (state, telem), (bufs, masks) = jax.lax.scan(
            micro_step, (state, telem), (qs, tqs, uqs, nvs)
        )
        return state, telem, bufs, masks

    return jax.jit(batch_step, donate_argnums=(0, 1))


class StreamEngineBase:
    """Host facade shared by the single-device and sharded engines.

    Subclasses set ``state``, ``telem``, and ``_step`` in ``__init__`` and
    override :meth:`_global_capacity`.  Compacted buffers carry one merged
    segment per micro-batch (the sharded engine merges its shards down to
    one global buffer before they reach the host); ``drain_arrays`` still
    handles multi-segment layouts through the trailing-axis reshape.

    D2H copies run on a single-worker copy thread: ``push`` dispatches the
    device step and enqueues the output buffers; the worker materializes
    them to numpy (double-buffered — device compute of the next push
    overlaps the copy of the previous one); ``drain_*`` only joins on the
    already-copied results.

    ``tracer`` times the host stages (:mod:`repro.obs.spans`) into the
    registry; ``spans_dispatched`` counts device dispatches and is the
    ordinal each stage's annotation carries.
    """

    def __init__(
        self, cfg: EngineConfig, registry: Optional[MetricsRegistry] = None
    ) -> None:
        # cfg invariants are enforced by EngineConfig.__post_init__
        self.cfg = cfg
        self._next_uid = 0
        # futures of host-materialized (bufs, masks, nvs, nbytes, wait_s,
        # d2h_s) records, in dispatch order
        self._pending: List[concurrent.futures.Future] = []
        self._copier = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sssj-drain"
        )
        self.n_items = 0
        # host↔device traffic accounting (what the dense path would have
        # moved vs what the compacted path actually moves)
        self.bytes_to_host = 0
        self.bytes_dense_equiv = 0
        # unified observability surface (DESIGN.md §12): engine counters
        # publish under engine/… at snapshot time; stats() is a
        # compatibility view over the same snapshot
        self.registry = registry if registry is not None else MetricsRegistry()
        self.registry.register_collector(self._publish_metrics)
        self.tracer = SpanTracer(self.registry)
        self.spans_dispatched = 0

    def _global_capacity(self) -> int:
        return self.cfg.capacity

    # ------------------------------------------------------------------ #
    def push(self, vecs: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Feed one request batch; returns the uids assigned to it.

        Does NOT synchronize with the device — call :meth:`drain_pairs` /
        :meth:`drain_arrays` to collect emitted pairs.  A new batch size
        triggers one recompile of the scan.
        """
        b = np.asarray(vecs).shape[0]
        if b == 0:
            return np.empty((0,), np.int32)
        uq, qs, tqs, uqs, nvs = pad_request(
            vecs, ts, self._next_uid, self.cfg.micro_batch
        )
        self._next_uid += b
        self.n_items += b
        self.spans_dispatched += 1
        n = self.spans_dispatched
        with self.tracer.span("dispatch", n):
            self.state, self.telem, bufs, masks = self._step(
                self.state, self.telem, qs, tqs, uqs, nvs
            )
        self._pending.append(
            self._copier.submit(self._fetch, bufs, masks, nvs, n)
        )
        # the dense path would have fetched (mb, capacity) + (mb, mb) f32
        # score matrices per micro-batch
        mb = self.cfg.micro_batch
        self.bytes_dense_equiv += qs.shape[0] * 4 * (
            mb * self._global_capacity() + mb * mb
        )
        return uq

    @staticmethod
    def _fetch(bufs: PairBuffer, masks, nvs: np.ndarray, dispatch: int):
        """Worker-thread D2H: materialize one dispatch's device outputs.

        Times two stages under their annotations: ``device_wait``, until
        the step's outputs are ready, and ``d2h``, their copy to host
        memory.  The durations ride back with the record; the caller
        thread records them (:meth:`_join`).
        """
        with Span("device_wait", dispatch) as wait:
            jax.block_until_ready((bufs, masks))
        with Span("d2h", dispatch) as d2h:
            host = jax.tree.map(np.asarray, bufs)
            masks = np.asarray(masks)
        nbytes = sum(x.nbytes for x in host) + masks.nbytes
        return host, masks, nvs, nbytes, wait.seconds, d2h.seconds

    # ------------------------------------------------------------------ #
    def _join(self) -> list:
        """Wait for the copy thread's record of every pending dispatch (the
        ``flush_wait`` span) and record each one's copy-thread stages."""
        with self.tracer.span("flush_wait", self.spans_dispatched):
            recs = [f.result() for f in self._pending]
        self._pending.clear()
        for *_, nbytes, wait_s, d2h_s in recs:
            self.bytes_to_host += nbytes
            self.tracer.record("device_wait", wait_s)
            self.tracer.record("d2h", d2h_s)
        return recs

    @staticmethod
    def _assemble(recs: list):
        """The pairs and row masks of joined records, in stream order."""
        ua_all, ub_all, sc_all, mk_all = [], [], [], []
        for bufs, masks, nvs, *_ in recs:
            n = np.asarray(bufs.n_pairs)
            n = n.reshape(n.shape[0], -1)             # (n_micro, n_segments)
            n_micro, n_seg = n.shape
            width = bufs.uid_a.reshape(n_micro, -1).shape[1] // n_seg
            sel = np.arange(width)[None, None, :] < n[:, :, None]
            # row-major (micro, segment, rank) flatten == stream order
            ua_all.append(bufs.uid_a.reshape(n_micro, n_seg, width)[sel])
            ub_all.append(bufs.uid_b.reshape(n_micro, n_seg, width)[sel])
            sc_all.append(bufs.score.reshape(n_micro, n_seg, width)[sel])
            lanes = np.arange(masks.shape[1])[None, :]
            mk_all.append(masks[lanes < nvs[:, None]])
        if not ua_all:
            z = np.empty((0,), np.int32)
            return z, z.copy(), np.empty((0,), np.float32), np.empty((0,), bool)
        return (
            np.concatenate(ua_all),
            np.concatenate(ub_all),
            np.concatenate(sc_all),
            np.concatenate(mk_all).astype(bool),
        )

    def drain_arrays(
        self, return_masks: bool = False
    ) -> Tuple[np.ndarray, ...]:
        """Collect everything emitted since the last drain.

        Returns ``(uid_a, uid_b, score)`` arrays for every pair (uid_a is
        the newer item).  With ``return_masks=True`` a fourth array rides
        along: a ``(n_items,)`` bool per-row match mask, aligned with the
        uids handed out by the intervening :meth:`push` calls — exact even
        when pair emission overflowed (it derives from level-1 counts,
        DESIGN.md §3).
        """
        ua, ub, sc, mk = self._assemble(self._join())
        if return_masks:
            return ua, ub, sc, mk
        return ua, ub, sc

    def drain_pairs(self) -> List[Tuple[int, int, float]]:
        """Compatibility drain: list of ``(uid_a, uid_b, score)`` tuples."""
        ua, ub, sc = self.drain_arrays()
        return list(zip(ua.tolist(), ub.tolist(), sc.tolist()))

    def close(self) -> None:
        """Release the drain worker thread; undrained copies are abandoned
        (the worker finishes any copy already in flight, then exits)."""
        self._copier.shutdown(wait=False)

    def __del__(self) -> None:
        try:
            self._copier.shutdown(wait=False)
        except Exception:
            pass

    # ------------------------------------------------------------------ #
    @property
    def overflow(self) -> int:
        """Live ring slots overwritten (window undersized), all shards."""
        return int(np.asarray(self.state.overflow).sum())

    @property
    def pairs_dropped(self) -> int:
        """Total pairs lost to emission capacity at any level (tile_k,
        per-shard, or max_pairs) — the undersized-buffer signal."""
        t = self.telem
        return int(
            np.asarray(t.dropped).sum() + np.asarray(t.dropped_tile).sum()
        )

    @property
    def overflow_by_tenant(self) -> Optional[np.ndarray]:
        """Per-victim-stream live overwrites ``(n_lanes,)``, summed over
        shards; ``None`` when the state carries no stream lanes."""
        lo = self.state.lane_overflow
        if lo is None:
            return None
        arr = np.asarray(lo)
        return arr.reshape(-1, arr.shape[-1]).sum(axis=0)

    def _publish_metrics(self, reg: MetricsRegistry) -> None:
        """Snapshot-time collector: engine counters under ``engine/…``,
        per-victim-stream overflow under ``tenant/<k>/…`` (DESIGN.md §12).
        Device telemetry is summed here exactly as the legacy ``stats()``
        did, so registry and legacy values are the same numbers."""
        t = jax.tree.map(lambda x: int(np.asarray(x).sum()), self.telem)
        c = reg.counter
        c("engine/n_items").set(self.n_items)
        c("engine/chunks_executed").set(t.chunks)
        c("engine/tiles_total").set(t.tiles)
        c("engine/pairs_emitted").set(t.pairs)
        c("engine/pairs_dropped").set(t.dropped + t.dropped_tile)
        c("engine/pairs_dropped_budget").set(t.dropped)
        c("engine/pairs_dropped_tile").set(t.dropped_tile)
        c("engine/window_overflow").set(self.overflow)
        c("engine/bytes_to_host").set(self.bytes_to_host)
        c("engine/bytes_dense_equiv").set(self.bytes_dense_equiv)
        # L2/prefix gate counters (DESIGN.md §13); tiles_total repeats the
        # window-join tile count so skip fractions are self-contained
        c("engine/prune/tiles_total").set(t.tiles)
        c("engine/prune/tiles_skipped_time").set(t.tiles_skipped_time)
        c("engine/prune/tiles_skipped_l2").set(t.tiles_skipped_l2)
        c("engine/prune/strips_survived").set(t.strips_survived)
        by_tenant = self.overflow_by_tenant
        if by_tenant is not None:
            for k, v in enumerate(by_tenant.tolist()):
                c(f"tenant/{k}/window_overflow").set(int(v))

    @staticmethod
    def _legacy_engine_view(snap: dict) -> dict:
        """The pre-registry ``stats()`` key vocabulary, derived from a
        registry snapshot (the compatibility view, DESIGN.md §12)."""
        out = {
            "n_items": snap["engine/n_items"],
            "chunks_executed": snap["engine/chunks_executed"],
            "tiles_total": snap["engine/tiles_total"],
            "pairs_emitted": snap["engine/pairs_emitted"],
            "pairs_dropped": snap["engine/pairs_dropped"],
            "pairs_dropped_budget": snap["engine/pairs_dropped_budget"],
            "pairs_dropped_tile": snap["engine/pairs_dropped_tile"],
            "window_overflow": snap["engine/window_overflow"],
            "bytes_to_host": snap["engine/bytes_to_host"],
            "bytes_dense_equiv": snap["engine/bytes_dense_equiv"],
        }
        by_tenant = []
        while f"tenant/{len(by_tenant)}/window_overflow" in snap:
            by_tenant.append(snap[f"tenant/{len(by_tenant)}/window_overflow"])
        if by_tenant:
            out["window_overflow_by_tenant"] = by_tenant
        return out

    def metrics(self) -> dict:
        """The namespaced registry snapshot (the primary stats surface)."""
        return self.registry.snapshot()

    def stats(self) -> dict:
        return self._legacy_engine_view(self.registry.snapshot())


class StreamEngine(StreamEngineBase):
    """Single-device scan-pipelined engine over one ring window."""

    def __init__(self, cfg: EngineConfig) -> None:
        super().__init__(cfg)
        self.state: WindowState = init_window(
            cfg.capacity, cfg.d, n_lanes=cfg.n_lanes, eviction=cfg.eviction,
            summary_block_w=cfg.block_w if cfg.gate_enabled else None,
            summary_chunk_d=cfg.chunk_d,
        )
        self.telem = init_telemetry()
        self._step = make_batch_step(cfg)
