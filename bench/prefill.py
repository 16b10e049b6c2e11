"""Steady-state window: build the service and fill its ring from the seed.

A deployment's ring is full.  Streaming a whole ring of arrivals through
``submit``/``flush`` before every run would take minutes, so set-up writes
the arrivals that precede the measured window straight into the ring, on
the device, as if they had been streamed: the same rows, timestamps, uids,
tenant lanes and strip summaries.  The rows come from :mod:`bench.gen`;
the state is built with the program's own window write
(``push_with_overflow``) and strip summary (``summarize_strips``).

A service on a mesh (a configuration with ``shards`` > 1) keeps one ring
shard per device.  Its prefill deals arrival ``i`` to shard ``i mod P``, as
the sharded step does with every full micro-batch, and writes each shard
inside a ``shard_map``: every device makes and writes only its own rows,
with its own cursor, overflow and policy lanes, and summarizes its own
strips.  No device ever holds the global window.

This is the one module of the benchmark that reads the program's
internals: the runtime's window pytree, its engine facade (``mesh``,
``axis``, ``n_shards`` of a sharded one) and the uid / tenant / local-id
bookkeeping of ``MultiTenantRuntime`` and ``MultiTenantSSSJService``.  A
service-level snapshot/restore would shrink it to one call.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.engine.window import push_with_overflow
from repro.kernels.sssj_join import summarize_strips
from repro.runtime import MultiTenantRuntime, TenantTable
from repro.serving.service import MultiTenantSSSJService

from bench import gen

__all__ = ["build_service", "install"]


def build_service(cfg: dict, gate: bool | None = None,
                  mesh: Mesh | None = None) -> MultiTenantSSSJService:
    """The service a configuration describes, on ``mesh`` where one is
    given (its ring split over the mesh's window axis).  ``gate`` forces
    the strip gate on or off (``None``: the program's own choice)."""
    k = len(cfg["thetas"])
    table = TenantTable(cfg["thetas"], [gen.lam_of(cfg)] * k)
    svc = MultiTenantSSSJService(
        table, dim=cfg["d"], capacity=cfg["capacity"],
        micro_batch=cfg["micro_batch"], max_pairs=cfg["max_pairs"],
        tile_k=cfg["tile_k"], span=cfg["span"],
        max_queue_per_tenant=cfg["max_queue_per_tenant"],
        eviction=cfg["eviction"], mesh=mesh,
    )
    if gate is not None:
        rt = svc.runtime
        rt_cfg = dataclasses.replace(rt.cfg, l2_gate=gate)
        rt.close()
        svc.runtime = None  # free the first window before the second
        svc.runtime = MultiTenantRuntime(
            rt_cfg, table, span=cfg["span"],
            max_queue_per_tenant=cfg["max_queue_per_tenant"],
            engine=rt.engine,
        )
    return svc


@partial(jax.jit, donate_argnums=0,
         static_argnames=("d", "tau", "eviction"))
def _write(state, key_row, key_anchor, idx, anchor, noise, sq, n_valid,
           quotas, *, d, tau, eviction):
    q = gen.rows(key_row, key_anchor, idx, anchor, noise, d=d)
    ts = idx.astype(jnp.float32)
    t_max = ts[n_valid - 1]
    return push_with_overflow(
        state, q, ts, idx.astype(jnp.int32), n_valid, t_max, tau, sq=sq,
        eviction=eviction, quotas=quotas,
    )


@partial(jax.jit, static_argnames=("rows", "block_w", "chunk_d"))
def _summary_part(vecs, ts, uids, lo, *, rows, block_w, chunk_d):
    """Strip summaries of window rows ``[lo, lo + rows)``; a whole window
    at once would need more temporaries than the chip holds."""
    d = vecs.shape[1]
    return summarize_strips(
        jax.lax.dynamic_slice(vecs, (lo, 0), (rows, d)),
        jax.lax.dynamic_slice(ts, (lo,), (rows,)),
        jax.lax.dynamic_slice(uids, (lo,), (rows,)),
        block_w=block_w, chunk_d=chunk_d,
    )


def _detach(rt):
    """The runtime's window, taken out of it, and its strip summary apart:
    the ring's old buffers are donated to the first write."""
    state, rt.state = rt.state, None
    return state._replace(summary=None), state.summary


def _fill_one(rt, plan: gen.Plan, n_rows: int, block: int):
    ecfg = rt.cfg
    state, summary = _detach(rt)
    for lo in range(0, n_rows, block):
        key_row, key_anchor, idx, anchor, noise = gen.block_args(
            plan, lo, block)
        sq = np.zeros(block, np.int32)
        hi = min(lo + block, n_rows)
        sq[:hi - lo] = plan.tenant[lo:hi]
        state = _write(
            state, key_row, key_anchor, idx, anchor, noise, sq,
            np.int32(hi - lo), ecfg.quotas_device(), d=ecfg.d,
            tau=rt.table.tau_max, eviction=ecfg.eviction,
        )
    if summary is not None:
        del summary
        cap = state.ts.shape[0]
        rows = _summary_rows(cap, block, ecfg.block_w)
        parts = [_summary_part(state.vecs, state.ts, state.uids,
                               np.int32(lo), rows=rows,
                               block_w=ecfg.block_w, chunk_d=ecfg.chunk_d)
                 for lo in range(0, cap, rows)]
        summary = jax.tree.map(lambda *x: jnp.concatenate(x), *parts)
    return state._replace(summary=summary)


def _auto(mesh: Mesh) -> Mesh:
    """The mesh with every axis ``Auto``: the view the program lays its
    sharded window out on."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _leading(axis: str, tree):
    """Specs that split every leaf of ``tree`` along its leading axis."""
    return jax.tree.map(lambda x: P(axis, *([None] * (x.ndim - 1))), tree)


def _sharded_write(mesh: Mesh, axis: str, state, quotas, *, d, tau,
                   eviction):
    """``_write`` on every shard of a sharded window: shard ``s`` takes the
    block's arrivals ``s, s + P, s + 2P, ...``, makes only those rows, and
    pushes them into its own ring with its own cursor and lanes."""
    p = mesh.shape[axis]

    def local(st, key_row, key_anchor, idx, anchor, noise, sq, n_valid):
        loc = jax.lax.axis_index(axis) + p * jnp.arange(idx.shape[0] // p)
        sub = st._replace(
            cursor=st.cursor[0], overflow=st.overflow[0],
            lane_cursor=None if st.lane_cursor is None
            else st.lane_cursor[0],
            lane_overflow=None if st.lane_overflow is None
            else st.lane_overflow[0])
        sub = _write(sub, key_row, key_anchor, idx[loc], anchor[loc], noise,
                     sq[loc], jnp.sum((loc < n_valid).astype(jnp.int32)),
                     quotas, d=d, tau=tau, eviction=eviction)
        return jax.tree.map(lambda new, old: new.reshape(old.shape), sub, st)

    specs = _leading(axis, state)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(specs,) + (P(),) * 7, out_specs=specs,
        check_vma=False), donate_argnums=0)


def _sharded_summary(mesh: Mesh, axis: str, summary, *, rows, block_w,
                     chunk_d):
    """On every shard, summarize its own ring rows ``[lo, lo + rows)`` into
    its own strips of ``summary``: per-shard geometry."""
    specs = _leading(axis, summary)

    def local(summ, vecs, ts, uids, lo):
        part = _summary_part(vecs, ts, uids, lo, rows=rows, block_w=block_w,
                             chunk_d=chunk_d)
        return jax.tree.map(lambda a, b: jax.lax.dynamic_update_slice_in_dim(
            a, b, lo // block_w, 0), summ, part)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(specs, P(axis, None), P(axis), P(axis), P()),
        out_specs=specs, check_vma=False), donate_argnums=0)


def _summary_rows(cap: int, block: int, block_w: int) -> int:
    rows = min(block, cap)
    if cap % rows or rows % block_w:
        raise ValueError(f"ring of {cap} rows does not split into "
                         f"summary blocks of {rows}")
    return rows


def _fill_sharded(rt, plan: gen.Plan, n_rows: int, block: int):
    ecfg, eng = rt.cfg, rt.engine
    state, summary = _detach(rt)
    mesh, p = _auto(eng.mesh), eng.n_shards
    if block % p:
        raise ValueError(f"blocks of {block} rows do not deal evenly over "
                         f"{p} shards")
    write = _sharded_write(mesh, eng.axis, state, ecfg.quotas_device(),
                           d=ecfg.d, tau=rt.table.tau_max,
                           eviction=ecfg.eviction)
    for lo in range(0, n_rows, block):
        key_row, key_anchor, idx, anchor, noise = gen.block_args(
            plan, lo, block)
        sq = np.zeros(block, np.int32)
        hi = min(lo + block, n_rows)
        sq[:hi - lo] = plan.tenant[lo:hi]
        state = write(state, key_row, key_anchor, idx, anchor, noise, sq,
                      np.int32(hi - lo))
    if summary is not None:
        cap = ecfg.capacity
        rows = _summary_rows(cap, block, ecfg.block_w)
        refresh = _sharded_summary(mesh, eng.axis, summary, rows=rows,
                                   block_w=ecfg.block_w, chunk_d=ecfg.chunk_d)
        for lo in range(0, cap, rows):
            summary = refresh(summary, state.vecs, state.ts, state.uids,
                              np.int32(lo))
    return state._replace(summary=summary)


def install(svc: MultiTenantSSSJService, plan: gen.Plan, n_rows: int,
            block: int) -> None:
    """Put arrivals ``[0, n_rows)`` of ``plan`` into ``svc``'s ring and
    bookkeeping, exactly as if each had been submitted and flushed."""
    rt = svc.runtime
    sharded = getattr(rt.engine, "mesh", None) is not None
    rt.state = (_fill_sharded if sharded else _fill_one)(rt, plan, n_rows,
                                                         block)

    tenant = plan.tenant[:n_rows]
    k_n = rt.table.n_tenants
    counts = np.bincount(tenant, minlength=k_n)
    local = np.empty(n_rows, np.int64)
    for k in range(k_n):
        local[tenant == k] = np.arange(counts[k])
    rt._next_uid = n_rows
    rt.n_items = n_rows
    buf = np.empty((max(1024, 2 * n_rows),), np.int32)
    buf[:n_rows] = tenant
    rt._uid_tenant_buf = buf
    rt._uid_tenant_n = n_rows
    rt._mask_uid0 = n_rows
    for k in range(k_n):
        rt.submitted_by_tenant[k] = int(counts[k])
    svc._next_local = [int(c) for c in counts]
    svc._local_of = dict(zip(range(n_rows), local.tolist()))
    jax.block_until_ready(rt.state)

