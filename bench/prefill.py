"""Steady-state window: build the service and fill its ring from the seed.

A deployment's ring is full.  Streaming a whole ring of arrivals through
``submit``/``flush`` before every run would take minutes, so set-up writes
the arrivals that precede the measured window straight into the ring, on
the device, as if they had been streamed: the same rows, timestamps, uids,
tenant lanes and strip summaries.  The rows come from :mod:`bench.gen`;
the state is built with the program's own window write
(``push_with_overflow``) and strip summary (``summarize_strips``).

This is the one module of the benchmark that reads the program's
internals: the runtime's window pytree and the uid / tenant / local-id
bookkeeping of ``MultiTenantRuntime`` and ``MultiTenantSSSJService``.  A
service-level snapshot/restore would shrink it to one call.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.window import push_with_overflow
from repro.kernels.sssj_join import summarize_strips
from repro.runtime import MultiTenantRuntime, TenantTable
from repro.serving.service import MultiTenantSSSJService

from bench import gen

__all__ = ["build_service", "install"]


def build_service(cfg: dict, gate: bool | None = None) -> MultiTenantSSSJService:
    """The service a configuration describes.  ``gate`` forces the strip
    gate on or off (``None``: the program's own choice)."""
    k = len(cfg["thetas"])
    table = TenantTable(cfg["thetas"], [gen.lam_of(cfg)] * k)
    svc = MultiTenantSSSJService(
        table, dim=cfg["d"], capacity=cfg["capacity"],
        micro_batch=cfg["micro_batch"], max_pairs=cfg["max_pairs"],
        tile_k=cfg["tile_k"], span=cfg["span"],
        max_queue_per_tenant=cfg["max_queue_per_tenant"],
        eviction=cfg["eviction"],
    )
    if gate is not None:
        rt = svc.runtime
        rt_cfg = dataclasses.replace(rt.cfg, l2_gate=gate)
        rt.close()
        svc.runtime = None  # free the first window before the second
        svc.runtime = MultiTenantRuntime(
            rt_cfg, table, span=cfg["span"],
            max_queue_per_tenant=cfg["max_queue_per_tenant"],
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


def install(svc: MultiTenantSSSJService, plan: gen.Plan, n_rows: int,
            block: int) -> None:
    """Put arrivals ``[0, n_rows)`` of ``plan`` into ``svc``'s ring and
    bookkeeping, exactly as if each had been submitted and flushed."""
    rt = svc.runtime
    ecfg = rt.cfg
    state = rt.state
    summary = state.summary
    rt.state = None
    state = state._replace(summary=None)
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
        rows = min(block, cap)
        if cap % rows or rows % ecfg.block_w:
            raise ValueError(f"ring of {cap} rows does not split into "
                             f"summary blocks of {rows}")
        parts = [_summary_part(state.vecs, state.ts, state.uids,
                               np.int32(lo), rows=rows,
                               block_w=ecfg.block_w, chunk_d=ecfg.chunk_d)
                 for lo in range(0, cap, rows)]
        summary = jax.tree.map(lambda *x: jnp.concatenate(x), *parts)
    rt.state = state._replace(summary=summary)

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

