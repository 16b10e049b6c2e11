"""Client loops that drive ``MultiTenantSSSJService.submit``/``flush``.

Both loops take the timed pool (rows, tenants, request starts) and use the
public service API only; each request is one ``submit`` call, whose
arguments the row representation's service side cuts from the pool's rows
(``bench/serve/<name>.py``).  A traffic
file picks one by ``client.kind``:

* ``backlog`` — requests are submitted until ``spans_queued`` dispatch
  spans are queued, then ``flush``; measures the throughput of the served
  path.
* ``poisson`` — open loop: requests come due at Poisson times, at the
  file's ``rate`` in items per second; due requests are submitted as they
  come due, a ``flush`` runs as soon as a full micro-batch is queued, and
  ``flush(final=True)`` pads out a partial one once its oldest arrival has
  waited ``deadline_arrivals`` arrivals' time.  An arrival's latency runs
  from its request's due time to the return of the ``flush`` that
  dispatched it.

A ``flush`` dispatches every full micro-batch queued (and, with
``final=True``, the rest), in admission order; the loops count the rows
each returns by that rule.  ``annotate(name)`` wraps each call into the
service so that a profiler trace can say what the host was doing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable

import numpy as np

from repro.runtime import TenantBackpressure

__all__ = ["Pool", "WindowResult", "run_client", "poisson_due"]


@dataclasses.dataclass
class Pool:
    base: int              # arrival index of the pool's first row
    rows: object           # the data side's host rows of the pool
    tenant: np.ndarray     # (n,) i32
    starts: np.ndarray     # (r,) first row of each request; starts[0] == 0
    args: Callable         # (rows, a, b) -> submit's arguments for [a, b)

    @property
    def n(self) -> int:
        return self.tenant.size

    @classmethod
    def of(cls, plan, rows, lo: int, hi: int, args: Callable) -> "Pool":
        """The pool of arrivals ``[lo, hi)`` of ``plan``, whose host rows
        are ``rows``; a request that began before ``lo`` keeps its rest as
        the first."""
        s = plan.starts[(plan.starts > lo) & (plan.starts < hi)] - lo
        return cls(lo, rows, plan.tenant[lo:hi],
                   np.concatenate([[0], s]).astype(np.int64), args)


@dataclasses.dataclass
class WindowResult:
    t0: float                     # first timed submit (perf_counter)
    t_end: float                  # return of the last flush
    n_sent: int                   # pool rows offered
    returned: int                 # rows whose flush returned
    refused: np.ndarray           # (n_sent,) bool, TenantBackpressure
    flushes: list                 # flush() results, in order
    flush_s: np.ndarray           # wall time of each flush
    latency_s: np.ndarray | None  # per returned row (poisson only)
    late_s: np.ndarray | None     # submit time - due time (poisson only)
    batches: list                 # (lo, hi) pool rows of each micro-batch


def _nothing(name):
    return contextlib.nullcontext()


def _submit(svc, pool: Pool, r: int, refused, annotate) -> int:
    """Submit request ``r``; returns its row count (0 if refused)."""
    a = int(pool.starts[r])
    b = int(pool.starts[r + 1]) if r + 1 < pool.starts.size else pool.n
    ts = np.arange(pool.base + a, pool.base + b, dtype=np.float64)
    try:
        with annotate("bench.submit"):
            svc.submit(int(pool.tenant[a]), *pool.args(pool.rows, a, b), ts)
    except TenantBackpressure:
        refused[a:b] = True
        return 0
    return b - a


def _batches(refused, sent: int, n_disp: int, mb: int) -> list:
    """Pool-row ranges of the micro-batches that carried the first
    ``n_disp`` admitted rows."""
    adm = np.flatnonzero(~refused[:sent])[:n_disp]
    return [(int(adm[i]), int(adm[min(i + mb, adm.size) - 1]) + 1)
            for i in range(0, adm.size, mb)]


def _flush(svc, final, flushes, flush_s, annotate):
    t = time.perf_counter()
    with annotate("bench.flush"):
        flushes.append(svc.flush(final=final))
    flush_s.append(time.perf_counter() - t)


def _backlog(svc, pool: Pool, cfg: dict, client: dict, seconds: float,
             annotate) -> WindowResult:
    mb = cfg["micro_batch"]
    chunk = client["spans_queued"] * cfg["span"] * mb
    n = pool.n
    refused = np.zeros(n, bool)
    flushes, flush_s = [], []
    r = queued = n_disp = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        while queued < chunk and r < pool.starts.size:
            queued += _submit(svc, pool, r, refused, annotate)
            r += 1
        if queued < chunk:
            break
        _flush(svc, False, flushes, flush_s, annotate)
        take = (queued // mb) * mb
        n_disp += take
        queued -= take
    t_end = time.perf_counter()
    sent = int(pool.starts[r]) if r < pool.starts.size else n
    return WindowResult(t0, t_end, sent, n_disp, refused[:sent], flushes,
                        np.asarray(flush_s), None, None,
                        _batches(refused, sent, n_disp, mb))


def poisson_due(rng: np.random.Generator, rate: float, seconds: float):
    """Due times (s from the window's start) of a Poisson stream."""
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        more = due[-1] + np.cumsum(rng.exponential(1.0 / rate, n))
        due = np.concatenate([due, more])
    return due[due < seconds]


def _poisson(svc, pool: Pool, cfg: dict, client: dict, due_req: np.ndarray,
             annotate) -> WindowResult:
    mb = cfg["micro_batch"]
    wait = client["deadline_arrivals"] / client["rate"]
    n_req = due_req.size
    n = int(pool.starts[n_req]) if n_req < pool.starts.size else pool.n
    due = np.repeat(due_req, np.diff(np.append(pool.starts[:n_req], n)))
    refused = np.zeros(n, bool)
    t_done = np.full(n, np.nan)
    t_sub = np.zeros(n)
    flushes, flush_s = [], []
    r = sent = q_lo = n_disp = 0     # requests, rows submitted; first queued
    t0 = time.perf_counter()

    def flush(final):
        nonlocal q_lo, n_disp
        queued = np.flatnonzero(~refused[q_lo:sent]) + q_lo
        take = queued if final else queued[:(queued.size // mb) * mb]
        _flush(svc, final, flushes, flush_s, annotate)
        t_done[take] = time.perf_counter() - t0
        n_disp += take.size
        q_lo = int(take[-1]) + 1 if take.size else q_lo
        if final:
            q_lo = sent

    while True:
        now = time.perf_counter() - t0
        hi = int(np.searchsorted(due_req, now, side="right"))
        while r < hi:
            a = int(pool.starts[r])
            _submit(svc, pool, r, refused, annotate)
            r += 1
            sent = int(pool.starts[r]) if r < n_req else n
            t_sub[a:sent] = now
        n_queued = int((~refused[q_lo:sent]).sum())
        if n_queued >= mb:
            flush(False)
            continue
        if sent == n:
            if n_queued:
                flush(True)
            break
        wake = due_req[r]
        if n_queued:
            oldest = q_lo + int(np.argmin(refused[q_lo:sent]))
            if now >= due[oldest] + wait:
                flush(True)
                continue
            wake = min(wake, due[oldest] + wait)
        with annotate("bench.idle"):
            time.sleep(max(0.0, wake - (time.perf_counter() - t0)))
    t_end = time.perf_counter()
    ok = ~refused & ~np.isnan(t_done)
    return WindowResult(t0, t_end, n, n_disp, refused, flushes,
                        np.asarray(flush_s), (t_done - due)[ok],
                        (t_sub - due)[ok], _batches(refused, n, n_disp, mb))


def run_client(svc, pool: Pool, cfg: dict, client: dict, seconds: float,
               due_req: np.ndarray | None = None, annotate=_nothing):
    if client["kind"] == "backlog":
        return _backlog(svc, pool, cfg, client, seconds, annotate)
    if client["kind"] == "poisson":
        return _poisson(svc, pool, cfg, client, due_req, annotate)
    raise ValueError(f"unknown client kind {client['kind']!r}")
