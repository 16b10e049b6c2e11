"""Beyond-paper: throughput of the XLA-compiled blocked join (the jnp ref
path — the kernel itself targets TPU and runs in interpret mode here, so
wall-clock is only meaningful for the compiled dense path), the on-device
pair-compaction stage it feeds (engine emission path), and the roofline
picture of the Pallas kernel from its static work model."""

from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sssj_join import compact_pairs, sssj_join_tiles
from repro.kernels.sssj_join.ops import sssj_join_scores

from .common import Row, device_peaks


def run(fast: bool = True) -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)
    Q = W = 512 if fast else 2048
    for d in ((256,) if fast else (256, 1024)):
        q = rng.standard_normal((Q, d)).astype(np.float32)
        w = rng.standard_normal((W, d)).astype(np.float32)
        # plant near-duplicates so the emission path has real pairs to move
        q[: Q // 16] = w[: Q // 16] + 0.05 * rng.standard_normal((Q // 16, d))
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        w /= np.linalg.norm(w, axis=1, keepdims=True)
        tq = np.sort(rng.random(Q) * 100).astype(np.float32) + 0.5
        tw = np.sort(rng.random(W) * 100).astype(np.float32)
        uq = np.arange(W, W + Q, dtype=np.int32)
        uw = np.arange(W, dtype=np.int32)
        args = [jnp.asarray(x) for x in (q, w, tq, tw, uq, uw)]
        kw = dict(theta=0.7, lam=0.05, use_ref=True)
        out, _ = sssj_join_scores(*args, **kw)
        out.block_until_ready()
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            out, _ = sssj_join_scores(*args, **kw)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        gflops = 2 * Q * W * d / dt / 1e9
        rows.append(Row(f"kernel/ref_dense/Q{Q}xW{W}xd{d}/gflops", gflops,
                        f"{dt*1e3:.1f} ms/join"))

        # join + fused on-device compaction (the engine's emission path):
        # the incremental cost of never moving the dense matrix to the host
        max_pairs = 4096

        @functools.partial(jax.jit, static_argnums=())
        def _join_compact(q, w, tq, tw, uq, uw):
            scores, _, _ = sssj_join_tiles(q, w, tq, tw, uq, uw, **kw)
            return compact_pairs(scores, uq, uw, max_pairs=max_pairs)

        buf = _join_compact(*args)
        jax.block_until_ready(buf)
        t0 = time.perf_counter()
        for _ in range(reps):
            buf = _join_compact(*args)
        jax.block_until_ready(buf)
        dt_c = (time.perf_counter() - t0) / reps
        rows.append(Row(
            f"kernel/compacted/Q{Q}xW{W}xd{d}/overhead_pct",
            100.0 * (dt_c - dt) / dt,
            f"{dt_c*1e3:.1f} ms/join+compact, {int(buf.n_pairs)} pairs",
        ))
        # static work model of the Pallas kernel for this shape on the chip
        # this runs on: the larger of full-tile FLOPs over the f32 matmul
        # rate (HIGHEST precision counted as 6 bf16 MXU passes) and the
        # query + window bytes over HBM bandwidth.  A CPU has no entry —
        # off the chip the row is not produced.
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            peaks = device_peaks(dev)
            t_mxu = 2 * Q * W * d / (peaks["bf16_flops"] / 6)
            t_hbm = 4 * (Q + W) * d / peaks["hbm_bytes_per_s"]
            rows.append(Row(
                f"kernel/roofline/Q{Q}xW{W}xd{d}/us",
                max(t_mxu, t_hbm) * 1e6,
                f"{dev.device_kind}, "
                f"{'compute' if t_mxu >= t_hbm else 'memory'}-bound",
            ))
    return rows


def check(rows: List[Row]) -> List[str]:
    return []
