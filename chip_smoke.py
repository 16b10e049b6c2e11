#!/usr/bin/env python3
"""Smoke run of the multi-tenant join service on one TPU chip (or four).

    python3 chip_smoke.py             # one chip: the served path at full size
    python3 chip_smoke.py --chips 4   # the sharded window on four chips only

One chip: ``MultiTenantSSSJService`` at a deployment-sized window — near-
duplicate detection over encoder embeddings (SemDeDup, arXiv:2303.09540):
unit-norm f32 vectors at d = 768, a ring of 2^20 rows (3 GiB), 8 tenants
with θ in [0.85, 0.95] and a λ whose horizon spans half the window.  It
checks that the compiled scan step holds both Pallas kernels (strip gate
and candidate join) as TPU custom calls, streams capacity + 4 spans of
seeded traffic through submit/flush so the ring wraps, and checks 256
query rows pair-for-pair against a numpy brute force over the items live
in the window at each row's arrival, with no pair dropped.

Four chips: the same service sharded over ``jax.make_mesh((4,), ("data",))``
with 2^20 rows per chip, against a single-chip service on device 0, over a
stream whose live horizon fits 2^20 rows: identical per-tenant pair sets
and no live item overwritten on either.

Timings printed on the way are smoke timings, not benchmark numbers.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed phase
exits non-zero before it.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

D = 768
CAPACITY = 1 << 20            # ring rows per chip
N_TENANTS = 8
THETAS = np.linspace(0.85, 0.95, N_TENANTS)
HORIZON = CAPACITY // 2       # θ_min's horizon, in arrivals
LAM = math.log(1.0 / THETAS.min()) / HORIZON
MICRO_BATCH = 64              # service defaults
SPAN = 4
BLOCK = MICRO_BATCH * SPAN    # rows per dispatched span
VERIFY_ROWS = 256
DUP_FRAC = 0.02               # planted near-duplicates
NOISE = 0.1                   # dup = normalize(src + NOISE · unit noise)
MARGIN = 2e-4                 # no planted score this close to its θ
SHARDED_ITEMS = 1 << 17       # stream of the four-chip comparison


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------- #
# traffic: seeded, admission-ordered, with planted near-duplicates
# --------------------------------------------------------------------- #
def make_stream(n: int, seed: int, tail_dups: int = 0):
    """``n`` unit vectors in admission order (timestamp = index).

    Within every dispatched span rows are grouped by tenant, so a span is
    a handful of per-tenant ``submit`` calls.  Planted pairs copy an older
    item of the same tenant with noise; a source is copied once and is
    never a copy itself, so the true pairs are exactly the planted ones
    whose decayed score clears the tenant's θ (random pairs at d = 768
    stay far below 0.85).  Pairs scoring within MARGIN of θ are unplanted,
    so f32 rounding cannot decide a pair.  ``tail_dups`` extra copies land
    in the last span blocks, after the ring has wrapped.
    """
    rng = np.random.default_rng(seed)
    tenant = rng.integers(0, N_TENANTS, n)
    vecs = rng.standard_normal((n, D), dtype=np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    max_lag = min(n - 1, (3 * CAPACITY) // 4)

    n_dup = int(DUP_FRAC * n)
    dst = np.concatenate([
        rng.integers(2 * BLOCK, n, n_dup),
        rng.integers(max(2 * BLOCK, n - 4 * BLOCK), n, tail_dups),
    ])
    used = np.zeros(n, bool)
    pairs = []
    for g in dst.tolist():
        lo = max(0, g - max_lag)
        hi = g - 2 * BLOCK                  # source in an earlier span
        if hi <= lo:
            continue
        src = int(rng.integers(lo, hi))
        if used[g] or used[src]:
            continue
        used[g] = used[src] = True
        pairs.append((src, g))
    # same tenant, then group rows by tenant inside each span block
    src_a = np.array([p[0] for p in pairs])
    dst_a = np.array([p[1] for p in pairs])
    tenant[dst_a] = tenant[src_a]
    order = np.lexsort((np.arange(n), tenant, np.arange(n) // BLOCK))
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    tenant = tenant[order]
    src_a, dst_a = pos[src_a], pos[dst_a]
    z = rng.standard_normal((len(pairs), D)).astype(np.float32)
    z /= np.linalg.norm(z, axis=1, keepdims=True)
    dup = vecs[src_a] + NOISE * z
    dup /= np.linalg.norm(dup, axis=1, keepdims=True)
    score = (
        np.einsum("ij,ij->i", dup.astype(np.float64),
                  vecs[src_a].astype(np.float64))
        * np.exp(-LAM * (dst_a - src_a))
    )
    keep = np.abs(score - THETAS[tenant[dst_a]]) >= MARGIN
    vecs[dst_a[keep]] = dup[keep]
    return vecs, tenant, dst_a[keep]


def pick_rows(n: int, dups: np.ndarray, seed: int) -> np.ndarray:
    """Verified rows: planted copies (positives and horizon negatives) from
    before and from after the ring wrapped (the last 4 spans), 3/8 of the
    rows each where the stream has them, and plain rows for the rest."""
    rng = np.random.default_rng(seed + 1)
    late = n - 4 * BLOCK
    picks = []
    for pool in (dups[dups < late], dups[dups >= late]):
        picks.append(rng.choice(pool, min(len(pool), VERIFY_ROWS * 3 // 8),
                                replace=False))
    rest = VERIFY_ROWS - sum(len(p) for p in picks)
    plain = rng.permutation(
        np.setdiff1d(rng.integers(2 * BLOCK, n, 4 * rest), dups)
    )
    picks.append(plain[:rest])
    rows = np.unique(np.concatenate(picks))
    check(len(rows) == VERIFY_ROWS, f"picked {len(rows)} verified rows")
    return rows


def reference_pairs(vecs, tenant, rows, capacity):
    """Numpy brute force: for each verified row g, every older item of its
    tenant in the ring when g's micro-batch was joined (the last
    ``capacity`` arrivals before the micro-batch, plus the micro-batch's
    own earlier rows) whose decayed score reaches the tenant's θ.  Scores
    near θ are recomputed in float64."""
    sims = np.empty((vecs.shape[0], len(rows)), np.float32)
    q = vecs[rows].T
    for a in range(0, vecs.shape[0], 1 << 16):
        np.matmul(vecs[a:a + (1 << 16)], q, out=sims[a:a + (1 << 16)])
    ref = {}
    for r, g in enumerate(rows.tolist()):
        k = tenant[g]
        lo = max(0, g - g % MICRO_BATCH - capacity)
        cand = np.arange(lo, g)
        cand = cand[tenant[lo:g] == k]
        score = sims[cand, r] * np.exp(-LAM * (g - cand))
        near = cand[score >= THETAS[k] - 0.01]
        exact = (vecs[near].astype(np.float64) @ vecs[g].astype(np.float64)
                 * np.exp(-LAM * (g - near)))
        check(np.all(np.abs(exact - THETAS[k]) >= MARGIN / 2),
              f"row {g}: a reference score lies within {MARGIN / 2} of θ")
        ref[g] = dict(zip(near[exact >= THETAS[k]].tolist(),
                          exact[exact >= THETAS[k]].tolist()))
    return ref


# --------------------------------------------------------------------- #
# driving the service
# --------------------------------------------------------------------- #
def build_service(capacity: int, mesh=None):
    from repro.runtime import TenantTable
    from repro.serving.service import MultiTenantSSSJService

    table = TenantTable(THETAS, [LAM] * N_TENANTS)
    return MultiTenantSSSJService(table, dim=D, capacity=capacity, mesh=mesh)


def stream_through(services, vecs, tenant, label: str):
    """Submit span by span (one ``submit`` per tenant run), flush after
    each span; returns per-service ``{(g_newer, g_older): score}`` in
    admission-index space and the stream seconds after the first span."""
    n = vecs.shape[0]
    glob_of = [np.nonzero(tenant == k)[0] for k in range(N_TENANTS)]
    emitted = [dict() for _ in services]
    t_first = None
    report = max(1, (n // BLOCK) // 8)
    for b, a in enumerate(range(0, n, BLOCK)):
        seg = tenant[a:a + BLOCK]
        cuts = np.flatnonzero(np.diff(seg)) + 1
        for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, len(seg)]):
            for svc in services:
                svc.submit(int(seg[lo]), vecs[a + lo:a + hi],
                           np.arange(a + lo, a + hi, dtype=np.float64))
        for svc, got in zip(services, emitted):
            for k, pairs in svc.flush().items():
                gk = glob_of[k]
                for x, y, s in pairs:
                    got[(int(gk[x]), int(gk[y]))] = s
        if t_first is None:
            t_first = time.perf_counter()
        elif b % report == 0:
            dt = time.perf_counter() - t_first
            log(f"[{label}] {a + BLOCK}/{n} items streamed, "
                f"{a / dt:.0f} items/s (smoke timing)")
    return emitted, time.perf_counter() - t_first


def check_service_stats(svc, label: str) -> None:
    st = svc.stats()
    log(f"[{label}] pairs_emitted={st['pairs_emitted']} "
        f"pairs_dropped={st['pairs_dropped']} "
        f"window_overflow={st['window_overflow']}")
    check(st["pairs_dropped"] == 0, f"{label}: {st['pairs_dropped']} dropped")
    check(st["window_overflow"] == 0,
          f"{label}: {st['window_overflow']} live items overwritten")


def verify_rows(emitted, ref, label: str) -> None:
    by_row = {}
    for (g, j), s in emitted.items():
        by_row.setdefault(g, {})[j] = s
    n_pairs = 0
    for g, want in ref.items():
        got = by_row.get(g, {})
        check(got.keys() == want.keys(),
              f"{label}: row {g} emitted {sorted(got)[:5]} "
              f"but the reference has {sorted(want)[:5]}")
        for j, s in want.items():
            check(abs(got[j] - s) < 1e-5,
                  f"{label}: pair ({g}, {j}) score {got[j]} vs {s}")
        n_pairs += len(want)
    check(n_pairs > 0, f"{label}: the verified rows hold no true pair")
    log(f"[{label}] {len(ref)} verified rows match the numpy reference "
        f"({n_pairs} pairs)")


def compiled_kernels(svc) -> set:
    """Kernels in the service's own compiled scan step."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.sssj_join import tpu_kernels

    rt = svc.runtime
    f32, i32 = jnp.float32, jnp.int32
    shapes = [
        jax.ShapeDtypeStruct((SPAN, MICRO_BATCH, D), f32),
        jax.ShapeDtypeStruct((SPAN, MICRO_BATCH), f32),
        jax.ShapeDtypeStruct((SPAN, MICRO_BATCH), i32),
        jax.ShapeDtypeStruct((SPAN, MICRO_BATCH), i32),
        jax.ShapeDtypeStruct((SPAN,), i32),
    ]
    t0 = time.perf_counter()
    compiled = rt._step.lower(rt.state, rt.telem, *shapes).compile()
    log(f"compile of the scan step: {time.perf_counter() - t0:.1f} s "
        f"(smoke timing)")
    return tpu_kernels(compiled.as_text())


def device_memory_line(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def one_chip(seed: int) -> None:
    import jax

    from repro.kernels.sssj_join import CANDIDATE_KERNEL, GATE_KERNEL

    svc = build_service(CAPACITY)
    vecs_dev = svc.runtime.state.vecs
    log(f"window: {vecs_dev.shape} {vecs_dev.dtype} on {vecs_dev.devices()}, "
        f"{vecs_dev.nbytes / 2**30:.2f} GiB resident")
    kernels = compiled_kernels(svc)
    for name in (GATE_KERNEL, CANDIDATE_KERNEL):
        log(f"tpu_custom_call {name}: {name in kernels}")
    check({GATE_KERNEL, CANDIDATE_KERNEL} <= kernels,
          f"compiled step lacks a Pallas kernel: {sorted(kernels)}")

    n = CAPACITY + 4 * BLOCK
    t0 = time.perf_counter()
    vecs, tenant, dups = make_stream(n, seed, tail_dups=VERIFY_ROWS)
    rows = pick_rows(n, dups, seed)
    log(f"stream: {n} items, {len(dups)} planted copies, "
        f"{time.perf_counter() - t0:.1f} s to generate")
    (emitted,), secs = stream_through([svc], vecs, tenant, "1 chip")
    log(f"streamed {n} items in {secs:.1f} s after the first span: "
        f"{(n - BLOCK) / secs:.0f} items/s (smoke timing)")
    uids = np.asarray(svc.runtime.state.uids)
    check(n > CAPACITY and uids.min() > 0,
          "the ring never wrapped: uid 0 is still resident")
    log(f"ring wrapped: {n} items through {CAPACITY} slots, oldest resident "
        f"uid {uids.min()}")
    check_service_stats(svc, "1 chip")
    t0 = time.perf_counter()
    ref = reference_pairs(vecs, tenant, rows, CAPACITY)
    log(f"numpy reference: {time.perf_counter() - t0:.1f} s")
    verify_rows(emitted, ref, "1 chip")
    log(f"peak device memory: {device_memory_line(jax.devices()[0])}")


def four_chips(seed: int) -> None:
    import jax

    mesh = jax.make_mesh((4,), ("data",))
    sharded = build_service(4 * CAPACITY, mesh=mesh)
    single = build_service(CAPACITY)
    for label, svc in (("4 chips", sharded), ("device 0", single)):
        v = svc.runtime.state.vecs
        log(f"[{label}] window {v.shape} over "
            f"{sorted(d.id for d in v.devices())}, "
            f"{v.nbytes / 2**30:.2f} GiB")
    n = SHARDED_ITEMS
    vecs, tenant, dups = make_stream(n, seed)
    rows = pick_rows(n, dups, seed)
    (got_4, got_1), secs = stream_through(
        [sharded, single], vecs, tenant, "4 chips + device 0"
    )
    log(f"streamed {n} items through both services in {secs:.1f} s "
        f"(smoke timing)")
    check_service_stats(sharded, "4 chips")
    check_service_stats(single, "device 0")
    for k in range(N_TENANTS):
        a = {p for p in got_4 if tenant[p[0]] == k}
        b = {p for p in got_1 if tenant[p[0]] == k}
        check(a == b, f"tenant {k}: sharded and single-chip pair sets differ "
              f"({len(a - b)} only sharded, {len(b - a)} only single)")
    check(got_4, "the comparison stream emitted no pair")
    log(f"per-tenant pair sets identical: {len(got_4)} pairs over "
        f"{N_TENANTS} tenants")
    verify_rows(got_4, reference_pairs(vecs, tenant, rows, CAPACITY),
                "4 chips")
    for dev in jax.devices():
        log(f"peak device memory {dev.id}: {device_memory_line(dev)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
