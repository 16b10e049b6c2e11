#!/usr/bin/env python3
"""Benchmark of the multi-tenant similarity-join service on one chip or a
mesh of them.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a cell of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  The configuration's ``rows`` names its row
representation (absent: ``dense``), whose data side
(``bench/rows/<rows>.py``) makes the stream and the plain reference and
whose service side (``bench/serve/<rows>.py``) builds and fills the
service.  A run builds the service the configuration describes (its ring
split over ``shards`` chips where the configuration has that key), fills
its ring with the arrivals that precede the window, warms up, drives
``submit``/``flush`` for ``--seconds`` with the mix's client
(``bench/client.py``), then checks a seeded sample of the window's rows
against the plain reference.  With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` it records a profiler trace of the
window and reports the cell's per-layer metrics (``bench/metrics/``).

The last line of stdout is one JSON object; the numbers compared for
``correct`` end stderr, each beside its limit.  A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

class NoChip(RuntimeError):
    pass


def _load(root: str, *parts) -> dict:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, mix, limits and metric specs, found
    by name under ``root``."""
    bench = _load(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    wl = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]

    def mine(m):
        return m.get("workloads") is None or workload in m["workloads"]

    return {
        "root": root,
        "workload": wl,
        "cfg": _load(root, cfg_entry["file"]),
        "mix": _load(root, "bench", "traffic", wl["traffic"] + ".json"),
        "limits": _load(root, "bench", "limits", wl["config"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def row_sides(cell: dict):
    """The data side and the service side of the configuration's row
    representation (``rows``, absent: ``dense``), found by its name."""
    from bench.load import module

    name = cell["cfg"].get("rows", "dense")
    return (module(cell["root"], "rows", name),
            module(cell["root"], "serve", name))


def enable_cache() -> str:
    """JAX's persistent compilation cache: the directory the environment
    names, else a fixed one inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def mesh_of(cfg: dict, devs):
    """The mesh a configuration's ring is split over: its ``shards``
    devices on the program's window axis ``data``; ``None`` for one."""
    import jax

    shards = cfg.get("shards", 1)
    if shards == 1:
        return None
    return jax.make_mesh((shards,), ("data",), devices=devs[:shards])


def _log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.3f} s] {msg}", file=sys.stderr,
          flush=True)


def _sample(rng, rows: np.ndarray, anchored: np.ndarray, n: int):
    """Up to ``n`` rows, half of them anchored where the window has them."""
    a, p = rows[anchored], rows[~anchored]
    k = min(a.size, n // 2)
    pick = [rng.choice(a, k, replace=False),
            rng.choice(p, min(p.size, n - k), replace=False)]
    return np.sort(np.concatenate(pick))


def _pairs_of(flushes, glob_of, sample) -> tuple[dict, int]:
    """``{g: {j: score}}`` of the sampled rows from ``flush`` results, in
    arrival indices, and how many pairs of any row came more than once."""
    want = set(sample.tolist())
    got = {g: {} for g in want}
    keys = []
    for out in flushes:
        for t, pairs in out.items():
            gk = glob_of[t]
            if not pairs:
                continue
            ab = np.asarray([(a, b) for a, b, _ in pairs], np.int64)
            ab.sort(axis=1)
            keys.append((t << 48) | (ab[:, 0] << 24) | ab[:, 1])
            for a, b, s in pairs:
                g = int(gk[a])
                if g in want:
                    got[g][int(gk[b])] = s
    keys = np.concatenate(keys) if keys else np.zeros(0, np.int64)
    return got, int(keys.size - np.unique(keys).size)


class _Watch:
    """What pauses the host inside the window: Python's collector (count,
    total, longest) and JAX tracing or compiling (count, seconds; each
    compile is also named on stderr)."""

    def __init__(self):
        self.gc_n = self.jit_n = 0
        self.gc_s = self.gc_max = self.jit_s = 0.0
        self._t = 0.0

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.gc_n += 1
        self.gc_s += dt
        self.gc_max = max(self.gc_max, dt)

    def _jit(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.jit_n += 1
            self.jit_s += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._jit)
        jax.config.update("jax_log_compiles", True)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        import jax

        gc.callbacks.remove(self._gc)
        jax.config.update("jax_log_compiles", False)
        jax.monitoring.unregister_event_duration_listener(self._jit)

    def __str__(self):
        return (f"collector paused {self.gc_n} times, {self.gc_s:.3f} s in "
                f"all, longest {self.gc_max:.3f} s; {self.jit_n} JAX "
                f"trace/compile events, {self.jit_s:.3f} s")


def run(cell: dict, seed: int, seconds: float, trace: bool,
        chips=chip) -> tuple[dict, dict]:
    """One run of a cell; returns ``(result, checks)``."""
    import jax

    from bench import client, gen, reference, trace as tr
    from bench.metrics import Readings, read_metric

    wl, cfg, mix = cell["workload"], cell["cfg"], cell["mix"]
    data, serve = row_sides(cell)
    devs = chips(wl["chips"])[:wl["chips"]]
    dev = devs[0]
    _log(f"device {dev.device_kind} x{len(devs)}")
    cl = mix["client"]
    cap, mb, span = cfg["capacity"], cfg["micro_batch"], cfg["span"]
    # the warm-up makes the window's calls: a flush of two spans, one of
    # a micro-batch, and a padded one
    warm = (2 * span + 1) * mb + mb // 2
    block = data.block_rows(cfg)
    lo_i, hi_i = mix["request_items"]
    due_req = None
    if cl["kind"] == "poisson":
        # the same request times for every seed: the seed changes which
        # rows arrive, not when
        due_req = client.poisson_due(
            np.random.default_rng([mix["schedule_seed"], 1]),
            cl["rate"] * 2 / (lo_i + hi_i), seconds)
        starts = gen.request_starts(mix, cap + hi_i * (due_req.size + 2))
        starts = np.append(cap, starts[starts > cap])
        n_pool = int(starts[due_req.size]) - cap
    else:
        chunk = cl["spans_queued"] * span * mb
        n_pool = chunk * (math.ceil(mix["pool_items_per_s"] * seconds
                                    / chunk) + 1)
    plan = data.make_plan(cfg, mix, seed, cap + n_pool)
    _log(f"plan of {cap} + {n_pool} arrivals")
    svc = serve.build_service(cfg, mesh=mesh_of(cfg, devs))
    serve.install(svc, plan, cap - warm, block)
    _log("ring filled")

    def pool_of(lo, hi):
        return client.Pool.of(plan, data.host_rows(plan, lo, hi, block), lo,
                              hi, serve.submit_args)

    warm_pool = pool_of(cap - warm, cap)
    pool = pool_of(cap, cap + n_pool)
    _log("pool made")
    client.run_client(svc, warm_pool, cfg, {"kind": "backlog",
                                            "spans_queued": 2}, math.inf)
    svc.flush()
    svc.flush(final=True)
    before = svc.snapshot()
    # what set-up made lives to the end: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    _log(f"warmed up; set-up {setup_s:.3f} s")

    trace_dir = os.path.join(cell["root"], ".bench_trace")
    with _Watch() as watch:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("bench.window"):
                res = client.run_client(svc, pool, cfg, cl, seconds, due_req,
                                        annotate=jax.profiler.TraceAnnotation)
            jax.profiler.stop_trace()
        else:
            res = client.run_client(svc, pool, cfg, cl, seconds, due_req)
    after = svc.snapshot()
    # the rows still queued at the close ride out on one last flush, so
    # that every admitted row's pairs can be checked
    tail = svc.flush(final=True)
    drained = svc.snapshot()
    stats = svc.stats()
    # the fullest chip's peak
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peak = max((p for p in peaks if p is not None), default=None)
    _log(f"window closed: {res.returned} rows in {res.t_end - res.t0:.3f} s; "
         f"{watch}; flushes (ms): "
         f"{' '.join(str(round(1e3 * x)) for x in res.flush_s)}")

    # arrivals the service took, and each tenant's local-id → arrival index
    m = cap + res.n_sent
    admitted = np.ones(m, bool)
    admitted[cap:] = ~res.refused
    glob_of = [np.flatnonzero((plan.tenant[:m] == k) & admitted)
               for k in range(len(cfg["thetas"]))]
    took = np.flatnonzero(~res.refused) + cap
    rng_sample = np.random.default_rng([seed, 2])
    sample = _sample(rng_sample, took, plan.anchored[took],
                     mix["sample_rows"])
    got, duplicate = _pairs_of(res.flushes + [tail], glob_of, sample)
    batches = [(int(a) + cap, int(b) + cap) for a, b in res.batches]
    del svc
    gc.unfreeze()
    gc.collect()

    ref = data.reference_pairs(plan, cfg, sample, admitted, block,
                               mix["sample_rows"], devices=devs)
    _log(f"reference over {sample.size} rows")
    th = np.asarray(cfg["thetas"], np.float32)
    nums = reference.compare(got, ref, {g: th[plan.tenant[g]]
                                        for g in sample.tolist()})
    shards = cfg.get("shards", 1)
    if shards > 1:
        # arrival i lies on shard i mod P: every micro-batch is full, or
        # (the warm-up's) a multiple of P rows
        cross = sum(1 for g, want in ref.items() for j, s in want.items()
                    if s >= th[plan.tenant[g]] + reference.BAND
                    and (g - j) % shards)
        _log(f"{cross} of {nums['ref_pairs']} reference pairs join rows "
             f"on different shards")
    nums["duplicate"] = duplicate
    nums["pairs_dropped"] = int(stats["pairs_dropped"])
    nums["window_overflow"] = int(stats["window_overflow"])

    def dispatched(snap):
        return int(snap["router/items_dispatched"]
                   - before["router/items_dispatched"])

    # rows the service took and never dispatched, even on the last flush;
    # rows the client counted as returned that the service had not
    # dispatched by the close
    nums["unreturned"] = abs(int(took.size) - dispatched(drained))
    nums["miscounted"] = abs(res.returned - dispatched(after))
    checks = {}
    for name, value in nums.items():
        lim = cell["limits"][name]
        checks[name] = {"value": value, "limit": lim,
                        "ok": reference.within(value, lim)}
    correct = all(c["ok"] for c in checks.values())

    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(res.n_sent),
              "failed": int(res.refused.sum()) + nums["unreturned"]}
    if not trace:
        window = res.t_end - res.t0
        e2e = {"setup_s": setup_s,
               "items_per_s": res.returned / window}
        if res.latency_s is not None and res.latency_s.size:
            p50, p99 = np.percentile(res.latency_s, [50, 99])
            e2e["latency_p50_ms"] = 1e3 * float(p50)
            e2e["latency_p99_ms"] = 1e3 * float(p99)
            late = np.percentile(res.late_s, [50, 99])
            _log(f"client lateness p50 {1e3 * late[0]:.3f} ms, "
                 f"p99 {1e3 * late[1]:.3f} ms")
        for spec in cell["end_to_end"]:
            if spec["name"] in e2e:
                metrics[spec["name"]] = {"value": e2e[spec["name"]],
                                         "unit": spec["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    else:
        red = tr.reduce(tr.extract(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        r = Readings(cfg=cfg, device_kind=dev.device_kind, trace=red,
                     before=before, after=after, batches=batches,
                     tenant=plan.tenant[:m])
        for spec in cell["per_layer"]:
            v = read_metric(cell["root"], spec["name"], r)
            if v is not None:
                metrics[spec["name"]] = {"value": float(v),
                                         "unit": spec["unit"]}
        result["metrics"] = metrics
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
        result["device"] = device
        if red is not None:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], **c["limit"]}
                        for k, c in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = load_cell(args.workload)
    enable_cache()
    try:
        result, checks = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
