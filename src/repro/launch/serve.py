"""Serving driver: the paper's system end-to-end.

Batched requests (token sequences) → LM embedding (any ``--arch``) →
streaming similarity self-join → near-duplicate groups + trend events,
printed as they are detected.  This is the end-to-end example driver the
paper's kind dictates (a streaming/serving system, not a training recipe).

Example (CPU, seconds):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 32 --batch 16 --theta 0.85 --lam 0.05
"""

from __future__ import annotations

import argparse

import numpy as np

import jax

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.embedder import LMEmbedder
from repro.serving.service import SSSJService

__all__ = ["run_service"]


def run_service(
    arch: str,
    *,
    requests: int = 32,
    batch: int = 16,
    seq: int = 64,
    theta: float = 0.85,
    lam: float = 0.05,
    dup_frac: float = 0.25,
    seed: int = 0,
    verbose: bool = True,
):
    cfg = get_config(arch).reduced()
    embedder = LMEmbedder(cfg, key=jax.random.key(seed))
    service = SSSJService(
        theta=theta, lam=lam, dim=cfg.d_model, capacity=4096,
        embed_fn=embedder,
    )
    rng = np.random.default_rng(seed)
    t = 0.0
    recent: list[np.ndarray] = []
    planted = 0
    for r in range(requests):
        toks = rng.integers(1, cfg.vocab_size, (batch, seq))
        for i in range(batch):
            if recent and rng.random() < dup_frac:
                src = recent[int(rng.integers(0, len(recent)))]
                noise = rng.random(seq) < 0.05
                toks[i] = np.where(noise, toks[i], src)
                planted += 1
        for i in range(batch):
            recent.append(toks[i].copy())
        recent = recent[-256:]
        ts = t + np.arange(batch) * 0.01
        t += 1.0
        pairs = service.submit(toks.astype(np.int32), ts)
        if verbose and pairs:
            print(f"request batch {r}: {len(pairs)} similar pairs")
    groups = service.duplicate_groups()
    trends = service.trending(min_size=3)
    if verbose:
        es = service.engine.stats()
        print(f"\nitems={service.stats.n_items} planted_dups={planted} "
              f"pairs={service.stats.n_pairs} "
              f"dropped={service.stats.pairs_dropped}")
        print(f"host↔device: {es['bytes_to_host']} B compacted vs "
              f"{es['bytes_dense_equiv']} B dense-equivalent")
        print(f"duplicate groups: {len(groups)}; trending (≥3): {len(trends)}")
        for g in trends[:5]:
            print("  trend:", g)
    return service, groups, trends


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--theta", type=float, default=0.85)
    ap.add_argument("--lam", type=float, default=0.05)
    ap.add_argument("--dup-frac", type=float, default=0.25)
    args = ap.parse_args()
    enable_compile_cache()
    run_service(
        args.arch, requests=args.requests, batch=args.batch, seq=args.seq,
        theta=args.theta, lam=args.lam, dup_frac=args.dup_frac,
    )


if __name__ == "__main__":
    main()
