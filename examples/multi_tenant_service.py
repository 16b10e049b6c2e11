"""End-to-end driver: one engine serving many logical streams.

Eight tenants — each with its own similarity threshold and decay horizon —
submit tiny per-request batches that no single tenant could fill a
micro-batch with.  The multi-tenant runtime coalesces them onto one
stream-tagged device engine (DESIGN.md §9): cross-tenant pairs are masked
on device, per-tenant (θ, λ) rides a small device table, and the service
groups near-duplicates under namespaced (tenant, uid) keys.

The same traffic then replays on the **sharded** variant (DESIGN.md §10):
the identical service facade over ``ShardedFacade`` spreads the ring
window across P shards (real devices on a chip, virtual host devices on a
CPU) and must produce the identical per-tenant groups.

The final act is the **bursty-tenant demo** (DESIGN.md §11): one tenant
floods a deliberately undersized window at ~15× the others' rate.  Under
the default oldest-first eviction the flood overwrites the slow tenants'
still-live documents and their near-duplicate repost chains fall apart;
under ``eviction="quota"`` each tenant owns a static sub-ring, the burst
can only evict its own items, and every slow tenant's chain groups stay
intact.

    PYTHONPATH=src python examples/multi_tenant_service.py
"""

from repro.launch.mesh import require_devices, use_host_devices

N_SHARDS = 2
# a CPU run shards over virtual host devices; the flag must land before
# jax initializes its backends (the first repro import below that touches
# devices) — a run on a chip uses its real devices
use_host_devices(N_SHARDS)

import numpy as np  # noqa: E402

from repro.data.synth import bursty_tenant_traffic  # noqa: E402
from repro.runtime import TenantTable  # noqa: E402
from repro.serving import MultiTenantSSSJService  # noqa: E402

rng = np.random.default_rng(0)
K, DIM, ROUNDS, PER_SUBMIT = 8, 64, 12, 3

# strict tenants (high θ, short horizon) next to permissive ones
table = TenantTable(
    thetas=[0.95, 0.9, 0.85, 0.9, 0.95, 0.8, 0.9, 0.85],
    lams=[0.2, 0.05, 0.1, 0.02, 0.5, 0.05, 0.1, 0.2],
)

# every tenant periodically re-posts a noisy copy of its own base document
bases = rng.standard_normal((K, DIM)).astype(np.float32)
traffic = []                       # (tenant, docs, timestamps), replayable
t = 0.0
for r in range(ROUNDS):
    for k in range(K):
        docs = rng.standard_normal((PER_SUBMIT, DIM)).astype(np.float32)
        docs[0] = bases[k] + 0.01 * rng.standard_normal(DIM)
        traffic.append((k, docs, t + np.arange(PER_SUBMIT) * 1e-3))
        t += 0.01


def drive(svc):
    per_round = 0
    for k, docs, ts in traffic:
        svc.submit(k, docs, ts)
        per_round += 1
        if per_round % K == 0:
            svc.flush(final=False)  # coalesce: full micro-batches only
    svc.flush(final=True)
    return svc


svc = drive(MultiTenantSSSJService(table, dim=DIM, capacity=1024, micro_batch=32))
stats = svc.stats()
assert stats["n_items"] == K * ROUNDS * PER_SUBMIT
assert stats["pairs_dropped"] == 0
for k in range(K):
    groups = svc.duplicate_groups(k)
    # each tenant's planted repost chain groups under its OWN local uids;
    # nothing leaked across streams
    assert groups and max(len(g) for g in groups) >= ROUNDS // 2, (k, groups)
print(f"✓ {K} tenants, {stats['n_items']} documents on one engine; "
      f"padding waste {stats['padding_waste']:.1%}, "
      f"{stats['spans_dispatched']} device dispatches, "
      f"per-tenant groups e.g. tenant 0 → {svc.duplicate_groups(0)[:1]}")

# ---- sharded variant: same service, ring window over N_SHARDS shards ---- #
import jax  # noqa: E402

require_devices(N_SHARDS, "the sharded variant")
mesh = jax.make_mesh((N_SHARDS,), ("data",))
svc_sh = drive(MultiTenantSSSJService(
    table, dim=DIM, capacity=1024, micro_batch=32, mesh=mesh,
))
sh = svc_sh.stats()
assert sh["pairs_dropped"] == 0 and sh["n_shards"] == N_SHARDS
for k in range(K):
    assert svc_sh.duplicate_groups(k) == svc.duplicate_groups(k), k
print(f"✓ sharded: identical per-tenant groups over {N_SHARDS} shards "
      f"(per-shard live slots {sh['shards']['live_slots']}, "
      f"per-shard pairs {sh['shards']['pairs_emitted']})")

# ---- bursty-tenant demo: quota eviction keeps slow tenants intact ---- #
# tenant 0 floods BURST random documents per round into a 32-slot window;
# tenants 1..3 repost a noisy copy of their base every 1.5 time units
# (within their τ ≈ 2.2 horizon, so consecutive reposts should chain) —
# the same canonical flood stream the conformance suite and the eviction
# benchmark drive (repro.data.synth.bursty_tenant_traffic)
B_ROUNDS, BURST, B_CAP = 10, 45, 32
bursty_table = TenantTable(thetas=[0.9, 0.8, 0.8, 0.8],
                           lams=[2.0, 0.1, 0.1, 0.1])
bursty_submits, _ = bursty_tenant_traffic(3, B_ROUNDS, BURST, DIM)


def drive_bursty(svc):
    for k, docs, ts in bursty_submits:
        svc.submit(k, docs, ts)
    svc.flush(final=True)
    return svc


svc_old = drive_bursty(MultiTenantSSSJService(
    bursty_table, dim=DIM, capacity=B_CAP, micro_batch=16,
))                                               # eviction="oldest" default
svc_quo = drive_bursty(MultiTenantSSSJService(
    bursty_table, dim=DIM, capacity=B_CAP, micro_batch=16,
    eviction="quota",                            # equal split: 8 slots each
))
so, sq = svc_old.stats(), svc_quo.stats()
for k in (1, 2, 3):
    # quota: the whole repost chain survives as one group per tenant …
    assert svc_quo.duplicate_groups(k) == [list(range(B_ROUNDS))], k
    # … while oldest-first broke the chain (the flood evicted live reposts)
    assert svc_old.duplicate_groups(k) != [list(range(B_ROUNDS))], k
slow_lost_old = sum(so["window_overflow_by_tenant"][1:])
slow_lost_quo = sum(sq["window_overflow_by_tenant"][1:])
assert slow_lost_old > 0 and slow_lost_quo == 0
print(f"✓ bursty demo: oldest-first evicted {slow_lost_old} live slow-tenant "
      f"docs (groups broken, e.g. tenant 1 → {svc_old.duplicate_groups(1)}); "
      f"quota evicted {slow_lost_quo} (chains intact, "
      f"{sq['window_overflow_by_tenant'][0]} self-evictions stay the bursty "
      f"tenant's own problem)")
