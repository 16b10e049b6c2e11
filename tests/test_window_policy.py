"""Write-slot policy layer (DESIGN.md §11): property-based invariants of
:func:`repro.engine.window.select_write_slots` and the policy push.

Four contracts, each hypothesis-driven when the optional dependency is
present and a fixed seed sweep otherwise (same pattern as
``test_runtime.py``):

  * **uniqueness** — no two rows of a micro-batch ever select the same
    slot, under any policy (dropped rows route to the ``capacity``
    sentinel);
  * **split invariance** — pushing a batch whole or split at any point
    leaves identical ring state and cursors (``oldest`` always; ``dead``
    in the non-overflow regime, i.e. writes land on dead slots; ``quota``
    lane cursors always);
  * **quota conservation** — under arbitrary wrap, stream *k*'s items
    only ever occupy its own sub-ring, and no other stream's items leak
    in (slot counts are conserved);
  * **dead-first preference** — a live slot is never overwritten while a
    dead one exists: live overwrites equal exactly
    ``max(0, n_valid − n_dead)``.
"""

import numpy as np
import pytest

try:  # optional dev dependency: richer search when present, fixed sweep not
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax.numpy as jnp

from repro.engine.window import (
    init_window,
    push_with_overflow,
    quota_partition,
    select_write_slots,
)

D = 4
K = 3
TAU = 2.0


def _random_state(rng, cap, eviction="oldest", n_lanes=K, t_now=10.0):
    """A ring in a random but reachable shape: a mix of empty slots,
    expired (dead) slots, and live slots, random cursor and lane cursors."""
    state = init_window(cap, D, n_lanes=n_lanes, eviction=eviction)
    kind = rng.integers(0, 3, cap)              # 0 empty, 1 expired, 2 live
    ts = np.full(cap, 3.0e30, np.float32)
    uids = np.full(cap, -1, np.int32)
    sids = np.full(cap, -1, np.int32)
    filled = kind > 0
    n_fill = int(filled.sum())
    uids[filled] = rng.permutation(n_fill).astype(np.int32)
    sids[filled] = rng.integers(0, n_lanes, n_fill).astype(np.int32)
    ts[kind == 1] = t_now - TAU - 1.0 - rng.random((kind == 1).sum())
    # live items stay live for every t_max a batch can reach (≤ 48 rows at
    # 0.01 spacing → t_now + 0.48), so no cut point of a batch and no
    # choice of t_max moves a slot across the horizon
    ts[kind == 2] = t_now - (TAU - 0.5) * rng.random((kind == 2).sum())
    vecs = rng.standard_normal((cap, D)).astype(np.float32)
    vecs[~filled] = 0.0
    state = state._replace(
        vecs=jnp.asarray(vecs), ts=jnp.asarray(ts), uids=jnp.asarray(uids),
        sids=jnp.asarray(sids),
        cursor=jnp.asarray(rng.integers(0, cap), jnp.int32),
    )
    if state.lane_cursor is not None:
        state = state._replace(
            lane_cursor=jnp.asarray(
                rng.integers(0, 1 << 20, n_lanes), jnp.int32
            )
        )
    return state, kind, t_now


def _batch(rng, b, n_valid, t_now, uid0=1000):
    q = rng.standard_normal((b, D)).astype(np.float32)
    tq = (t_now + 0.01 * np.arange(b)).astype(np.float32)
    uq = np.arange(uid0, uid0 + b, dtype=np.int32)
    uq[n_valid:] = -1
    sq = rng.integers(0, K, b).astype(np.int32)
    return jnp.asarray(q), jnp.asarray(tq), jnp.asarray(uq), jnp.asarray(sq)


def _quotas(rng, cap):
    # quota_partition refuses fewer slots than streams: callers draw cap ≥ K
    return jnp.asarray(quota_partition(cap, rng.random(K) + 0.25), jnp.int32)


# --------------------------------------------------------------------- #
# uniqueness: no two rows of a micro-batch select the same slot
# --------------------------------------------------------------------- #
def _check_unique(seed, cap, b, eviction):
    rng = np.random.default_rng(seed)
    if eviction == "quota":
        cap = max(cap, K)
    ev = "quota" if eviction == "quota" else "oldest"
    state, _, t_now = _random_state(rng, cap, eviction=ev)
    n_valid = int(rng.integers(0, min(b, cap) + 1))
    _, _, _, sq = _batch(rng, b, n_valid, t_now)
    quotas = _quotas(rng, cap) if eviction == "quota" else None
    dest, _, _, self_evicted = select_write_slots(
        state, b, jnp.int32(n_valid), jnp.float32(t_now + 0.01 * b), TAU,
        sq=sq, eviction=eviction, quotas=quotas,
    )
    dest = np.asarray(dest)
    written = dest[dest < cap]
    assert written.size == np.unique(written).size, (eviction, dest)
    # every valid row either writes a slot or is an accounted self-eviction
    se = np.asarray(self_evicted)
    assert ((dest < cap) | se)[:n_valid].all()
    assert (dest[n_valid:] == cap).all() and not se[n_valid:].any()


@pytest.mark.parametrize("eviction", ["oldest", "dead", "quota"])
@pytest.mark.parametrize("seed,cap,b", [(0, 16, 8), (1, 32, 32), (2, 7, 5)])
def test_unique_slots_sweep(seed, cap, b, eviction):
    _check_unique(seed, cap, b, eviction)


# --------------------------------------------------------------------- #
# split invariance: one push vs the same rows split at any boundary
# --------------------------------------------------------------------- #
def _push(state, q, tq, uq, sq, n_valid, eviction, quotas):
    t_max = jnp.max(
        jnp.where(jnp.arange(q.shape[0]) < n_valid, tq, -jnp.inf),
        initial=-jnp.inf,
    )
    return push_with_overflow(
        state, q, tq, uq, jnp.int32(n_valid), t_max, TAU, sq=sq,
        eviction=eviction, quotas=quotas,
    )


def _states_equal(a, b):
    for name, x, y in zip(a._fields, a, b):
        if x is None and y is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=name
        )


def _check_split_invariance(seed, cap, b, eviction):
    rng = np.random.default_rng(seed)
    if eviction == "quota":
        cap = max(cap, K)
    ev = "quota" if eviction == "quota" else "oldest"
    state, kind, t_now = _random_state(rng, cap, eviction=ev)
    if eviction == "dead":
        # the guaranteed regime: enough dead slots for the whole batch
        # (overflow overwrites are policy-dependent across splits by design)
        b = min(b, int((kind != 2).sum()))
        if b == 0:
            return
    q, tq, uq, sq = _batch(rng, b, b, t_now)
    quotas = _quotas(rng, cap) if eviction == "quota" else None
    whole = _push(state, q, tq, uq, sq, b, eviction, quotas)
    cut = int(rng.integers(0, b + 1))
    first = _push(state, q[:cut], tq[:cut], uq[:cut], sq[:cut], cut,
                  eviction, quotas)
    second = _push(first, q[cut:], tq[cut:], uq[cut:], sq[cut:], b - cut,
                   eviction, quotas)
    _states_equal(whole, second)


@pytest.mark.parametrize("eviction", ["oldest", "dead", "quota"])
@pytest.mark.parametrize("seed,cap,b", [
    (0, 16, 8), (1, 32, 20), (2, 9, 9), (3, 24, 1),
])
def test_split_invariance_sweep(seed, cap, b, eviction):
    _check_split_invariance(seed, cap, b, eviction)


# --------------------------------------------------------------------- #
# quota: sub-ring containment is conserved under arbitrary wrap
# --------------------------------------------------------------------- #
def _check_quota_conservation(seed, cap, rounds):
    rng = np.random.default_rng(seed)
    state = init_window(cap, D, n_lanes=K, eviction="quota")
    quotas = _quotas(rng, cap)
    offs = np.concatenate([[0], np.cumsum(np.asarray(quotas))[:-1]])
    uid0 = 0
    t = 1.0
    for _ in range(rounds):
        b = int(rng.integers(1, cap + 1))
        q, tq, uq, sq = _batch(rng, b, b, t, uid0=uid0)
        state = _push(state, q, tq, uq, sq, b, "quota", quotas)
        uid0 += b
        t += 0.5
        sids = np.asarray(state.sids)
        uids = np.asarray(state.uids)
        for k in range(K):
            lo, hi = int(offs[k]), int(offs[k]) + int(quotas[k])
            inside = sids[lo:hi]
            # stream k's sub-ring holds only stream-k items (or empties) …
            assert set(np.unique(inside)) <= {-1, k}, (k, inside)
            # … and stream k's items never appear anywhere else
            outside = np.concatenate([sids[:lo], sids[hi:]])
            assert not (outside == k).any(), k
        # lane cursors stay inside their sub-rings
        lc = np.asarray(state.lane_cursor)
        assert (0 <= lc).all() and (lc < np.asarray(quotas)).all()
        assert (uids[sids == -1] == -1).all()


@pytest.mark.parametrize("seed,cap,rounds", [(0, 16, 6), (1, 31, 8), (2, 8, 12)])
def test_quota_conservation_sweep(seed, cap, rounds):
    _check_quota_conservation(seed, cap, rounds)


# --------------------------------------------------------------------- #
# dead-first: live overwrites happen only once every dead slot is used
# --------------------------------------------------------------------- #
def _check_dead_first_preference(seed, cap, b):
    rng = np.random.default_rng(seed)
    state, kind, t_now = _random_state(rng, cap)
    b = min(b, cap)
    n_valid = int(rng.integers(0, b + 1))
    q, tq, uq, sq = _batch(rng, b, n_valid, t_now)
    t_max = jnp.float32(t_now + 0.01 * b)
    dead = np.asarray(
        (state.uids < 0) | (t_max - state.ts > TAU)
    )
    dest, _, _, _ = select_write_slots(
        state, b, jnp.int32(n_valid), t_max, TAU, sq=sq, eviction="dead",
    )
    dest = np.asarray(dest)
    written = dest[dest < cap]
    live_hits = int((~dead[written]).sum())
    assert live_hits == max(0, n_valid - int(dead.sum()))
    # and the policy push counts exactly those as overflow
    new = _push(state, q, tq, uq, sq, n_valid, "dead", None)
    assert int(new.overflow) == live_hits
    assert int(np.asarray(new.lane_overflow).sum()) == live_hits


@pytest.mark.parametrize("seed,cap,b", [(0, 16, 16), (1, 12, 7), (2, 6, 6)])
def test_dead_first_preference_sweep(seed, cap, b):
    _check_dead_first_preference(seed, cap, b)


# --------------------------------------------------------------------- #
# quota self-eviction: wrapping one sub-ring inside a single micro-batch
# keeps the newest writer per slot and counts the earlier rows as overflow
# --------------------------------------------------------------------- #
def test_quota_self_eviction_accounted():
    state = init_window(6, D, n_lanes=2, eviction="quota")
    quotas = jnp.asarray([2, 4], jnp.int32)
    rng = np.random.default_rng(5)
    b = 5
    q = jnp.asarray(rng.standard_normal((b, D)), jnp.float32)
    tq = jnp.asarray(1.0 + 0.01 * np.arange(b), jnp.float32)
    uq = jnp.asarray(np.arange(b), jnp.int32)
    sq = jnp.asarray([0, 0, 0, 1, 1], jnp.int32)   # 3 rows into a 2-slot ring
    new = _push(state, q, tq, uq, sq, b, "quota", quotas)
    uids = np.asarray(new.uids)
    # newest two of stream 0 survive, in sub-ring order (cursor wrapped)
    assert sorted(uids[:2].tolist()) == [1, 2]
    assert uids[2:4].tolist() == [3, 4] and (uids[4:] == -1).all()
    assert int(new.overflow) == 1
    assert np.asarray(new.lane_overflow).tolist() == [1, 0]


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cap=st.integers(2, 48),
        b=st.integers(1, 48),
        eviction=st.sampled_from(["oldest", "dead", "quota"]),
    )
    def test_unique_slots_property(seed, cap, b, eviction):
        _check_unique(seed, cap, min(b, cap), eviction)

    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cap=st.integers(2, 48),
        b=st.integers(1, 48),
        eviction=st.sampled_from(["oldest", "dead", "quota"]),
    )
    def test_split_invariance_property(seed, cap, b, eviction):
        _check_split_invariance(seed, cap, min(b, cap), eviction)

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cap=st.integers(3, 32),
        rounds=st.integers(1, 8),
    )
    def test_quota_conservation_property(seed, cap, rounds):
        _check_quota_conservation(seed, cap, rounds)

    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cap=st.integers(1, 48),
        b=st.integers(1, 48),
    )
    def test_dead_first_preference_property(seed, cap, b):
        _check_dead_first_preference(seed, cap, b)
