"""Event-queue semantics: pop order must equal the reference total order
(time, Packet<Local, src_host, seq) — reference src/main/core/work/event.rs:104-155 —
validated property-style against a plain Python sorted list."""

import random
import re

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu import equeue
from shadow_tpu.equeue import PAYLOAD_LANES
from shadow_tpu.events import KIND_PACKET, pack_tie, tie_seq, tie_src_host, tie_is_local
from shadow_tpu.simtime import TIME_MAX


def _mk_events(rng, n, num_hosts, seq_base=0):
    evs = []
    for i in range(n):
        t = rng.randrange(0, 50)
        kind = rng.choice([KIND_PACKET, 1, 2])
        src = rng.randrange(num_hosts)
        seq = seq_base + i
        data = [rng.randrange(100) for _ in range(PAYLOAD_LANES)]
        evs.append((t, kind, src, seq, data))
    return evs


def test_tie_packing_roundtrip():
    tie = pack_tie(3, 12345, 678)
    assert tie_src_host(tie) == 12345
    assert tie_seq(tie) == 678
    assert tie_is_local(tie) == 1
    tie_p = pack_tie(KIND_PACKET, 1, 2)
    assert tie_is_local(tie_p) == 0
    assert tie_p < tie  # packets sort before locals at equal time


def test_push_pop_single_host_matches_sorted_order():
    rng = random.Random(7)
    H, Q, N = 3, 64, 40
    q = equeue.create(H, Q)
    expect = {h: [] for h in range(H)}
    evs = _mk_events(rng, N, H)
    for t, kind, src, seq, data in evs:
        dsth = rng.randrange(H)
        tie = pack_tie(kind, src, seq)
        q = equeue.push_many(
            q,
            dst=jnp.array([dsth], jnp.int32),
            valid=jnp.array([True]),
            time=jnp.array([t], jnp.int64),
            tie=jnp.array([tie], jnp.int64),
            kind=jnp.array([kind], jnp.int32),
            data=jnp.array([data], jnp.int32),
        )
        expect[dsth].append((t, tie, kind, tuple(data)))

    assert int(q.overflow.sum()) == 0
    assert [int(c) for c in q.count] == [len(expect[h]) for h in range(H)]

    # pop everything from all hosts simultaneously; per-host order must match
    got = {h: [] for h in range(H)}
    for _ in range(max(len(v) for v in expect.values())):
        ev, q = equeue.pop_min(q, jnp.ones((H,), bool))
        for h in range(H):
            if bool(ev.valid[h]):
                got[h].append((int(ev.time[h]), int(ev.tie[h]), int(ev.kind[h]), tuple(int(x) for x in ev.data[h])))
    for h in range(H):
        assert got[h] == sorted(expect[h]), f"host {h}"
    assert int(q.count.sum()) == 0
    assert int(jnp.min(q.time)) == TIME_MAX


def test_batched_push_with_conflicts():
    rng = random.Random(3)
    H, Q, M = 5, 32, 60
    q = equeue.create(H, Q)
    dst = [rng.randrange(H) for _ in range(M)]
    valid = [rng.random() < 0.8 for _ in range(M)]
    evs = _mk_events(rng, M, H)
    ties = [pack_tie(k, s, sq) for (_, k, s, sq, _) in evs]
    q = equeue.push_many(
        q,
        dst=jnp.array(dst, jnp.int32),
        valid=jnp.array(valid),
        time=jnp.array([e[0] for e in evs], jnp.int64),
        tie=jnp.array(ties, jnp.int64),
        kind=jnp.array([e[1] for e in evs], jnp.int32),
        data=jnp.array([e[4] for e in evs], jnp.int32),
    )
    expect = {h: [] for h in range(H)}
    for i in range(M):
        if valid[i]:
            t, k, _, _, d = evs[i]
            expect[dst[i]].append((t, ties[i], k, tuple(d)))
    for h in range(H):
        assert equeue.debug_sorted_events(q, h) == sorted(expect[h])


def test_push_self_and_overflow():
    H, Q = 4, 2
    q = equeue.create(H, Q)
    for i in range(3):  # third push overflows every host
        q = equeue.push_self(
            q,
            valid=jnp.ones((H,), bool),
            time=jnp.full((H,), 10 + i, jnp.int64),
            tie=jnp.array([pack_tie(1, h, i) for h in range(H)], jnp.int64),
            kind=jnp.full((H,), 1, jnp.int32),
            data=jnp.zeros((H, PAYLOAD_LANES), jnp.int32),
        )
    np.testing.assert_array_equal(np.asarray(q.count), 2)
    np.testing.assert_array_equal(np.asarray(q.overflow), 1)


def test_pop_respects_want_mask_and_empty_hosts():
    H, Q = 3, 4
    q = equeue.create(H, Q)
    q = equeue.push_self(
        q,
        valid=jnp.array([True, False, True]),
        time=jnp.array([5, 0, 9], jnp.int64),
        tie=jnp.array([pack_tie(1, h, 0) for h in range(H)], jnp.int64),
        kind=jnp.full((H,), 1, jnp.int32),
        data=jnp.zeros((H, PAYLOAD_LANES), jnp.int32),
    )
    ev, q = equeue.pop_min(q, jnp.array([True, True, False]))
    assert bool(ev.valid[0]) and not bool(ev.valid[1]) and not bool(ev.valid[2])
    assert int(ev.time[0]) == 5
    assert [int(c) for c in q.count] == [0, 0, 1]


def test_push_many_sorted_overflow_never_misroutes():
    """Regression (round-4 advisor, high): when one destination receives
    more than deliver_lanes entries, other hosts' deliveries must be
    unaffected and no entry may land on a wrong host with valid=True —
    overflow is dropped and counted, never misrouted."""
    H, Q, D = 4, 16, 2
    q = equeue.create(H, Q)
    # 4 entries to host 1 (two beyond D), 2 to host 0, 1 to host 3;
    # m=7 <= H*D=8, the exact regime the advisor flagged
    dst = [1, 1, 0, 1, 1, 0, 3]
    evs = _mk_events(random.Random(11), len(dst), H)
    ties = [pack_tie(k, s, sq) for (_, k, s, sq, _) in evs]
    q = equeue.push_many_sorted(
        q,
        dst=jnp.array(dst, jnp.int32),
        valid=jnp.ones((len(dst),), bool),
        time=jnp.array([e[0] for e in evs], jnp.int64),
        tie=jnp.array(ties, jnp.int64),
        kind=jnp.array([e[1] for e in evs], jnp.int32),
        data=jnp.array([e[4] for e in evs], jnp.int32),
        deliver_lanes=D,
    )
    sent = {h: [] for h in range(H)}
    for i, d in enumerate(dst):
        t, k, _, _, payload = evs[i]
        sent[d].append((t, ties[i], k, tuple(payload)))
    total_delivered = 0
    for h in range(H):
        got = equeue.debug_sorted_events(q, h)
        # every delivered event must be one this host was actually sent
        for item in got:
            assert item in sent[h], f"host {h} received a misrouted event {item}"
        total_delivered += len(got)
    # hosts within their lane budget receive everything, even while
    # another destination overflows
    assert len(equeue.debug_sorted_events(q, 0)) == 2
    assert len(equeue.debug_sorted_events(q, 3)) == 1
    # host 1 keeps exactly D of its 4 (arrival order); the rest are loud
    assert len(equeue.debug_sorted_events(q, 1)) == D
    assert int(jnp.sum(q.overflow)) == len(dst) - total_delivered == 2


def test_push_many_sorted_overflow_m_gt_grid_property():
    """The repair path's other static regime: m > H*D (no padding; filler
    slack comes only from invalid entries). Deliveries must equal exactly
    the first D entries per destination in arrival order."""
    rng = random.Random(23)
    H, Q, D, M = 3, 64, 2, 20
    for trial in range(8):
        q = equeue.create(H, Q)
        dst = [rng.randrange(H) for _ in range(M)]
        valid = [rng.random() < 0.7 for _ in range(M)]
        evs = _mk_events(rng, M, H, seq_base=trial * M)
        ties = [pack_tie(k, s, sq) for (_, k, s, sq, _) in evs]
        q = equeue.push_many_sorted(
            q,
            dst=jnp.array(dst, jnp.int32),
            valid=jnp.array(valid),
            time=jnp.array([e[0] for e in evs], jnp.int64),
            tie=jnp.array(ties, jnp.int64),
            kind=jnp.array([e[1] for e in evs], jnp.int32),
            data=jnp.array([e[4] for e in evs], jnp.int32),
            deliver_lanes=D,
        )
        sent = {h: [] for h in range(H)}
        for i in range(M):
            if valid[i]:
                t, k, _, _, payload = evs[i]
                sent[dst[i]].append((t, ties[i], k, tuple(payload)))
        delivered = 0
        for h in range(H):
            got = equeue.debug_sorted_events(q, h)
            # multiset/order-exact: the first D arrivals for h, sorted
            assert got == sorted(sent[h][:D]), f"trial {trial} host {h}"
            delivered += len(got)
        n_sent = sum(len(v) for v in sent.values())
        assert int(jnp.sum(q.overflow)) == n_sent - delivered


def _push_many_grid_ref(q, dst, valid, time, tie, kind, data, aux, deliver_lanes):
    """The landing as it was until PR 27, kept here as the plain reference:
    a dest-major [H, D] delivery grid filled by one row scatter (slot
    dst * D + rank of the stable destination sort) and merged into the
    queue rows lane by lane through push_self_lanes."""
    m, h = dst.shape[0], q.num_hosts
    d = min(deliver_lanes, m)
    key1 = jnp.where(valid, dst, h).astype(jnp.int32)
    pos = jnp.arange(m, dtype=jnp.int32)
    key1_s, order = jax.lax.sort((key1, pos), num_keys=1, is_stable=True)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), key1_s[1:] != key1_s[:-1]])
    rank = pos - jax.lax.cummax(jnp.where(seg_start, pos, -1))
    fits = (key1_s < h) & (rank < d)
    slot = jnp.where(fits, key1_s * d + rank, h * d)  # OOB -> dropped

    def words(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def long(x):
        return jax.lax.bitcast_convert_type(x, jnp.int64)

    rows = jnp.concatenate(
        [words(time), words(tie), kind[:, None], aux[:, None],
         jnp.ones((m, 1), jnp.int32), data], axis=1)
    g = (jnp.zeros((h * d, rows.shape[1]), jnp.int32).at[slot]
         .set(rows[order], mode="drop").reshape(h, d, rows.shape[1]))
    g_valid = g[:, :, 6] != 0
    beyond_d = jnp.sum(valid, dtype=jnp.int32) - jnp.sum(g_valid, dtype=jnp.int32)
    q2 = equeue.push_self_lanes(
        q, valid=g_valid, time=long(g[:, :, 0:2]), tie=long(g[:, :, 2:4]),
        kind=g[:, :, 4], data=g[:, :, 7:], aux=g[:, :, 5])
    return q2.replace(overflow=q2.overflow.at[0].add(beyond_d))


def _batch(rng, m, hosts, p_valid=0.8, dst=None, t0=0):
    """One push batch of m entries: (dst, valid, time, tie, kind, data, aux)."""
    if dst is None:
        dst = rng.integers(0, hosts, m)
    return (
        jnp.asarray(dst, jnp.int32),
        jnp.asarray(rng.random(m) < p_valid),
        jnp.asarray(t0 + rng.integers(0, 50, m), jnp.int64),
        jnp.asarray(rng.integers(0, 1 << 62, m), jnp.int64),
        jnp.asarray(rng.integers(1, 5, m), jnp.int32),
        jnp.asarray(rng.integers(0, 1 << 30, (m, PAYLOAD_LANES)), jnp.int32),
        jnp.asarray(rng.integers(0, 1500, m), jnp.int32),
    )


def _pop_some(rng, q, rounds):
    for _ in range(rounds):
        _, q = equeue.pop_min(q, jnp.asarray(rng.random(q.num_hosts) < 0.6))
    return q


K = equeue.LAND_LANES

# name -> (H, Q, D, [batches as (M, kwargs of _batch)], pops between batches)
_LANDING_CASES = {
    "empty_batch": (4, 8, 8, [(0, {})], 0),
    "all_invalid": (4, 8, 8, [(12, {"p_valid": 0.0})], 0),
    "d_below_fan_in_row0_overflow": (4, 16, 2, [(24, {"p_valid": 1.0})], 0),
    "room_below_fan_in_row_overflow": (3, 4, 4, [(9, {}), (9, {}), (9, {})], 0),
    "tombstones_from_interleaved_pops": (5, 12, 12, [(20, {})] * 5, 3),
    "tombstones_and_narrow_d": (5, 12, 3, [(20, {})] * 5, 2),
    "m_below_h": (16, 8, 8, [(5, {}), (3, {"p_valid": 1.0})], 1),
    "m_above_h_times_d": (3, 64, 2, [(20, {}), (20, {})], 1),
    "one_destination_takes_everything": (
        4, 8, 8, [(6, {"dst": [2] * 6}), (6, {"dst": [2] * 6, "p_valid": 1.0})], 1),
    "d_one": (4, 8, 1, [(10, {}), (10, {})], 1),
    # the loop's edges (K = LAND_LANES arrival lanes a pass): a fan-in that
    # ends on, one past and well past a pass, onto rows that interleaved
    # pops left with tombstones
    "fan_in_exactly_k": (4, 4 * K, 4 * K, [(K + 6, {"dst": [1] * K + [0, 2, 3] * 2})] * 3, 2),
    "fan_in_k_plus_one": (
        4, 4 * K, 4 * K, [(K + 4, {"dst": [3] * (K + 1) + [0, 1, 2], "p_valid": 1.0})] * 3, 2),
    "fan_in_three_k_plus_one": (
        5, 8 * K, 8 * K,
        [(3 * K + 5, {"dst": [2] + [0, 1, 3, 4] + [2] * (3 * K), "p_valid": 1.0})] * 2, 3),
    "room_ends_inside_a_pass": (
        3, K + K // 2 + 1, 4 * K,
        [(2 * K + 3, {"dst": [1] * (2 * K) + [0, 2, 2], "p_valid": 1.0})] * 2, 1),
    "deliver_lanes_end_inside_a_pass": (
        3, 4 * K, K + 3, [(2 * K + 3, {"dst": [0] * (2 * K) + [1, 2, 2], "p_valid": 1.0})] * 2, 1),
    "one_destination_takes_the_whole_queue": (
        4, 3 * K, 3 * K, [(3 * K + 2, {"dst": [2] * (3 * K + 2), "p_valid": 1.0})], 0),
}


@pytest.mark.parametrize("case", sorted(_LANDING_CASES))
def test_push_many_sorted_equals_grid_reference(case):
    """The pull landing against the delivery-grid spelling it replaced:
    every queue leaf, bit for bit — slot placement, the stale contents of
    popped slots, count, per-row overflow, row-0 overflow beyond
    deliver_lanes, head_time — after every push of the case."""
    hosts, cap, d, batches, pops = _LANDING_CASES[case]
    rng = np.random.default_rng(sorted(_LANDING_CASES).index(case))
    q_new = q_ref = equeue.create(hosts, cap)
    for i, (m, kw) in enumerate(batches):
        ent = _batch(rng, m, hosts, t0=40 * i, **kw)
        q_new = equeue.push_many_sorted(q_new, *ent, deliver_lanes=d)
        q_ref = _push_many_grid_ref(q_ref, *ent, deliver_lanes=d)
        for name in ("time", "tie", "kind", "data", "aux", "count", "overflow", "head_time"):
            np.testing.assert_array_equal(
                np.asarray(getattr(q_new, name)), np.asarray(getattr(q_ref, name)),
                err_msg=f"{case}: leaf {name} after batch {i}")
        q_new = q_ref = _pop_some(rng, q_new, pops)


@pytest.mark.parametrize("case", sorted(_LANDING_CASES))
def test_landing_makes_as_many_passes_as_the_busiest_destination_needs(case, monkeypatch):
    """The landing's loop body runs ceil(max_h land[h] / K) times a push,
    land[h] the arrivals row h took (its count's growth): none for a batch
    that is empty or all invalid. max_land is what land_sorted hands back,
    land_passes what the tracker plane books of it."""
    hosts, cap, d, batches, pops = _LANDING_CASES[case]
    rng = np.random.default_rng(sorted(_LANDING_CASES).index(case))
    ran = []

    def counted_loop(cond, body, carry):
        ran.append(0)
        while bool(cond(carry)):
            carry = body(carry)
            ran[-1] += 1
        return carry

    monkeypatch.setattr(jax.lax, "while_loop", counted_loop)
    q = equeue.create(hosts, cap)
    for i, (m, kw) in enumerate(batches):
        ent = _batch(rng, m, hosts, t0=40 * i, **kw)
        before = np.asarray(q.count)
        q, max_land = equeue.land_sorted(q, *ent, deliver_lanes=d)
        most = int((np.asarray(q.count) - before).max())
        assert int(max_land) == most
        assert int(equeue.land_passes(max_land)) == -(-most // K)
        assert (ran.pop() if m else 0) == -(-most // K) and not ran
        q = _pop_some(rng, q, pops)
    if case in ("empty_batch", "all_invalid"):
        assert most == 0


def test_vmapped_landing_gives_each_replica_its_own_leaves():
    """Two replicas under jax.vmap with different fan-in (one arrival a
    destination against 3 K + 1 on one): the batched loop runs to the
    larger pass count, and each replica's queue and max_land are what it
    gets alone."""
    hosts, cap = 4, 8 * K
    rng = np.random.default_rng(77)
    m = 3 * K + 4
    wide = _batch(rng, m, hosts, p_valid=1.0, dst=[0, 1, 3] + [2] * (3 * K + 1))
    thin = _batch(rng, m, hosts, dst=(list(range(hosts)) * m)[:m])
    thin = (thin[0], thin[1].at[:hosts].set(True).at[hosts:].set(False)) + thin[2:]
    q0 = _pop_some(rng, equeue.push_many(equeue.create(hosts, cap), *_batch(rng, 40, hosts)), 3)
    q1 = _pop_some(rng, equeue.push_many(equeue.create(hosts, cap), *_batch(rng, 40, hosts)), 2)
    alone = [equeue.land_sorted(q, *ent) for q, ent in ((q0, thin), (q1, wide))]
    assert [int(n) for _, n in alone] == [1, 3 * K + 1]

    def stack(a, b):
        return jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)

    got_q, got_n = jax.vmap(equeue.land_sorted)(stack(q0, q1), *stack(thin, wide))
    assert got_n.tolist() == [1, 3 * K + 1]
    for r, (want, _) in enumerate(alone):
        for a, b in zip(jax.tree.leaves(got_q), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a[r]), np.asarray(b), err_msg=f"replica {r}")


def test_push_many_at_time_max_counted_on_row_zero():
    """A batched push at TIME_MAX never takes a slot: it is masked before
    the sort and counted on overflow row 0;
    the other entries land as if it had not been sent."""
    H, Q = 3, 4
    dst, valid, time, tie, kind, data, aux = _batch(np.random.default_rng(5), 6, H, p_valid=1.0)
    time = time.at[2].set(TIME_MAX)
    q = equeue.push_many(equeue.create(H, Q), dst, valid, time, tie, kind, data, aux)
    keep = np.arange(6) != 2
    want = equeue.push_many(
        equeue.create(H, Q), dst[keep], valid[keep], time[keep], tie[keep],
        kind[keep], data[keep], aux[keep])
    assert int(q.overflow[0]) == int(want.overflow[0]) + 1
    q = q.replace(overflow=want.overflow)
    for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    free = np.asarray(q.time) == TIME_MAX
    assert free.sum(axis=1).tolist() == (Q - np.asarray(q.count)).tolist()


def test_push_at_time_max_rejected_loudly():
    """The TIME_MAX free-slot invariant: a push at the sentinel time is
    rejected and counted into overflow instead of desyncing occupancy."""
    H, Q = 2, 4
    q = equeue.create(H, Q)
    q = equeue.push_self(
        q,
        valid=jnp.array([True, True]),
        time=jnp.array([5, TIME_MAX], jnp.int64),
        tie=jnp.array([pack_tie(1, h, 0) for h in range(H)], jnp.int64),
        kind=jnp.full((H,), 1, jnp.int32),
        data=jnp.zeros((H, PAYLOAD_LANES), jnp.int32),
    )
    assert [int(c) for c in q.count] == [1, 0]
    assert [int(o) for o in q.overflow] == [0, 1]
    # occupancy stays consistent: free slots == capacity - count
    free = np.asarray(q.time) == TIME_MAX
    assert free.sum(axis=1).tolist() == [Q - 1, Q]


# --- the pop: one reduction for the slot and its tie, ONE gather for the rest --

_I64_MAX = np.iinfo(np.int64).max
_POP_SCENARIOS = ("empty_rows", "shared_minimum", "tombstones", "full_rows", "want_false")


def _pop_world(cap, scenario, hosts=6):
    """(queue, want): a queue written leaf by leaf, every payload word of
    every slot distinct (free slots too: a tombstone keeps its stale
    words), shaped by `scenario`."""
    rng = np.random.default_rng([cap, _POP_SCENARIOS.index(scenario)])
    live = rng.random((hosts, cap)) < {"tombstones": 0.3, "full_rows": 1.0}.get(scenario, 0.6)
    live[:, -1] = True  # no row empty unless the scenario empties it
    time = rng.integers(0, 40, (hosts, cap))
    tie = rng.permutation(hosts * cap).reshape(hosts, cap) + 1000  # all distinct
    if scenario == "empty_rows":
        live[[0, 2]] = False
    if scenario == "shared_minimum":
        time[:] = rng.integers(5, 8, (hosts, cap))  # many slots share the minimum time
        time[:, -1] = 5
    if scenario == "tombstones":
        live[:, 0] = False  # the first slot is stale, the minimum sits late in the row
        time[:, -1] = 0
    # word w of slot s of host h; w = 0, 1 are kind and aux, the rest the data lanes
    words = (np.arange(hosts)[:, None, None] * 1_000_000
             + np.arange(cap)[None, :, None] * 16
             + np.arange(2 + PAYLOAD_LANES)[None, None, :]).astype(np.int32)
    time = np.where(live, time, TIME_MAX)
    q = equeue.EventQueue(
        time=jnp.asarray(time, jnp.int64),
        tie=jnp.asarray(np.where(live, tie, _I64_MAX), jnp.int64),
        kind=jnp.asarray(words[:, :, 0]),
        data=jnp.asarray(words[:, :, 2:]),
        aux=jnp.asarray(words[:, :, 1]),
        count=jnp.asarray(live.sum(1), jnp.int32),
        overflow=jnp.zeros((hosts,), jnp.int32),
        head_time=jnp.asarray(time.min(1), jnp.int64),
    )
    want = rng.random(hosts) < 0.5 if scenario == "want_false" else np.ones(hosts, bool)
    return q, jnp.asarray(want)


def _plain_pop(q, want):
    """The pop, host by host in plain Python over NumPy copies of the
    leaves: (per host the head event with its slot, or None; the leaves after)."""
    time, tie, count = np.array(q.time), np.array(q.tie), np.array(q.count)
    kind, aux, data = np.asarray(q.kind), np.asarray(q.aux), np.asarray(q.data)
    events = []
    for h in range(time.shape[0]):
        live = [s for s in range(time.shape[1]) if time[h, s] != TIME_MAX]
        if not live:
            events.append(None)
            continue
        s = min(live, key=lambda s: (time[h, s], tie[h, s], s))
        events.append(dict(
            slot=s, time=int(time[h, s]), tie=int(tie[h, s]), kind=int(kind[h, s]),
            aux=int(aux[h, s]), data=[int(x) for x in data[h, s]],
        ))
        if want[h]:
            time[h, s], tie[h, s] = TIME_MAX, _I64_MAX
            count[h] -= 1
    return events, dict(time=time, tie=tie, kind=kind, aux=aux, data=data, count=count,
                        head_time=time.min(1))


@pytest.mark.parametrize("scenario", _POP_SCENARIOS)
@pytest.mark.parametrize("cap", [4, 64, 384])
def test_pop_equals_a_plain_numpy_pop(cap, scenario):
    q, want = _pop_world(cap, scenario)
    pop = jax.jit(equeue.pop_min)
    for _ in range(3):  # three pops in a row: tombstones of this very pop included
        events, after = _plain_pop(q, np.asarray(want))
        ev, q = pop(q, want)
        for h, e in enumerate(events):
            assert bool(ev.valid[h]) == (e is not None and bool(want[h])), h
            if e is None:  # an empty row peeks the free slots' keys
                assert int(ev.time[h]) == TIME_MAX and int(ev.tie[h]) == _I64_MAX
                continue
            got = dict(time=int(ev.time[h]), tie=int(ev.tie[h]), kind=int(ev.kind[h]),
                       aux=int(ev.aux[h]), data=[int(x) for x in ev.data[h]])
            assert got == {k: v for k, v in e.items() if k != "slot"}, (h, e["slot"])
        for name, leaf in after.items():
            np.testing.assert_array_equal(np.asarray(getattr(q, name)), leaf, err_msg=name)
        assert int(q.overflow.sum()) == 0


def _drain_row(q, host):
    """Every event of `host` in pop order as (time, tie, kind, aux, data)."""
    out = []
    while int(q.count[host]):
        ev, q = equeue.pop_min(q, jnp.ones((q.num_hosts,), bool))
        out.append((int(ev.time[host]), int(ev.tie[host]), int(ev.kind[host]),
                    int(ev.aux[host]), tuple(int(x) for x in ev.data[host])))
    return out


@pytest.mark.parametrize("cap", [8, 64])
@pytest.mark.parametrize("writer", ["push_self_lanes", "land_sorted"])
def test_popped_words_are_the_pushed_ones(writer, cap):
    """kind, aux and the eight data lanes come back word for word: after a
    lane push, and after a landing of LAND_LANES + 1 arrivals to one row
    (two passes of its loop), onto a row that holds a tombstone."""
    hosts, n = 3, equeue.LAND_LANES + 1
    rng = np.random.default_rng(cap)
    q = equeue.create(hosts, cap)
    q = equeue.push_self(  # one event a row, popped again: slot 0 is stale, not empty
        q, jnp.ones((hosts,), bool), jnp.zeros((hosts,), jnp.int64),
        jnp.arange(hosts, dtype=jnp.int64), jnp.full((hosts,), 9, jnp.int32),
        jnp.full((hosts, PAYLOAD_LANES), -7, jnp.int32), jnp.full((hosts,), 77, jnp.int32),
    )
    _, q = equeue.pop_min(q, jnp.ones((hosts,), bool))
    time = rng.integers(1, 30, (hosts, n))
    tie = rng.permutation(hosts * n).reshape(hosts, n)
    kind = rng.integers(0, 5, (hosts, n)).astype(np.int32)
    aux = rng.integers(0, 1 << 24, (hosts, n)).astype(np.int32)
    data = rng.integers(-(1 << 31), 1 << 31, (hosts, n, PAYLOAD_LANES)).astype(np.int32)
    if writer == "push_self_lanes":
        q = equeue.push_self_lanes(
            q, jnp.ones((hosts, n), bool), jnp.asarray(time, jnp.int64),
            jnp.asarray(tie, jnp.int64), jnp.asarray(kind), jnp.asarray(data), jnp.asarray(aux),
        )
        rows = range(hosts)
    else:  # all n arrivals to row 1, shuffled among invalid entries
        m = 3 * n
        at = rng.permutation(m)[:n]

        def spread(x, dtype=jnp.int32):  # x's n rows at `at` among m, zeros between
            out = np.zeros((m,) + x.shape[1:], x.dtype)
            out[at] = x
            return jnp.asarray(out, dtype)

        q, max_land = equeue.land_sorted(
            q, jnp.ones((m,), jnp.int32), spread(np.ones(n, bool), bool),
            spread(time[1], jnp.int64), spread(tie[1], jnp.int64),
            spread(kind[1]), spread(data[1]), spread(aux[1]),
        )
        assert int(max_land) == n and int(equeue.land_passes(max_land)) == 2
        rows = [1]
    assert int(q.overflow.sum()) == 0
    for h in rows:
        pushed = sorted(
            (int(time[h, l]), int(tie[h, l]), int(kind[h, l]), int(aux[h, l]),
             tuple(int(x) for x in data[h, l])) for l in range(n)
        )
        assert _drain_row(q, h) == pushed, h


def _gathers(jaxpr):
    """gather equations of a jaxpr, those of the functions it calls included."""
    return sum(
        (eqn.primitive.name == "gather")
        + sum(_gathers(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns
    )


def _reads_a_gather(jaxpr, outvar):
    """Whether `outvar` of `jaxpr` is computed from any gather's result (an
    equation that calls a function with a gather inside counts whole)."""
    needed = {outvar}
    for eqn in reversed(jaxpr.eqns):
        if not needed & set(eqn.outvars):
            continue
        if eqn.primitive.name == "gather" or any(
            _gathers(sub) for sub in jax.core.jaxprs_in_params(eqn.params)
        ):
            return True
        needed |= {v for v in eqn.invars if isinstance(v, jax.extend.core.Var)}
    return False


def test_lowered_pop_holds_one_gather_and_only_the_payload_reads_it():
    q, want = _pop_world(64, "tombstones")
    text = jax.jit(equeue.pop_min).lower(q, want).as_text()
    # the module's functions by name; a count follows the calls a body makes
    bodies = {
        m.group(1): m.group(0) for m in
        re.finditer(r"func\.func (?:public |private )?@(\w+)\((?s:.*?)\n  }\n", text)
    }

    def count(fn):
        return bodies[fn].count('"stablehlo.gather"(') + sum(
            count(callee) for callee in re.findall(r"call @(\w+)\(", bodies[fn]))

    assert count("main") == 1, text
    closed = jax.make_jaxpr(equeue.pop_min)(q, want)
    out = jax.eval_shape(equeue.pop_min, q, want)
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(out)]
    by_path = dict(zip(paths, closed.jaxpr.outvars))
    for field in ("time", "tie", "kind", "aux"):  # out of the row's one reduction
        assert not _reads_a_gather(closed.jaxpr, by_path[f"[0].{field}"]), field
    assert _reads_a_gather(closed.jaxpr, by_path["[0].data"])  # the check sees the one
