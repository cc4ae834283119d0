"""The landing's run counts (equeue.run_bounds, scope exchange/land/count).

`push_many_sorted` needs, for every destination, how many of the batch's
entries it receives (`cnt`) and where its run starts once the batch is
sorted by destination (`begin`). Pinned here, on the CPU, through the
function `push_many_sorted` itself calls:

  * `cnt` equals `numpy.bincount` of the valid keys and `begin` its
    exclusive cumulative sum, at the shapes that could break a one-hot
    over 128-host blocks: hosts not a multiple of 128, more than 2**15
    hosts, an empty batch, every entry to one host, hosts at both ends of
    the id range, invalid entries (key == hosts);
  * `push_many_sorted` lands by those counts: row counts, the rows'
    own overflow and the global overflow on row 0 equal a numpy model on a
    seeded batch with full and overflowing rows;
  * the lowered landing holds the ONE product `[blocks, M] x [M, 128]`:
    the run counts are a single spelling, chosen by nothing (PR 32 read
    `exchange.count_ms_per_unit` at 524,288 hosts before deciding so;
    PERF.md section 6).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu import equeue
from shadow_tpu.equeue import PAYLOAD_LANES, EventQueue, push_many_sorted, run_bounds
from shadow_tpu.simtime import TIME_MAX


def _keys(case: str, h: int, m: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if case == "one-destination":
        return np.full(m, h // 3, np.int32)
    if case == "both-ends":
        return rng.choice(np.array([0, h - 1], np.int32), size=m)
    keys = rng.integers(0, h, size=m).astype(np.int32)
    if case == "invalid-entries":  # the invalids' key is h itself
        keys[rng.random(m) < 0.4] = h
    return keys


CASES = [
    # (name, hosts, entries)
    ("seeded", 256, 4096),
    ("invalid-entries", 256, 4096),
    ("hosts-not-a-multiple-of-128", 200, 3000),
    ("hosts-below-one-block", 5, 64),
    ("hosts-above-2**15", 40_000, 2048),
    ("empty-batch", 384, 0),
    ("one-destination", 300, 1000),
    ("both-ends", 1000, 512),
]


@pytest.mark.parametrize("case,h,m", CASES, ids=[c[0] for c in CASES])
def test_counts_and_begins_equal_bincount(case, h, m):
    keys = _keys(case, h, m, seed=h + m)
    cnt, begin = jax.jit(run_bounds, static_argnums=1)(jnp.asarray(keys, jnp.int32), h)
    assert cnt.shape == begin.shape == (h,)
    assert cnt.dtype == begin.dtype == jnp.int32
    want = np.bincount(keys[keys < h], minlength=h)
    np.testing.assert_array_equal(np.asarray(cnt), want)
    np.testing.assert_array_equal(np.asarray(begin), np.cumsum(want) - want)
    # begin[x] is the number of sorted keys below x: where x's run starts
    np.testing.assert_array_equal(
        np.asarray(begin), np.searchsorted(np.sort(keys), np.arange(h), side="left"))


def _queue(h: int, cap: int, filled: np.ndarray) -> EventQueue:
    """Row x holds filled[x] live events in its first slots."""
    live = np.arange(cap)[None, :] < filled[:, None]
    q = equeue.create(h, cap)
    return q.replace(
        time=jnp.where(jnp.asarray(live), jnp.int64(5), q.time),
        count=jnp.asarray(filled, jnp.int32),
    )


@pytest.mark.parametrize("deliver_lanes", (8, 3))
def test_push_many_sorted_lands_by_those_counts(deliver_lanes):
    """Full rows, rows that overflow their room, a destination beyond D
    arrivals, invalid and out-of-range entries: counts and both overflow
    ledgers as a numpy model of the contract gives them."""
    h, cap, m = 200, 8, 1500
    rng = np.random.default_rng(32)
    filled = rng.integers(0, cap + 1, size=h)
    filled[:4] = cap  # full rows
    filled[7] = cap - 1  # room for one of the many sent to it
    dst = rng.integers(-2, h + 2, size=m).astype(np.int32)  # some to no host of this queue
    dst[:40] = 7  # one destination far beyond D and its room
    valid = rng.random(m) < 0.8
    time = rng.integers(10, 1000, size=m).astype(np.int64)
    time[rng.random(m) < 0.02] = int(TIME_MAX)  # the free-slot marker is rejected
    q = push_many_sorted(
        _queue(h, cap, filled), jnp.asarray(dst), jnp.asarray(valid), jnp.asarray(time),
        jnp.zeros(m, jnp.int64), jnp.ones(m, jnp.int32),
        jnp.zeros((m, PAYLOAD_LANES), jnp.int32), deliver_lanes=deliver_lanes,
    )
    ok = valid & (time < int(TIME_MAX)) & (dst >= 0) & (dst < h)
    cnt = np.bincount(dst[ok], minlength=h)
    fit = np.minimum(cnt, deliver_lanes)
    land = np.minimum(fit, cap - filled)
    overflow = fit - land
    overflow[0] += valid.sum() - fit.sum()
    np.testing.assert_array_equal(np.asarray(q.count), filled + land)
    np.testing.assert_array_equal(np.asarray(q.overflow), overflow)
    assert (np.asarray(q.time) != int(TIME_MAX)).sum(axis=1).tolist() == (filled + land).tolist()
    assert overflow[7] > 0 and overflow[0] > 0 and land.sum() > 0


@pytest.mark.parametrize("h,m", [(256, 4096), (1000, 16_000), (40_000, 2048)])
def test_the_landing_holds_the_one_product(h, m):
    """One dot_general in the lowered landing, over [M, ceil(H/128)] and
    [M, 128] one-hots, whatever the shapes: no second spelling, no choice."""
    q = jax.eval_shape(lambda: equeue.create(h, 8))

    def land(q, dst, valid, time):
        return push_many_sorted(
            q, dst, valid, time, jnp.zeros(m, jnp.int64), jnp.ones(m, jnp.int32),
            jnp.zeros((m, PAYLOAD_LANES), jnp.int32))

    text = jax.jit(land).lower(
        q, jax.ShapeDtypeStruct((m,), jnp.int32), jax.ShapeDtypeStruct((m,), jnp.bool_),
        jax.ShapeDtypeStruct((m,), jnp.int64)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 1, dots
    blocks = -(-h // 128)
    assert re.search(rf"tensor<{blocks}x{m}xi8>, tensor<{m}x128xi8>", dots[0]), dots[0]
    assert f"tensor<{blocks}x128xi32>" in dots[0]


def test_push_many_sorted_counts_through_run_bounds(monkeypatch):
    """The landing's counts are run_bounds' and no copy: with the function
    replaced, the landing follows the replacement."""
    seen = []

    def spy(key, h):
        seen.append((key.shape, h))
        return jnp.zeros(h, jnp.int32), jnp.zeros(h, jnp.int32)

    monkeypatch.setattr(equeue, "run_bounds", spy)
    h, m = 16, 40
    q = push_many_sorted(
        equeue.create(h, 4), jnp.arange(m, dtype=jnp.int32) % h, jnp.ones(m, bool),
        jnp.full(m, 9, jnp.int64), jnp.zeros(m, jnp.int64), jnp.ones(m, jnp.int32),
        jnp.zeros((m, PAYLOAD_LANES), jnp.int32))
    assert seen == [((m,), h)]
    assert int(q.count.sum()) == 0 and int(q.overflow[0]) == m  # nothing counted, nothing landed
