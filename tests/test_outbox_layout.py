"""The outbox payload's axes (engine/state.py Outbox.data: [H, 8, O]).

Contracts pinned here:

  * `stage_packets` on the `[H, 8, O]` payload stages what the spelling on
    `[H, O, 8]` staged (kept beside this test, `_stage_hwo`): every leaf
    equal with the payload viewed back as `[H, O, 8]`, `fill` and
    `overflow` included, on random emissions over rows that fill up and
    overflow;
  * a flush of the staged outbox leaves the queue that `push_many_sorted`
    gives on the `[M, 8]` rows of a flatten spelled on the `[H, O, 8]`
    payload, entry (h, o) at o * H + h;
  * `grow_state` widens the payload on its slot axis: staging into a grown
    outbox equals staging into one built at the larger capacity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu import equeue
from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import flush_outbox, stage_packets
from shadow_tpu.engine.state import grow_state
from shadow_tpu.events import KIND_PACKET
from shadow_tpu.netstack import AUX_SIZE_MASK
from shadow_tpu.simtime import TIME_MAX

H = 24
LANES = equeue.PAYLOAD_LANES


def _stage_hwo(ob, data_hwo, kept, dst, deliver, tie, data, size):
    """The staging as it was spelled while the payload was [H, O, 8]."""
    o_cap = ob.valid.shape[1]
    lane_idx = jnp.arange(o_cap)[None, :]
    fill, overflow = ob.fill, ob.overflow
    obv, obd, obt, obtie, obaux = ob.valid, ob.dst, ob.time, ob.tie, ob.aux
    obdata = data_hwo
    for p in range(kept.shape[1]):
        has_room = fill < o_cap
        write = kept[:, p] & has_room
        at = (lane_idx == fill[:, None]) & write[:, None]
        obv = obv | at
        obd = jnp.where(at, dst[:, p][:, None], obd)
        obt = jnp.where(at, deliver[:, p][:, None], obt)
        obtie = jnp.where(at, tie[:, p][:, None], obtie)
        obdata = jnp.where(at[:, :, None], data[:, p, None, :], obdata)
        obaux = jnp.where(at, (size[:, p] & AUX_SIZE_MASK)[:, None], obaux)
        fill = fill + write.astype(jnp.int32)
        overflow = overflow + (kept[:, p] & ~has_room).astype(jnp.int32)
    ob = ob.replace(
        valid=obv, dst=obd, time=obt, tie=obtie, aux=obaux, fill=fill,
        overflow=overflow,
    )
    return ob, obdata


def _emissions(rng, ep, keep=0.7):
    """One iteration's packet lanes: ([H, EP] kept, dst, deliver, tie,
    [H, EP, 8] data, [H, EP] size); `keep` a share or one per row [H, 1]."""
    return (
        jnp.asarray(rng.random((H, ep)) < keep),
        jnp.asarray(rng.integers(0, H, (H, ep)), jnp.int32),
        jnp.asarray(rng.integers(1_000, 9_000_000, (H, ep)), jnp.int64),
        jnp.asarray(rng.integers(1, 1 << 60, (H, ep)), jnp.int64),
        jnp.asarray(rng.integers(-(1 << 31), 1 << 31, (H, ep, LANES)), jnp.int32),
        jnp.asarray(rng.integers(40, 1 << 20, (H, ep)), jnp.int32),
    )


def _state(o_cap, queue=64):
    cfg = EngineConfig(
        num_hosts=H, queue_capacity=queue, outbox_capacity=o_cap, runahead_ns=1_000_000
    )
    return cfg, init_state(cfg, model_state=())


def _hwo(ob):
    return jnp.moveaxis(ob.data, 1, 2)


@pytest.mark.parametrize("ep", [1, 5])
@pytest.mark.parametrize("o_cap", [16, 64, 256])
def test_staging_and_flush_equal_the_slot_major_spelling(o_cap, ep):
    rng = np.random.default_rng(o_cap * 10 + ep)
    cfg, st = _state(o_cap, queue=4 * o_cap)  # room for every arrival
    new = st.outbox
    assert new.data.shape == (H, LANES, o_cap)
    old, old_data = new, _hwo(new)
    # rows that emit always, never, and in between: the busy ones overflow
    row_keep = np.concatenate([[[1.0], [0.0]], rng.random((H - 2, 1))])
    stage, stage_hwo = jax.jit(stage_packets), jax.jit(_stage_hwo)
    for _ in range(-(-3 * o_cap // (2 * ep))):
        lanes = _emissions(rng, ep, row_keep)
        new = stage(new, *lanes)
        old, old_data = stage_hwo(old, old_data, *lanes)

    over = np.asarray(new.overflow)
    assert over.max() > 0 and (over == 0).any() and np.asarray(new.fill).min() < o_cap
    np.testing.assert_array_equal(np.asarray(_hwo(new)), np.asarray(old_data))
    for name in ("valid", "dst", "time", "tie", "aux", "fill", "overflow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(new, name)), np.asarray(getattr(old, name)), err_msg=name
        )

    # the flush: the landing sees entry (h, o) of every array at o * H + h
    # (slots major, hosts minor: engine/round.py _flush_outbox_traffic)
    m = H * o_cap
    want = equeue.push_many_sorted(
        st.queue,
        dst=old.dst.T.reshape(m),
        valid=old.valid.T.reshape(m),
        time=old.time.T.reshape(m),
        tie=old.tie.T.reshape(m),
        kind=jnp.full((m,), KIND_PACKET, jnp.int32),
        data=jnp.moveaxis(old_data, 1, 0).reshape(m, LANES),
        aux=old.aux.T.reshape(m),
        deliver_lanes=st.queue.capacity,
    )
    got = flush_outbox(st.replace(outbox=new), None, cfg)
    assert not np.asarray(got.outbox.valid).any()
    assert (np.asarray(got.outbox.time) == TIME_MAX).all()
    for a, b in zip(jax.tree.leaves(got.queue), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ep", [1, 5])
def test_grown_outbox_equals_one_built_at_the_larger_capacity(ep):
    rng = np.random.default_rng(ep)
    _, small = _state(16)
    _, large = _state(64)
    first, second = _emissions(rng, ep), _emissions(rng, ep)
    ob = stage_packets(small.outbox, *first)  # <= 5 of 16 slots: no overflow
    grown = grow_state(small.replace(outbox=ob), outbox_capacity=64).outbox
    assert grown.data.shape == (H, LANES, 64)
    grown = stage_packets(grown, *second)
    want = stage_packets(stage_packets(large.outbox, *first), *second)
    for a, b in zip(jax.tree.leaves(grown), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
