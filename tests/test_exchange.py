"""The round-boundary exchange (engine/round.py flush_outbox): all_to_all
or all_gather across shards, landed by pull (equeue.land_sorted).

Contracts pinned here:

  * the two exchange modes and the single-device run are trajectory- and
    stat-leaf-exact; queue grids compare as live content in canonical
    (time, tie) pop order — slot PLACEMENT follows arrival order, which
    the modes lay out differently, and pop order is key-driven either way;
  * a bursty fan-in lands up to the destination row's free room, the
    earliest keys first; what does not fit is counted on that row and
    check_capacity names it — never a silent drop;
  * the choices this engine once had and retired (engine "megakernel",
    exchange "segment" / "dense", pool_capacity, megakernel_tile) are
    refused with a message naming the values left.
"""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from test_mesh import _assert_mesh_slice_exact
from test_overlay import _world as _overlay_world

from shadow_tpu import equeue
from shadow_tpu.config import load_config_str
from shadow_tpu.engine import EngineConfig, ShardedRunner, init_state
from shadow_tpu.engine.round import (
    CapacityError,
    bootstrap,
    capacity_topk,
    check_capacity,
    flush_outbox,
    run_until,
)
from shadow_tpu.engine.sharded import AXIS
from shadow_tpu.models.phold import PholdModel
from shadow_tpu.simtime import NS_PER_MS

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("engine,k", [("plain", 0), ("pump", 3)], ids=["plain", "pump"])
def test_all_to_all_matches_all_gather_and_single_smoke(engine, k):
    """Quick-tier acceptance pin: 12 phold hosts block-sharded over 4
    virtual devices, under either exchange mode, equal the one-device run
    in every leaf (queues in pop order)."""
    assert jax.device_count() >= 4
    model = PholdModel(
        num_hosts=12, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    cfg, tables = _overlay_world(model, seed=5)
    cfg = dataclasses.replace(cfg, engine=engine, pump_k=k)
    end = 40 * NS_PER_MS
    st0 = bootstrap(init_state(cfg, model.init()), model, cfg)
    single = run_until(st0, end, model, tables, cfg, rounds_per_chunk=8)
    check_capacity(single)
    assert int(single.events_handled.sum()) > 0
    assert int(single.packets_sent.sum()) > 0  # the exchange carried traffic

    mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
    for mode in ("all_to_all", "all_gather"):
        c = dataclasses.replace(cfg, exchange=mode)
        sharded = ShardedRunner(mesh, model, tables, c, rounds_per_chunk=8).run_until(
            st0, end
        )
        _assert_mesh_slice_exact(sharded, single, f" ({engine} {mode} vs one device)")


def _bursty_state(cfg, model):
    """A deliberately bursty flush: every host stages 2 packets, ALL to
    host 0 — 16 deliveries into one row, keys rising in staging order."""
    st = init_state(cfg, model.init())  # NO bootstrap: queue stays empty
    h, o = st.outbox.valid.shape
    valid = np.zeros((h, o), bool)
    valid[:, :2] = True
    time = np.full((h, o), (1 << 62) - 1, np.int64)
    tie = np.zeros((h, o), np.int64)
    for i in range(h):
        for j in range(2):
            time[i, j] = 10 * NS_PER_MS + i * 2 + j
            tie[i, j] = i * 2 + j + 1
    ob = st.outbox.replace(
        valid=jnp.asarray(valid),
        dst=jnp.zeros((h, o), jnp.int32),
        time=jnp.asarray(time),
        tie=jnp.asarray(tie),
        aux=jnp.where(jnp.asarray(valid), jnp.int32(100), jnp.int32(0)),
        fill=jnp.full((h,), 2, jnp.int32),
    )
    return st.replace(outbox=ob)


def test_bursty_fanin_lands_to_queue_room_then_overflows_loudly():
    """A 16-arrival burst on one row lands in full where the row has 16
    free slots; with 15 the 15 earliest keys land, the one more is counted
    on the destination's own row, and check_capacity names the queue and
    the host."""
    model = PholdModel(num_hosts=8)
    keys = [(10 * NS_PER_MS + n, n + 1) for n in range(16)]

    def landed(queue_capacity):
        cfg, _tables = _overlay_world(
            model, seed=3, queue_capacity=queue_capacity, outbox_capacity=4
        )
        st = flush_outbox(_bursty_state(cfg, model), None, cfg)
        assert not bool(st.outbox.valid.any())  # the flush clears the outbox
        events = equeue.debug_sorted_events(st.queue, 0)
        return st, [(t, tie) for t, tie, _kind, _data in events]

    st, got = landed(16)
    check_capacity(st)  # exactly the row's room: nothing dropped
    assert got == keys
    assert int(st.queue.count[0]) == 16 and int(st.queue.count[1:].sum()) == 0

    st, got = landed(15)
    assert got == keys[:15]
    assert np.asarray(st.queue.overflow).tolist() == [1] + [0] * 7
    assert int(st.outbox.overflow.sum()) == 0
    with pytest.raises(CapacityError) as ei:
        check_capacity(st)
    assert "saturated: queue " in str(ei.value)
    assert ei.value.queue_overflow == 1 and ei.value.outbox_overflow == 0
    topk = capacity_topk(st)
    assert topk.startswith("top destination hosts by landed events")
    assert "host 0" in topk


def _yaml_engine(name):
    text = (REPO / "examples/phold/shadow.yaml").read_text()
    assert "  scheduler: tpu\n" in text
    return load_config_str(
        text.replace("  scheduler: tpu\n", f"  scheduler: tpu\n  engine: {name}\n")
    )


@pytest.mark.parametrize(
    "build,left",
    [
        (lambda: _yaml_engine("megakernel"), ("auto", "plain", "pump")),
        (lambda: EngineConfig(num_hosts=4, engine="megakernel"), ("auto", "plain", "pump")),
        (lambda: EngineConfig(num_hosts=4, exchange="segment"), ("all_to_all", "all_gather")),
        (lambda: EngineConfig(num_hosts=4, exchange="dense"), ("all_to_all", "all_gather")),
    ],
    ids=["yaml-engine-megakernel", "cfg-engine-megakernel", "cfg-exchange-segment",
         "cfg-exchange-dense"],
)
def test_retired_choices_are_refused(build, left):
    """The front door and EngineConfig refuse each retired value as they do
    any unknown name, and the message names the values left; the retired
    knobs are no longer fields."""
    with pytest.raises(ValueError) as ei:
        build()
    for name in left:
        assert repr(name) in str(ei.value)
    assert _yaml_engine("pump").experimental.engine == "pump"
    for retired in ({"pool_capacity": 1}, {"megakernel_tile": 8}):
        with pytest.raises(TypeError):
            EngineConfig(num_hosts=4, **retired)
