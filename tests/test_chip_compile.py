"""Ask the chip's compiler, without the chip (PR 22).

Pieces of the front-door run path are lowered and compiled for a
*described* TPU v5e at the tgen-10k world's 10,240-host shapes — what the
compiler refuses here, it refuses on the chip, at no chip time. A compile
that passes is a compile, never a run. The whole chunk programs, the full
event handler and the delivery-grid flush take minutes each and live in
tools/compile_for_chip.py; this file stays under two minutes.

The topology is described inside a module-scoped fixture (never at import,
never in conftest.py), so only the xdist worker that is handed this file
loads the TPU's library. Left to itself that library takes the fixed lock
/tmp/libtpu_lockfile for the life of the process and logs under
/tmp/tpu_logs — two tier-1 runs on one machine would meet there. The
fixture therefore loads it compile-only: no lock taken or asked for, no
log written, nothing outside the checkout.
"""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from shadow_tpu import equeue, netstack
from shadow_tpu.engine import round as rnd
from shadow_tpu.intmath import divmod_nonneg

HOSTS, QUEUE, OUTBOX = 10_240, 384, 64


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2. A missing libtpu skips; any other failure to
    describe the topology (a lock collision included) is an error, so the
    seven cases below never vanish silently."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        # read by libtpu at its first load in this process, which is here
        env.setenv("TPU_LOG_DIR", "disabled")
        env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def world():
    """The tgen-10k world as the front door builds it (shapes only are
    used below)."""
    import os

    from shadow_tpu.config import load_config_file
    from shadow_tpu.engine.state import init_state
    from shadow_tpu.runtime.manager import Manager

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    w = Manager(
        load_config_file(os.path.join(root, "examples", "tgen-10k", "shadow.yaml"))
    ).build_world()
    assert (w.ecfg.num_hosts, w.ecfg.queue_capacity, w.ecfg.outbox_capacity) == (
        HOSTS, QUEUE, OUTBOX,
    )
    state = jax.eval_shape(
        lambda: rnd.bootstrap(
            init_state(w.ecfg, w.model.init(), tx_bytes_per_interval=w.tx_refill,
                       rx_bytes_per_interval=w.rx_refill),
            w.model, w.ecfg,
        )
    )
    return w, state


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    print(f"compiled for the described chip in {time.perf_counter() - t0:.1f}s")
    return compiled


def test_pump_microstep_compiles(one_chip):
    """The fast path beside the plain handler: one pump microstep on a
    PumpCarry, as tools/compile_for_chip.py's piece of that name builds it,
    at a small shape (32 hosts of the tgen-10k world, queue 16, outbox 8):
    the chip's compiler takes every operation the pump is made of."""
    import os
    import sys

    from shadow_tpu.engine import pump

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    from compile_for_chip import build_world, state_shapes

    _, w = build_world(os.path.join(root, "examples", "tgen-10k", "shadow.yaml"), 32)
    cfg = dataclasses.replace(
        w.ecfg, engine="pump", pump_k=1, queue_capacity=16, outbox_capacity=8
    )
    assert rnd.model_pump_capable(w.model)
    carry = jax.eval_shape(
        lambda s, tb: pump.pump_carry_init(s, w.model, tb, cfg),
        state_shapes(w, cfg), w.tables,
    )
    t64 = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    _compile(
        lambda c, we, tb: pump.pump_microstep(c, we, w.model, tb, cfg),
        _on(carry, one_chip), t64, _on(w.tables, one_chip),
    )


def test_next_window_end_compiles(world, one_chip):
    w, state = world
    t64 = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    _compile(
        lambda s, e, tb: rnd._next_window_end(s, e, w.ecfg, None, tables=tb),
        _on(state, one_chip), t64, _on(w.tables, one_chip),
    )


def test_event_queue_pop_and_push_compile(world, one_chip):
    """The per-iteration queue ops at [10240, 384]: pop-min, and the
    multi-lane self push the handler ends with."""
    _, state = world
    q = _on(state.queue, one_chip)

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lanes = 5
    _compile(lambda q, want: equeue.pop_min(q, want), q, sd((HOSTS,), bool))
    _compile(
        equeue.push_self_lanes, q, sd((HOSTS, lanes), bool),
        sd((HOSTS, lanes), jnp.int64), sd((HOSTS, lanes), jnp.int64),
        sd((HOSTS, lanes), jnp.int32),
        sd((HOSTS, lanes, equeue.PAYLOAD_LANES), jnp.int32),
        sd((HOSTS, lanes), jnp.int32),
    )


def test_netstack_step_compiles(world, one_chip):
    """Token bucket + CoDel for every host: the int64 division by the
    per-host refill goes through intmath.divmod_nonneg — spelled `//` it
    costs the chip's compiler tens of seconds — and the `//` by the
    constant interval is reduced by XLA itself, so no integer divide may
    reach the compiled program."""
    _, state = world
    net = _on(state.net, one_chip)

    def sd(dt):
        return jax.ShapeDtypeStruct((HOSTS,), dt, sharding=one_chip)

    def step(net, now, size, need):
        ready, tok, last = netstack.tb_depart(
            net.rx_tokens, net.rx_last, net.rx_refill, now, size, need
        )
        drop, net = netstack.codel_dequeue(net, ready, ready - now, need)
        return ready, tok, last, drop, net

    compiled = _compile(step, net, sd(jnp.int64), sd(jnp.int64), sd(bool))
    int_div = re.findall(r"= [su](?:32|64)\[[^\n]*? (?:divide|remainder)\(", compiled.as_text())
    assert not int_div, int_div[:3]


def test_exact_division_matches_numpy_and_compiles(one_chip):
    rng = np.random.default_rng(22)
    x = np.concatenate([rng.integers(0, 2**62, 4000), rng.integers(0, 2**31, 4000),
                        [0, 1, 2**62 - 1, 2**63 - 1]])
    d = np.concatenate([rng.integers(1, 2**62, 2000), rng.integers(1, 2**20, 6000),
                        [1, 1, 1, 2**62]])
    q, r = jax.jit(divmod_nonneg)(x, d)
    np.testing.assert_array_equal(np.asarray(q), x // d)
    np.testing.assert_array_equal(np.asarray(r), x % d)
    sd = jax.ShapeDtypeStruct((HOSTS, 5), jnp.int64, sharding=one_chip)
    _compile(divmod_nonneg, sd, sd)


@pytest.mark.parametrize(
    "queue, deliver_lanes", [(16, 4), (QUEUE, QUEUE)], ids=["lanes4", "front_door"]
)
def test_delivery_grid_landing_compiles(world, one_chip, queue, deliver_lanes):
    """equeue.push_many_sorted — the front door's exchange landing — with
    a whole 10,240-host outbox (655,360 entries) in flight: onto a
    narrowed queue at deliver_lanes=4, and as the front door runs it
    (deliver_lanes 0 = the queue's capacity, 384). The landing pulls by
    arrival lane in a loop, so neither width sizes anything: the index
    sort, the one-hot product that counts the runs, and a while whose
    pass gathers [H, LAND_LANES] indices twice are what is asked here."""
    m = HOSTS * OUTBOX
    q = _on(jax.eval_shape(lambda: equeue.create(HOSTS, queue)), one_chip)

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = _compile(
        lambda q, *a: equeue.push_many_sorted(q, *a, deliver_lanes=deliver_lanes),
        q, sd((m,), jnp.int32), sd((m,), bool), sd((m,), jnp.int64),
        sd((m,), jnp.int64), sd((m,), jnp.int32),
        sd((m, equeue.PAYLOAD_LANES), jnp.int32), sd((m,), jnp.int32),
    )
    text = compiled.as_text()
    # the payload must not ride the sort: (destination, position) only,
    # and nothing else is sorted (searchsorted's method="sort" would be)
    sorts = [ln.split(" sort(")[0] for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 1 and sorts[0].count(f"[{m}]") == 2 and "s64[" not in sorts[0], sorts
    # no delivery grid: nothing of [H * D] rows is scattered
    assert " scatter(" not in text
    # the pull is a loop over arrival lanes: every gather issues H x LAND_LANES
    # indices (the permutation's word, then the 14 payload words), none the
    # queue's H x Q slots or the outbox's M entries, and no [14, H, Q] pull
    # result or [14, M] copy in sorted order is made
    assert " while(" in text
    made = [ln.split(" = ")[1].split("{")[0] for ln in text.splitlines() if " = " in ln]
    gathers = sorted(
        ln.split(" = ")[1].split("{")[0] for ln in text.splitlines() if " gather(" in ln
    )
    lanes = equeue.LAND_LANES
    assert gathers == sorted([f"s32[{lanes},{HOSTS}]", f"s32[14,{lanes},{HOSTS}]"]), gathers
    flat = (f"[14,{HOSTS},{queue}]", f"[{HOSTS * queue},14]", f"[14,{HOSTS * queue}]", f"[{m},14]")
    assert not [b for b in made if b.endswith(flat)]


def test_sharded_window_and_exchange_collective_compile(topo):
    """On the described 2x2 mesh, hosts block-sharded four ways: the
    window agreement (round._pmin) and the all_to_all of a
    destination-bucketed outbox column — the collectives of
    engine/sharded.py's chunk. The chip's compiler lowers no 64-bit
    all-reduce but Sum, which is why the engine's int64 pmin/pmax are
    spelled as gather + local reduce."""
    from shadow_tpu.engine.sharded import AXIS

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    assert mesh.size == 4
    local = HOSTS // 4 * OUTBOX
    sharded = NamedSharding(mesh, P(AXIS))
    args = (
        jax.ShapeDtypeStruct((HOSTS,), jnp.int64, sharding=sharded),
        jax.ShapeDtypeStruct((HOSTS * OUTBOX,), jnp.int64, sharding=sharded),
    )

    def on_mesh(pmin):
        def exchange(head_time, col):
            start = pmin(jnp.min(head_time), AXIS)
            got = jax.lax.all_to_all(col.reshape(4, local // 4), AXIS, 0, 0, tiled=False)
            return start, got.reshape(local)

        return jax.shard_map(
            exchange, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(), P(AXIS)), check_vma=False,
        )

    _compile(on_mesh(rnd._pmin), *args)
    with pytest.raises(jax.errors.JaxRuntimeError, match="only of Sum all reduce"):
        _compile(on_mesh(jax.lax.pmin), *args)
