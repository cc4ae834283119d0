"""Adaptive conservative windows (engine/round.py _next_window_end):
window_end = min over hosts of (next event time + per-node lookahead) —
the LBTS bound — must be LEAF-IDENTICAL to fixed-width rounds: the
delivery clamp max(t + lat, window_end) provably never binds under the
bound, so widening the window regroups rounds without moving a single
event, draw, or byte. Pinned here on phold + tgen across
plain/pump, sharded, ensemble slices, and through a
checkpoint roundtrip; plus the perf pin — a sparse-in-time scenario
drains in provably fewer iterations/rounds.

What may legitimately differ between window policies (and is therefore
canonicalized/excluded): queue/outbox slot PLACEMENT and dead-slot
tombstones (flush batching differs; pops are key-driven so placement is
semantically void — same normalization as tests/test_pump.py), and the
round-structure diagnostics iters_done / lanes_live / win_ns_sum /
tracker round counters / occupancy high-water marks (fewer, wider
rounds is the point)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_pump import _normalize
from test_pump import _world as _tgen_world

from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import (
    ChunkProbe,
    bootstrap,
    run_until,
    state_probe,
)
from shadow_tpu.engine.state import state_from_host, state_to_host
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.models.phold import PholdModel
from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC

# host nodes (0, 1) talk over 20 ms links; nodes 2-3 carry the graph's
# 1 ms minimum-latency edge but host no traffic — so the fixed
# conservative width is 1 ms while every host's true lookahead is 20 ms
HETERO_GML = "\n".join(
    [
        "graph [",
        "  directed 0",
        *[f"  node [ id {i} ]" for i in range(4)],
        '  edge [ source 0 target 0 latency "20 ms" ]',
        '  edge [ source 1 target 1 latency "20 ms" ]',
        '  edge [ source 0 target 1 latency "20 ms" ]',
        '  edge [ source 2 target 3 latency "1 ms" ]',
        '  edge [ source 2 target 2 latency "1 ms" ]',
        '  edge [ source 3 target 3 latency "1 ms" ]',
        "]",
    ]
)


def _hetero_world(num_hosts, max_delay_ms=50):
    graph = NetworkGraph.from_gml(HETERO_GML)
    tables = compute_routing(graph).with_hosts(
        [i % 2 for i in range(num_hosts)]
    )
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=32,
        runahead_ns=graph.min_latency_ns(),
        seed=9,
        # tracker on: populates the probe's rounds_live (the mean-width
        # denominator) and widens the leaf-equivalence pins to the
        # tracker plane
        tracker=True,
    )
    model = PholdModel(
        num_hosts=num_hosts,
        min_delay_ns=1 * NS_PER_MS,
        max_delay_ns=max_delay_ms * NS_PER_MS,
    )
    st = bootstrap(init_state(cfg, model.init()), model, cfg)
    return cfg, model, tables, st


def _canon(st):
    """test_pump's queue normalization + zero every round-structure
    diagnostic a different window policy legitimately changes, and mask
    outbox tombstones (the outbox is empty after the final flush, but
    dead slots keep batching-dependent garbage)."""
    st = _normalize(st)
    ob = st.outbox
    v = np.asarray(ob.valid)
    assert not v.any(), "outbox should be flushed at run end"
    ob = ob.replace(
        dst=jnp.zeros_like(ob.dst),
        time=jnp.full_like(ob.time, 0),
        tie=jnp.zeros_like(ob.tie),
        data=jnp.zeros_like(ob.data),
        aux=jnp.zeros_like(ob.aux),
    )
    return st.replace(
        outbox=ob,
        win_ns_sum=st.win_ns_sum * 0,
        rounds_live=st.rounds_live * 0,
        tracker=st.tracker.replace(
            rounds_idle=st.tracker.rounds_idle * 0,
            queue_hwm=st.tracker.queue_hwm * 0,
            outbox_hwm=st.tracker.outbox_hwm * 0,
            exch_hwm=st.tracker.exch_hwm * 0,
            land_hwm=st.tracker.land_hwm * 0,
            land_passes=st.tracker.land_passes * 0,
            flush_cols=st.tracker.flush_cols * 0,
        ),
    )


def _assert_canon_equal(a, b):
    fa = jax.tree_util.tree_leaves_with_path(_canon(a))
    fb = jax.tree.leaves(_canon(b))
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        assert jnp.array_equal(la, lb), (
            f"mismatch at {jax.tree_util.keystr(path)}"
        )


def _probe(st) -> ChunkProbe:
    return ChunkProbe.from_array(np.asarray(jax.jit(state_probe)(st)))


def test_adaptive_leaf_identical_and_fewer_iters_phold():
    """The tentpole pin, one pair of runs: on the sparse-in-time phold
    world the adaptive engine must (a) produce leaf-identical simulation
    state and (b) drain in >= 2x fewer pop-iterations (the published
    acceptance bar; the win here is ~3.7x)."""
    cfg, model, tables, st0 = _hetero_world(32)
    end = int(0.6 * NS_PER_SEC)
    adaptive = run_until(st0, end, model, tables, cfg, rounds_per_chunk=8)
    fixed = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg, adaptive_window=False),
        rounds_per_chunk=8,
    )
    pa, pf = _probe(adaptive), _probe(fixed)
    assert pa.events_handled == pf.events_handled > 0
    assert pa.iters * 2 <= pf.iters, (pa.iters, pf.iters)
    # windows actually widened: the mean LIVE window is a multiple of the
    # fixed 1 ms conservative width (it tracks the hosts' 20 ms lookahead)
    assert pf.window_ns_mean > 0
    assert pa.window_ns_mean > 2 * pf.window_ns_mean, (
        pa.window_ns_mean, pf.window_ns_mean
    )
    _assert_canon_equal(adaptive, fixed)


def test_adaptive_gated_off_under_dynamic_runahead():
    """Under use_dynamic_runahead the round-end delivery clamp MOVES
    delivery times (that IS the approximation), so window width is
    semantics-bearing there and _next_window_end must ignore
    adaptive_window — the combination would silently change
    trajectories for pre-existing dynamic-runahead configs."""
    from shadow_tpu.engine.round import _next_window_end

    cfg, model, tables, st0 = _hetero_world(8)
    end = int(NS_PER_SEC)
    fixed = _next_window_end(
        st0, end, dataclasses.replace(cfg, adaptive_window=False), None,
        tables=tables,
    )
    dyn = _next_window_end(
        st0, end, dataclasses.replace(cfg, use_dynamic_runahead=True), None,
        tables=tables,
    )
    widened = _next_window_end(st0, end, cfg, None, tables=tables)
    # the gate holds the dynamic window at the fixed floor…
    assert int(dyn) == int(fixed)
    # …which adaptive would otherwise have widened on this topology
    assert int(widened) > int(fixed)


class _ChunkTap:
    """Minimal on_state tap (the StateTap interface _drive consumes):
    commit the first verified chunk-boundary snapshot, then stand down."""

    def __init__(self):
        self.snaps = []

    def due(self, probe, chunk):
        return not self.snaps

    def commit(self, host_state):
        self.snaps.append(host_state)

    def interrupted(self):
        return False


def test_adaptive_checkpoint_roundtrip_leaf_exact():
    """Adaptive runs resume bit-exact: snapshot at a chunk boundary of
    the straight run (the checkpoint machinery's seam — _drive's
    on_state tap, through the state_to_host/state_from_host wire format),
    resume from the snapshot to the same end, and match the
    uninterrupted run on EVERY leaf — diagnostics included. The snapshot
    must come from a chunk boundary, not a separate run to `mid`: an
    end-clamped window at `mid` would legitimately regroup rounds."""
    cfg, model, tables, st0 = _hetero_world(16)
    end = int(0.4 * NS_PER_SEC)
    tap = _ChunkTap()
    straight = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=8, on_state=tap
    )
    assert tap.snaps, "run ended before a chunk-boundary snapshot landed"
    restored = state_from_host(tap.snaps[0], st0)
    assert int(np.asarray(restored.now)) < end, "snapshot was not mid-run"
    resumed = run_until(restored, end, model, tables, cfg, rounds_per_chunk=8)
    fa = jax.tree_util.tree_leaves_with_path(straight)
    fb = jax.tree.leaves(resumed)
    for (path, la), lb in zip(fa, fb):
        if jnp.issubdtype(getattr(la, "dtype", None), jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        assert jnp.array_equal(la, lb), (
            f"mismatch at {jax.tree_util.keystr(path)}"
        )


@pytest.mark.parametrize("engine,pump_k", [("plain", 0), ("pump", 4)])
def test_adaptive_matches_fixed_tgen_engines(engine, pump_k):
    """tgen (TCP + shaping + loss) under every engine: adaptive must
    equal the fixed-width PLAIN reference after canonicalization — one
    assertion covering both the window policy and the engine."""
    cfg0, model, tables, st0 = _tgen_world(8, 0.02, 20_000_000, seed=3)
    end = 40 * NS_PER_MS
    ref = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg0, adaptive_window=False),
        rounds_per_chunk=8,
    )
    got = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg0, engine=engine, pump_k=pump_k),
        rounds_per_chunk=8,
    )
    assert int(np.asarray(got.events_handled).sum()) > 0
    _assert_canon_equal(ref, got)


def test_adaptive_matches_fixed_sharded():
    """The window agreement stays mesh-uniform: an 8-shard adaptive run
    equals the single-device fixed-width run canonically."""
    from jax.sharding import Mesh

    from shadow_tpu.engine.sharded import AXIS, ShardedRunner

    assert jax.device_count() == 8
    cfg, model, tables, st0 = _hetero_world(16, max_delay_ms=20)
    end = int(0.15 * NS_PER_SEC)
    fixed_single = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg, adaptive_window=False),
        rounds_per_chunk=8,
    )
    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=8)
    adaptive_sharded = runner.run_until(st0, end)
    _assert_canon_equal(fixed_single, adaptive_sharded)


def test_adaptive_matches_fixed_ensemble_slices():
    """Every replica of an adaptive ensemble equals its fixed-width
    counterpart canonically (the per-replica window min under vmap)."""
    from shadow_tpu.engine.ensemble import (
        init_ensemble_state,
        replica_slice,
        run_ensemble_until,
    )

    cfg, model, tables, _ = _hetero_world(8, max_delay_ms=20)
    end = int(0.15 * NS_PER_SEC)
    ens0 = init_ensemble_state(cfg, model, 2)
    adaptive = run_ensemble_until(
        ens0, end, model, tables, cfg, rounds_per_chunk=8
    )
    fixed = run_ensemble_until(
        ens0, end, model, tables,
        dataclasses.replace(cfg, adaptive_window=False),
        rounds_per_chunk=8,
    )
    for r in range(2):
        _assert_canon_equal(
            replica_slice(adaptive, r), replica_slice(fixed, r)
        )
