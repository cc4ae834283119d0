"""The async dispatch pipeline (engine/round.py run_until: depth-2 chunk
pipelining, donated chunk states, device-side termination probes) is a
pure DRIVER change: pipelined+donated runs must be leaf-exact vs the
synchronous driver (pipeline=False, same executable, probe fetched before
every launch) on phold and tgen — across the plain and pump engines —
and the donation contract must fail loudly:
a donated state's buffers raise RuntimeError on any stale reuse while the
caller's own SimState is never invalidated."""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_pump import _world as _tgen_world

from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import (
    CapacityError,
    ChunkProbe,
    _run_chunk_jit,
    bootstrap,
    run_until,
)
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.models import PholdModel
from shadow_tpu.simtime import NS_PER_MS


def _assert_leaves_exact(a, b):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        assert jnp.array_equal(la, lb), f"mismatch at {jax.tree_util.keystr(path)}"


def _phold_world(num_hosts=6, n_nodes=3, seed=11, queue_capacity=64):
    rng_py = random.Random(seed)
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "500 us" ]')
    for i in range(n_nodes):
        for j in range(i + 1, n_nodes):
            lat = rng_py.randrange(1, 9)
            lines.append(f'  edge [ source {i} target {j} latency "{lat} ms" ]')
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    tables = compute_routing(graph, block=8).with_hosts(
        [i % n_nodes for i in range(num_hosts)]
    )
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=queue_capacity,
        outbox_capacity=8,
        runahead_ns=graph.min_latency_ns(),
        seed=seed,
    )
    model = PholdModel(
        num_hosts=num_hosts, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    st = bootstrap(init_state(cfg, model.init()), model, cfg)
    return cfg, model, tables, st


def test_pipelined_matches_sync_phold():
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    sync = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=False
    )
    piped = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=True
    )
    assert int(piped.events_handled.sum()) > 0
    _assert_leaves_exact(sync, piped)
    # the caller's state is never donated: st0 is still fully usable
    again = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=True
    )
    _assert_leaves_exact(piped, again)


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["plain", "pump"])
def test_pipelined_matches_sync_tgen(engine):
    """Leaf-exact pipelined-vs-sync on the flagship tgen TCP workload for
    every round engine.
    Slow tier: each engine compiles its own chunk executable twice; the
    tier-1 pipeline coverage is the phold equivalence + smoke above."""
    cfg0, model, tables, st0 = _tgen_world(8, 0.02, 20_000_000, seed=3)
    cfg = (
        dataclasses.replace(cfg0, engine="plain")
        if engine == "plain"
        else dataclasses.replace(cfg0, engine=engine, pump_k=3)
    )
    end = 30 * NS_PER_MS
    sync = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=False
    )
    piped = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=True
    )
    assert int(piped.events_handled.sum()) > 0
    _assert_leaves_exact(sync, piped)


def test_pipeline_three_chunk_smoke():
    """Tier-1 smoke: the pipelined driver runs (at least) 3 chunks on
    CPU; on_chunk receives already-fetched ChunkProbes with monotone
    progress."""
    cfg, model, tables, st0 = _phold_world()
    probes = []
    st = run_until(
        st0,
        20 * NS_PER_MS,
        model,
        tables,
        cfg,
        rounds_per_chunk=4,
        on_chunk=probes.append,
        pipeline=True,
    )
    assert len(probes) >= 3  # short chunks: the run spans several dispatches
    assert all(isinstance(p, ChunkProbe) for p in probes)
    assert all(p.overflow == 0 for p in probes)
    nows = [p.now for p in probes]
    assert nows == sorted(nows) and nows[-1] > 0
    assert probes[-1].events_handled == int(st.events_handled.sum())


def test_donated_buffer_reuse_raises():
    """Chunk inputs are donated: stale reuse of a donated state fails
    loudly with jax's deleted-array RuntimeError, while the caller's
    original state (pre-donatable copy) stays valid."""
    cfg, model, tables, st0 = _phold_world()
    donated = st0.donatable()
    end = jnp.asarray(40 * NS_PER_MS, jnp.int64)
    out, probe = _run_chunk_jit(donated, end, 4, model, tables, cfg)
    jax.block_until_ready(probe)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(donated.seq)
    # the output and the never-donated original are both intact
    assert int(out.events_handled.sum()) >= 0
    assert np.asarray(st0.seq).shape == (cfg.num_hosts,)


def test_overflow_surfaces_at_first_chunk():
    """The probe's overflow lane is checked every chunk: a capacity
    blowup raises at the chunk it occurs, not after the run drains."""
    cfg, model, tables, st0 = _phold_world()
    bad = st0.replace(
        queue=st0.queue.replace(overflow=st0.queue.overflow.at[0].add(3))
    )
    with pytest.raises(CapacityError, match="capacity exhausted"):
        run_until(
            bad, 400 * NS_PER_MS, model, tables, cfg,
            rounds_per_chunk=4, max_chunks=10_000,
        )


def test_rerun_on_finished_state_is_stable():
    """Driving an already-finished state again (both modes) is a no-op:
    every round takes the quiescence early-exit branch."""
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    done = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    for pipeline in (False, True):
        again = run_until(
            done, end, model, tables, cfg, rounds_per_chunk=4, pipeline=pipeline
        )
        _assert_leaves_exact(done, again)
