"""The packet-pump microscan (engine/pump.py) is a pure accelerator: with
pump_k on, the engine must produce BIT-IDENTICAL state to the unpumped
engine on the flagship tgen workload — same queue contents, TCP fields,
relay/AQM state, RNG counters, sequence counters, and byte/stream
counters — including under loss and shaping (where most pops are the
defer/completion chains the pump exists to batch, and recovery events
exercise every fallback path)."""

import dataclasses
import random

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import bootstrap, check_capacity, run_until
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.models.tgen import TgenModel
from shadow_tpu.netstack import bw_bits_per_sec_to_refill
from shadow_tpu.simtime import NS_PER_MS


def _world(num_hosts, loss, bw_bits, seed=11, grouped=False):
    """`grouped`: hosts in n_nodes blocks, as host groups give them (the
    routing lookup's runs: one a node), in place of striped over the nodes
    (one run a host)."""
    rng_py = random.Random(seed)
    n_nodes = 4
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "2 ms" ]')
    for i in range(n_nodes):
        for j in range(n_nodes):
            if i < j:
                lat = rng_py.randrange(2, 9)
                lines.append(
                    f'  edge [ source {i} target {j} latency "{lat} ms" '
                    f"packet_loss {loss} ]"
                )
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    tables = compute_routing(graph).with_hosts(
        [i * n_nodes // num_hosts if grouped else i % n_nodes for i in range(num_hosts)]
    )
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=192,
        outbox_capacity=32,
        runahead_ns=graph.min_latency_ns(),
        seed=seed,
        use_netstack=True,
        deliver_lanes=48,
    )
    model = TgenModel(
        num_hosts=num_hosts,
        num_clients=num_hosts // 2,
        num_servers=num_hosts - num_hosts // 2,
        resp_bytes=40_000,
        pause_ns=30 * NS_PER_MS,
    )
    bw = bw_bits_per_sec_to_refill(bw_bits)
    st = init_state(
        cfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw
    )
    st = bootstrap(st, model, cfg)
    return cfg, model, tables, st


def _run(cfg, model, tables, st, end_ns):
    st = run_until(st, end_ns, model, tables, cfg, rounds_per_chunk=16)
    check_capacity(st)
    return st


def _normalize(st):
    """Canonicalize the queue: slot PLACEMENT is semantically irrelevant
    (pops are key-driven; pumped runs interleave pushes differently), and
    pops tombstone only the (time, tie) keys, leaving stale kind/data/aux
    behind. Rows are sorted by (time, tie) with dead-slot content zeroed,
    so only the live event *sets* must match."""
    import numpy as np

    dead = np.asarray(st.queue.time) >= (1 << 62) - 1
    time = np.asarray(st.queue.time)
    tie = np.where(dead, np.iinfo(np.int64).max, np.asarray(st.queue.tie))
    kind = np.where(dead, 0, np.asarray(st.queue.kind))
    aux = np.where(dead, 0, np.asarray(st.queue.aux))
    data = np.where(dead[:, :, None], 0, np.asarray(st.queue.data))
    order = np.lexsort((tie, time), axis=1)
    oi = np.arange(time.shape[0])[:, None]
    q = st.queue.replace(
        time=jnp.asarray(time[oi, order]),
        tie=jnp.asarray(tie[oi, order]),
        kind=jnp.asarray(kind[oi, order]),
        aux=jnp.asarray(aux[oi, order]),
        data=jnp.asarray(data[oi, order]),
    )
    # iters_done/lanes_live count engine iterations, not simulation state
    return st.replace(
        queue=q, iters_done=st.iters_done * 0, lanes_live=st.lanes_live * 0
    )


def _assert_states_equal(a, b):
    a, b = _normalize(a), _normalize(b)
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        assert jnp.array_equal(la, lb), f"mismatch at {jax.tree_util.keystr(path)}"


@pytest.mark.parametrize(
    "loss,bw,grouped",
    [(0.0, 20_000_000, False), (0.02, 20_000_000, False), (0.02, 20_000_000, True)],
)
def test_pump_bit_identical_tgen(loss, bw, grouped):
    cfg0, model, tables, st0 = _world(32, loss, bw, grouped=grouped)
    assert tables.route_runs == (4 if grouped else 32)  # both by the runs' bounds
    end = 120 * NS_PER_MS
    ref = _run(cfg0, model, tables, st0, end)
    cfgp = dataclasses.replace(cfg0, pump_k=6)
    got = _run(cfgp, model, tables, st0, end)
    assert int(ref.model.streams_done.sum()) > 0  # real traffic flowed
    # pumped iterations must be fewer (the whole point) ...
    assert int(got.iters_done.sum()) < int(ref.iters_done.sum())
    # ... with identical simulation results. iters_done is the only field
    # allowed to differ (it counts engine iterations, not simulation state).
    _assert_states_equal(ref, got)


def test_pump_unshaped_world_matches():
    """No netstack shaping: only P2/P3 apply; defers never occur."""
    cfg0, model, tables, st0 = _world(16, 0.0, 0)
    cfg0 = dataclasses.replace(cfg0, use_netstack=False)
    end = 80 * NS_PER_MS
    ref = _run(cfg0, model, tables, st0, end)
    got = _run(dataclasses.replace(cfg0, pump_k=5), model, tables, st0, end)
    assert int(ref.model.streams_done.sum()) > 0
    _assert_states_equal(ref, got)
