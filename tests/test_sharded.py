"""Multi-chip conformance: the sharded engine (hosts block-sharded over an
8-virtual-device mesh, exchange via all_gather over the mesh axis) must
produce bit-identical results to the single-device engine."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from shadow_tpu import equeue
from shadow_tpu.engine import EngineConfig, ShardedRunner, init_state
from shadow_tpu.engine.round import bootstrap, run_until
from shadow_tpu.engine.sharded import AXIS
from shadow_tpu.graph import NetworkGraph, compute_routing, routing
from shadow_tpu.models import PholdModel
from shadow_tpu.simtime import NS_PER_MS


def _setup(num_hosts, n_nodes=4, loss=0.1, seed=31, grouped=False):
    rng_py = random.Random(seed)
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "700 us" ]')
    for i in range(n_nodes):
        for j in range(i + 1, n_nodes):
            lines.append(
                f'  edge [ source {i} target {j} latency "{rng_py.randrange(2, 9)} ms" packet_loss {loss} ]'
            )
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    # grouped: blocks of hosts a node, as host groups give them
    host_node = [
        i * n_nodes // num_hosts if grouped else i % n_nodes for i in range(num_hosts)
    ]
    tables = compute_routing(graph, block=8).with_hosts(host_node)
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=32,
        outbox_capacity=8,
        runahead_ns=graph.min_latency_ns(),
        seed=seed,
    )
    model = PholdModel(num_hosts=num_hosts, min_delay_ns=1 * NS_PER_MS, max_delay_ns=6 * NS_PER_MS)
    st = bootstrap(init_state(cfg, model.init()), model, cfg)
    return cfg, model, tables, st


def _assert_sharded_equals_single(st_single, st_sharded, cfg):
    for name in ["seq", "rng_counter", "packets_sent", "packets_dropped", "events_handled"]:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_single, name)), np.asarray(getattr(st_sharded, name)), err_msg=name
        )
    np.testing.assert_array_equal(
        np.asarray(st_single.model.recv_count), np.asarray(st_sharded.model.recv_count)
    )
    np.testing.assert_array_equal(
        np.asarray(st_single.model.send_count), np.asarray(st_sharded.model.send_count)
    )
    # queue contents identical per host (canonical order)
    for h in range(cfg.num_hosts):
        assert equeue.debug_sorted_events(st_sharded.queue, h) == equeue.debug_sorted_events(
            st_single.queue, h
        ), f"host {h}"


def test_sharded_matches_single_device():
    assert jax.device_count() == 8
    cfg, model, tables, st0 = _setup(num_hosts=16)
    end = 50 * NS_PER_MS

    st_single = run_until(st0, end, model, tables, cfg, rounds_per_chunk=16)

    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=16)
    st_sharded = runner.run_until(st0, end)

    _assert_sharded_equals_single(st_single, st_sharded, cfg)
    assert int(st_sharded.queue.overflow.sum()) == 0
    assert int(st_sharded.outbox.overflow.sum()) == 0


@pytest.mark.parametrize("path", ["runs", "gather"])
def test_two_shards_match_one_on_either_lookup_path(path, monkeypatch):
    """The routing lookup's tables are global and replicated: four runs of
    four hosts, two on each chip, read by the runs' bounds; and the striped
    map of 16 runs read by the gather (the limit lowered under it)."""
    if path == "gather":
        monkeypatch.setattr(routing, "ROUTE_RUNS_MAX", 8)
    cfg, model, tables, st0 = _setup(num_hosts=16, grouped=path == "runs")
    assert (tables.route_path, tables.route_runs) == ((path, 4) if path == "runs" else (path, 0))
    end = 50 * NS_PER_MS
    one = run_until(st0, end, model, tables, cfg, rounds_per_chunk=16)
    mesh = Mesh(np.array(jax.devices()[:2]), (AXIS,))
    two = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=16).run_until(st0, end)
    assert int(one.packets_sent.sum()) > 30 and int(one.packets_dropped.sum()) > 0
    _assert_sharded_equals_single(one, two, cfg)


def _setup_bulk(num_hosts, seed=17, exchange="all_to_all"):
    """Bulk-TCP world (handshake/Reno/retransmits + shaping) for the
    scaled sharded-equality check (the exchange seam that matters at 10k
    hosts, reference worker.rs:619-629)."""
    from shadow_tpu.models.bulk import BulkTcpModel
    from shadow_tpu.netstack import bw_bits_per_sec_to_refill

    rng_py = random.Random(seed)
    n_nodes = 8
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "1 ms" ]')
    for i in range(n_nodes):
        for j in range(i + 1, n_nodes):
            lines.append(
                f'  edge [ source {i} target {j} latency "{rng_py.randrange(2, 7)} ms" packet_loss 0.01 ]'
            )
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    host_node = [i % n_nodes for i in range(num_hosts)]
    tables = compute_routing(graph, block=8).with_hosts(host_node)
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=128,
        outbox_capacity=32,
        runahead_ns=graph.min_latency_ns(),
        seed=seed,
        use_netstack=True,
        exchange=exchange,
    )
    model = BulkTcpModel(
        num_hosts=num_hosts, num_pairs=num_hosts // 4, total_bytes=40_000
    )
    bw = bw_bits_per_sec_to_refill(50_000_000)
    st = bootstrap(
        init_state(cfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw),
        model,
        cfg,
    )
    return cfg, model, tables, st


@pytest.mark.parametrize("exchange", ["all_to_all", "all_gather"])
def test_sharded_bulk_tcp_1k_hosts_matches_single(exchange):
    """1024-host bulk-TCP (full simulated stack) sharded over 8 devices
    with the destination-bucketed all-to-all exchange — or the all_gather
    one — must equal the single-device run bit for bit."""
    assert jax.device_count() == 8
    cfg, model, tables, st0 = _setup_bulk(num_hosts=1024, exchange=exchange)
    end = 40 * NS_PER_MS

    st_single = run_until(st0, end, model, tables, cfg, rounds_per_chunk=8)

    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=8)
    st_sharded = runner.run_until(st0, end)

    for name in ["seq", "rng_counter", "packets_sent", "packets_dropped", "events_handled"]:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_single, name)),
            np.asarray(getattr(st_sharded, name)),
            err_msg=name,
        )
    np.testing.assert_array_equal(
        np.asarray(st_single.model.tcp.delivered), np.asarray(st_sharded.model.tcp.delivered)
    )
    np.testing.assert_array_equal(
        np.asarray(st_single.model.conns_established),
        np.asarray(st_sharded.model.conns_established),
    )
    assert int(np.asarray(st_sharded.model.tcp.delivered).sum()) > 0
    assert int(st_sharded.queue.overflow.sum()) == 0
    assert int(st_sharded.outbox.overflow.sum()) == 0


def test_sharded_rejects_uneven_split():
    cfg, model, tables, st0 = _setup(num_hosts=12)  # 12 % 8 != 0
    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    with pytest.raises(ValueError):
        ShardedRunner(mesh, model, tables, cfg)


def test_runahead_validation():
    cfg, model, tables, st0 = _setup(num_hosts=16)
    bad = EngineConfig(
        num_hosts=16, runahead_ns=10**12, seed=1, queue_capacity=32, outbox_capacity=8
    )
    with pytest.raises(ValueError):
        run_until(st0, 10 * NS_PER_MS, model, tables, bad)
