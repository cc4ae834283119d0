"""The scalar TCP oracle vs the device engine on the *flagship* tgen
workload (the model of the benchmark's tgen-10k cells): repeated
request/response streams with port recycling, slot reuse, loss, shaping + CoDel, TIMEWAIT
turnover. Two independent implementations of the same specification must
agree bit-for-bit — every TCP state field, every model counter, every
leftover queue entry (round-2 verdict item 3; reference analogue:
src/test/determinism/CMakeLists.txt:1-40)."""

import random

import numpy as np
import pytest

from shadow_tpu import equeue
from shadow_tpu.cpu_ref.tgen_ref import CpuRefTgen
from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import bootstrap, run_until
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.models.tgen import TgenModel
from shadow_tpu.netstack import bw_bits_per_sec_to_refill
from shadow_tpu.simtime import NS_PER_MS

from tests.test_cpu_ref_bulk import TCP_FIELDS


def _world(num_hosts, loss, shaped, seed):
    rng_py = random.Random(seed)
    n_nodes = 4
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "1 ms" ]')
    for i in range(n_nodes):
        for j in range(i + 1, n_nodes):
            lines.append(
                f'  edge [ source {i} target {j} latency "{rng_py.randrange(2, 6)} ms" packet_loss {loss} ]'
            )
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    host_node = [i % n_nodes for i in range(num_hosts)]
    tables = compute_routing(graph, block=4).with_hosts(host_node)
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=128,
        outbox_capacity=16,
        runahead_ns=graph.min_latency_ns(),
        seed=seed,
        use_netstack=shaped,
    )
    model = TgenModel(
        num_hosts=num_hosts,
        num_clients=num_hosts // 2,
        num_servers=num_hosts - num_hosts // 2,
        resp_bytes=25_000,
        pause_ns=40 * NS_PER_MS,
    )
    bw = bw_bits_per_sec_to_refill(20_000_000) if shaped else None
    return cfg, model, tables, host_node, bw


@pytest.mark.parametrize(
    "loss,shaped,end_ms,lanes",
    [(0.0, False, 250, 0), (0.05, False, 400, 0), (0.02, True, 400, 0), (0.02, True, 400, 3)],
    ids=["clean", "lossy", "lossy-shaped", "lossy-shaped-compact"],
)
def test_device_tgen_matches_scalar_oracle(loss, shaped, end_ms, lanes):
    import dataclasses

    cfg, model, tables, host_node, bw = _world(8, loss, shaped, seed=13)
    if lanes:
        cfg = dataclasses.replace(cfg, active_lanes=lanes)
    end = end_ms * NS_PER_MS

    st = init_state(cfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw)
    st = bootstrap(st, model, cfg)
    st = run_until(st, end, model, tables, cfg, rounds_per_chunk=16)

    ref = CpuRefTgen(cfg, model, tables, host_node,
                     tx_bytes_per_interval=bw, rx_bytes_per_interval=bw)
    ref.bootstrap()
    ref.run_until(end)

    # every TCP state field, bit for bit
    for f in TCP_FIELDS:
        dev = np.asarray(getattr(st.model.tcp, f))
        np.testing.assert_array_equal(dev, ref.tcp_field(f).astype(dev.dtype), err_msg=f)

    # model + engine counters
    np.testing.assert_array_equal(np.asarray(st.model.streams_started), ref.streams_started)
    np.testing.assert_array_equal(np.asarray(st.model.streams_done), ref.streams_done)
    np.testing.assert_array_equal(np.asarray(st.model.bytes_down), ref.bytes_down)
    np.testing.assert_array_equal(np.asarray(st.model.resets), ref.resets)
    np.testing.assert_array_equal(np.asarray(st.seq), np.array(ref.seq, np.uint32))
    np.testing.assert_array_equal(np.asarray(st.rng_counter), np.array(ref.ctr, np.uint32))
    np.testing.assert_array_equal(np.asarray(st.packets_sent), ref.packets_sent)
    np.testing.assert_array_equal(np.asarray(st.packets_dropped), ref.packets_dropped)
    np.testing.assert_array_equal(np.asarray(st.events_handled), ref.events_handled)
    if shaped:
        np.testing.assert_array_equal(np.asarray(st.net.codel_dropped), ref.codel_dropped)
        np.testing.assert_array_equal(np.asarray(st.net.bytes_sent), ref.bytes_sent)
        np.testing.assert_array_equal(np.asarray(st.net.bytes_recv), ref.bytes_recv)

    # leftover queue contents in canonical order
    for h in range(cfg.num_hosts):
        assert equeue.debug_sorted_events(st.queue, h) == ref.queue_contents(h), f"host {h}"

    # the run actually cycled streams (oracle self-check)
    assert sum(ref.streams_done) > 0
    assert sum(ref.bytes_down) >= sum(ref.streams_done) * model.resp_bytes
