"""Fat-tree topology ladder (BASELINE config 4: iperf-like TCP saturation
on a fat-tree). The generator (`examples/fattree/gen_fattree.py`) is
seed-free and writes the GML or the front door's whole document; the
benchmark's configuration `fattree-10k` is its output at k=16. Here, at
k=4 (8 edge switches, 64 hosts, the same rates, loss and `resp_bytes`):
the program through the front door against the plain reference
(`benchmarks/reference/pdes_ref.c`) to 3 ms of simulated time, all six
per-host counters exact, on one device and on four virtual ones."""

import importlib.util
import json
import subprocess
import sys
import pathlib

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).parent.parent
GEN = ROOT / "examples" / "fattree" / "gen_fattree.py"
END_NS = 3_000_000
SEED = 2**31 + 4242


def _gen():
    spec = importlib.util.spec_from_file_location("gen_fattree", GEN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _refworld():
    sys.path.insert(0, str(ROOT / "benchmarks"))
    try:
        from reference import world
    finally:
        sys.path.remove(str(ROOT / "benchmarks"))
    return world


@pytest.mark.parametrize("k,nodes,edges,groups", [(4, 20, 40, 8), (16, 320, 2176, 128)])
def test_generator_is_deterministic_and_counts(k, nodes, edges, groups):
    """Byte-identical across calls (nothing is drawn), with a k-ary
    fat-tree's counts: 5k^2/4 switches, k^2/2 self-loops + k^3/4
    edge-aggregation + k^3/4 aggregation-core links, k^2/2 host groups."""
    argv = [sys.executable, str(GEN), "--config", "--k", str(k), "--hosts-per-edge", "8"]
    a = subprocess.run(argv, capture_output=True, text=True, check=True).stdout
    b = subprocess.run(argv, capture_output=True, text=True, check=True).stdout
    assert a == b
    doc = json.loads(a)
    assert doc == _gen().fattree_config(k=k, hosts_per_edge=8)
    gml = doc["network"]["graph"]["inline"]
    assert gml.count("node [") == nodes == 5 * k * k // 4
    assert gml.count("edge [") == edges == k * k // 2 + k**3 // 2
    assert gml.count("packet_loss 0.0005") == k**3 // 4
    assert len(doc["hosts"]) == groups
    assert sum(g["quantity"] for g in doc["hosts"].values()) == 8 * groups
    # pod order: the first half of the groups (the clients) lie in the lower pods
    assert list(doc["hosts"])[:2] == ["p00e0", "p00e1"]
    assert len({json.dumps(g["processes"], sort_keys=True) for g in doc["hosts"].values()}) == 1
    assert doc["x-benchmark"]["reduced"] == ["network.graph"]


def test_benchmark_configuration_is_the_generators_output():
    """`benchmarks/configs/fattree-10k.json` is `gen_fattree.py --config`
    with its defaults: 10,240 hosts, 128 groups, 1 Gbit, k=16."""
    doc = json.loads((ROOT / "benchmarks" / "configs" / "fattree-10k.json").read_text())
    assert doc == _gen().fattree_config()
    assert sum(g["quantity"] for g in doc["hosts"].values()) == 10_240
    assert doc["experimental"]["queue_capacity"] == 512
    assert doc["experimental"]["outbox_capacity"] == 256


def _run(devices: int, **capacities):
    """The k=4 world through the front door to END_NS; returns the per-host
    counters, the last chunk's probe and the reference's counters."""
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.engine.round import host_stats
    from shadow_tpu.runtime.manager import Manager
    from shadow_tpu.runtime.scheduler import make_scheduler

    refworld = _refworld()
    raw = _gen().fattree_config(k=4, hosts_per_edge=8, rounds_per_chunk=8, **capacities)
    raw["general"]["seed"] = SEED
    raw["general"]["tracker"] = True  # high-water marks; trajectory-neutral
    ref_config = json.loads(json.dumps(raw))
    config = ConfigOptions.from_dict(raw)
    world = Manager(config).build_world()
    assert world.runahead_ns == 5_000 and world.ecfg.num_hosts == 64
    sched = make_scheduler(
        "tpu", world.model, world.tables, world.ecfg, world.host_node,
        parallelism=devices, rounds_per_chunk=config.experimental.rounds_per_chunk,
        tx_bytes_per_interval=world.tx_refill, rx_bytes_per_interval=world.rx_refill,
    )
    assert sched.num_devices == devices
    probes = []
    out = sched.run(END_NS, start_state=sched.initial_state(), on_chunk=probes.append)
    hs = host_stats(jax.block_until_ready(out))
    return {k: hs[k] for k in refworld.COUNTERS}, probes[-1], ref_config


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """run(ref_config) -> the reference's per-host counters at END_NS."""
    refworld = _refworld()
    work = str(tmp_path_factory.mktemp("pdes_ref"))
    binary = refworld.build_reference(work)

    def run(ref_config):
        world = refworld.World(ref_config, SEED)
        assert world.lat.shape == (20, 20) and world.runahead_ns == 5_000
        return refworld.run_reference(binary, world, END_NS, work)

    return run


@pytest.mark.parametrize("devices", (1, 4))
def test_program_matches_the_plain_reference(reference, devices):
    got, probe, ref_config = _run(devices, queue_capacity=512, outbox_capacity=256)
    want = reference(ref_config)
    assert probe.overflow == 0 and probe.drop_loss > 0
    assert int(want["packets_sent"].sum()) > 5_000  # saturating: ~110 packets a host
    numbers = _refworld().compare(got, want)
    assert all(v == 0 for v in numbers.values()), numbers


def test_a_refill_burst_above_half_the_outbox_neither_overflows_nor_differs(reference):
    """A host whose bucket refilled sends a whole congestion window (80
    packets here) in ONE 5 us round: more than half of a 128-slot outbox,
    staged and flushed without loss, and equal to the reference."""
    got, probe, ref_config = _run(1, queue_capacity=256, outbox_capacity=128)
    assert 64 < probe.outbox_hwm <= 128, probe.outbox_hwm
    assert probe.overflow == 0 and probe.queue_overflow == 0 and probe.outbox_overflow == 0
    numbers = _refworld().compare(got, reference(ref_config))
    assert all(v == 0 for v in numbers.values()), numbers


def test_fattree_bulk_tcp_smoke():
    gml = subprocess.run(
        [sys.executable, str(GEN), "4"], capture_output=True, text=True, check=True
    ).stdout
    from shadow_tpu.engine import EngineConfig, init_state
    from shadow_tpu.engine.round import bootstrap, check_capacity, run_rounds_scan
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models.bulk import BulkTcpModel
    from shadow_tpu.simtime import NS_PER_SEC

    graph = NetworkGraph.from_gml(gml)
    # k=4: 4 core + 4 pods x (2 agg + 2 edge) = 20 nodes; edges hold hosts
    assert graph.num_nodes == 20
    edge_nodes = [i for i in range(graph.num_nodes) if graph.bw_up_bits[i] > 0]
    assert len(edge_nodes) == 8
    num_hosts = 32
    host_node = [edge_nodes[i % len(edge_nodes)] for i in range(num_hosts)]
    tables = compute_routing(graph).with_hosts(host_node)
    cfg = EngineConfig(
        num_hosts=num_hosts,
        queue_capacity=512,
        outbox_capacity=128,
        runahead_ns=graph.min_latency_ns(),
        seed=7,
    )
    model = BulkTcpModel(num_hosts=num_hosts, num_pairs=num_hosts // 2, total_bytes=200_000)
    st = init_state(cfg, model.init())
    st = bootstrap(st, model, cfg)
    st = run_rounds_scan(st, jnp.asarray(NS_PER_SEC, jnp.int64), 400, model, tables, cfg)
    check_capacity(st)
    # every server host received the full stream, exactly once
    delivered = jnp.sum(st.model.tcp.delivered, axis=1)[num_hosts // 2 :]
    assert int(jnp.sum(delivered == 200_000)) == num_hosts // 2, delivered
    assert int(st.packets_unroutable.sum()) == 0
