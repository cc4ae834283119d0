"""Fat-tree topology ladder (BASELINE config 4: iperf-like TCP saturation
on a fat-tree). The generator (`examples/fattree/gen_fattree.py`) is
seed-free and writes the GML or the front door's whole document; the
benchmark's configuration `fattree-10k` is its output at k=16. Here, at
k=4 (8 edge switches, 64 hosts, the same rates, loss and `resp_bytes`):
the program through the front door against the plain reference
(`benchmarks/reference/pdes_ref.c`) to 3 ms of simulated time, all six
per-host counters exact, on one device and on four virtual ones.

The same with cables of unequal length (`--pod-step-us 8 --edge-step-us
3`, the benchmark's configuration `fattree-10k-cabled`, PR 34): the flows
fall out of step, so the same span holds many more live rounds, which
the state counts with the tracker on or off, whatever the chunking or
the device count."""

import dataclasses
import functools
import importlib.util
import json
import os
import re
import subprocess
import sys
import pathlib

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).parent.parent
GEN = ROOT / "examples" / "fattree" / "gen_fattree.py"
CONFIGS = ROOT / "benchmarks" / "configs"
END_NS = 3_000_000
SEED = 2**31 + 4242
STEPS = {"pod_step_us": 8, "edge_step_us": 3}  # fattree-10k-cabled's


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


@functools.lru_cache(maxsize=None)
def _run(devices: int, cabled: bool = False, rounds_per_chunk: int = 8, tracker: bool = True,
         queue_capacity: int = 512, outbox_capacity: int = 256):
    """The k=4 world through the front door to END_NS; returns the per-host
    counters, the last chunk's probe and the reference's document."""
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.engine.round import host_stats
    from shadow_tpu.runtime.manager import Manager
    from shadow_tpu.runtime.scheduler import make_scheduler

    refworld = _refworld()
    raw = _gen().fattree_config(
        k=4, hosts_per_edge=8, rounds_per_chunk=rounds_per_chunk,
        queue_capacity=queue_capacity, outbox_capacity=outbox_capacity,
        **(STEPS if cabled else {}))
    raw["general"]["seed"] = SEED
    raw["general"]["tracker"] = tracker  # high-water marks; trajectory-neutral
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


@pytest.mark.parametrize("cabled", (False, True), ids=("lockstep", "cabled"))
@pytest.mark.parametrize("devices", (1, 4))
def test_program_matches_the_plain_reference(reference, devices, cabled):
    got, probe, ref_config = _run(devices, cabled)
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


# --- cables of unequal length (PR 34) -------------------------------------


def _cli(*options):
    argv = [sys.executable, str(GEN), "--config", *options]
    return subprocess.run(argv, capture_output=True, text=True, check=True).stdout


def test_both_benchmark_configurations_are_the_generators_output_byte_for_byte():
    """The defaults still write `fattree-10k.json`, to the byte; the two
    steps write `fattree-10k-cabled.json`, and nothing is drawn."""
    assert _cli() == (CONFIGS / "fattree-10k.json").read_text()
    cabled = _cli("--pod-step-us", "8", "--edge-step-us", "3")
    assert cabled == _cli("--pod-step-us", "8", "--edge-step-us", "3")
    assert cabled == (CONFIGS / "fattree-10k-cabled.json").read_text()
    assert json.loads(cabled) == _gen().fattree_config(**STEPS)


def _one_way_us(doc):
    """Shortest one-way latency (us) between every pair of the document's
    graph nodes: the test's own Floyd-Warshall over the GML's edges."""
    import numpy as np

    gml = doc["network"]["graph"]["inline"]
    n = gml.count("node [")
    d = np.full((n, n), 10**9, np.int64)
    for a, b, us in re.findall(r'edge \[ source (\d+) target (\d+) latency "(\d+) us"', gml):
        d[int(a), int(b)] = d[int(b), int(a)] = min(d[int(a), int(b)], int(us))
    for m in range(n):
        d = np.minimum(d, d[:, m:m + 1] + d[m:m + 1, :])
    return d


def test_the_cabled_configuration_is_fattree_10k_with_unequal_paths():
    """10,240 hosts, the same groups, processes and source; 64 distinct
    client-to-server paths of 264-418 us where `fattree-10k` has one of
    200; the lookahead stays the 5 us self-loop; outbox 512, 128 rounds a
    chunk; nothing else differs."""
    cabled = json.loads((CONFIGS / "fattree-10k-cabled.json").read_text())
    plain = json.loads((CONFIGS / "fattree-10k.json").read_text())
    assert sum(g["quantity"] for g in cabled["hosts"].values()) == 10_240
    groups = list(cabled["hosts"].values())
    clients, servers = groups[:64], groups[64:]  # tgen's rule: client i fetches from server i
    for doc, want in ((cabled, sorted(264 + 16 * p + 6 * e for p in range(8) for e in range(8))),
                      (plain, [200] * 64)):
        d = _one_way_us(doc)
        assert int(d.min()) == 5  # the self-loop: the world's lookahead, 5,000 ns
        paths = sorted(int(d[c["network_node_id"], s["network_node_id"]])
                       for c, s in zip(clients, servers))
        assert paths == want
    assert cabled["experimental"] == dict(plain["experimental"], outbox_capacity=512,
                                          rounds_per_chunk=128)
    assert cabled["hosts"] == plain["hosts"] and cabled["general"] == plain["general"]
    head, head10 = cabled["x-benchmark"], plain["x-benchmark"]
    assert "--pod-step-us 8 --edge-step-us 3" in head["source"]
    assert "264-418 us, 64 distinct" in head["assumed"]["link_latency_us"]
    for key in ("reduced", "reduced_why", "guarantees"):
        assert head[key] == head10[key]
    assert head["reduced"] == ["network.graph"]
    differing = {k for k in head["assumed"] if head["assumed"][k] != head10["assumed"][k]}
    assert differing == {"link_latency_us", "queue_capacity", "outbox_capacity", "rounds_per_chunk"}


@pytest.mark.parametrize("other", [
    dict(tracker=False), dict(rounds_per_chunk=32, tracker=False),
    dict(rounds_per_chunk=128, tracker=False), dict(devices=4),
], ids=("tracker-off", "chunk-32", "chunk-128", "four-devices"))
def test_live_rounds_are_counted_whatever_the_tracker_the_chunking_or_the_devices(other):
    """`rounds_live` rides the probe from the state itself: the same count,
    and the same per-host counters, with the tracker off, at 8, 32 and 128
    rounds a chunk, and over four devices."""
    import numpy as np

    other = dict(other)
    want, want_probe, _ = _run(1, True)
    got, probe, _ = _run(other.pop("devices", 1), True, **other)
    assert probe.rounds_live == want_probe.rounds_live > 0
    assert probe.win_ns_sum == want_probe.win_ns_sum
    assert probe.window_ns_mean == want_probe.window_ns_mean > 0
    for k in want:
        assert np.array_equal(got[k], want[k]), k


def test_unequal_cables_make_many_more_live_rounds_for_the_same_span():
    """Four path lengths where there was one: the flows fall out of step
    and the adaptive window finds an event far more often."""
    _, lockstep, _ = _run(1, False)
    _, cabled, _ = _run(1, True)
    assert cabled.rounds_live > 3 * lockstep.rounds_live > 0, (cabled, lockstep)
    assert cabled.iters / cabled.rounds_live < lockstep.iters / lockstep.rounds_live


def test_the_cabled_cell_rehearses_end_to_end():
    """`benchmarks/run.py --workload fattree-10k-cabled.ramp --rehearse
    --trace 1`: 128 hosts on the CPU equal the plain reference, and the
    unit 2-3 ms has the counts the full size has (rounds and iterations
    depend on the paths, not on the hosts a switch): 123 live rounds,
    1,486 drain iterations, 1.1 % of the rows live."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         "fattree-10k-cabled.ramp", "--seed", "7", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True and out["failed"] == 0
    assert all(v["value"] == 0 == v["limit"] for v in out["check"].values())
    assert "128 hosts, 1 chip(s)" in r.stdout
    counted = {k: m["value"] for k, m in out["metrics"].items() if m["value"] is not None}
    assert set(counted) == {
        "drain.iters_per_unit", "drain.rounds_per_unit", "drain.occupancy_pct",
        "exchange.passes_per_unit", "exchange.fill_pct", "exchange.land_hwm", "exchange.staged_hwm",
        "exchange.flat_pct",
    }
    assert counted["drain.rounds_per_unit"] == 123
    assert counted["drain.iters_per_unit"] == 1486
    assert 1.0 < counted["drain.occupancy_pct"] < 1.25
    # the exchange's counts, tracker off (PR 36): the passes are the paths'
    # too (520 at full size), a mean round stages 0.04 % of what it flattens,
    # and the busiest destination of the ramp landed one window of 40
    assert counted["exchange.passes_per_unit"] == 520
    assert 0.03 < counted["exchange.fill_pct"] < 0.045
    assert counted["exchange.land_hwm"] == 40
    assert 40 <= counted["exchange.staged_hwm"] <= 128 * 512
    # the flush's width (PR 37): a sender stages at most one window of 40
    # in a round of the ramp, so every flush takes one block, 64 of 512
    # columns
    assert counted["exchange.flat_pct"] == 12.5


def _reader(name):
    path = ROOT / "benchmarks" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_two_readers_read_the_kept_probes_and_none_without_them(monkeypatch):
    """`drain.rounds_per_unit` and `drain.occupancy_pct` take the
    difference of the program's kept probes; against a program that keeps
    none (the parent: no `scopes.last_probes`), or whose newest entry was
    not the unit, they return None and raise nothing."""
    import types

    from shadow_tpu import scopes
    from shadow_tpu.engine.round import ChunkProbe, PROBE_LANES

    monkeypatch.syspath_prepend(str(ROOT / "benchmarks"))
    rounds, occupancy = _reader("drain.rounds_per_unit"), _reader("drain.occupancy_pct")
    ctx = types.SimpleNamespace(iters_per_unit=40, chips=1)

    def probe(rounds_live, iters, lanes_live):
        return dataclasses.replace(ChunkProbe.from_array([0] * PROBE_LANES),
                                   rounds_live=rounds_live, iters=iters, lanes_live=lanes_live)

    kept = scopes.EntryProbes(hosts=100, outbox_slots=1600, entry=probe(7, 60, 900),
                              chunk=probe(12, 100, 980))
    monkeypatch.setattr(scopes, "last_probes", kept)
    assert rounds(ctx) == 5 and occupancy(ctx) == pytest.approx(100.0 * 80 / (40 * 100))
    ctx.chips = 4  # a shard scans a quarter of the rows
    assert occupancy(ctx) == pytest.approx(100.0 * 80 / (40 * 25))
    ctx.iters_per_unit = 41  # the newest entry was not the unit
    assert rounds(ctx) is None and occupancy(ctx) is None
    ctx.iters_per_unit = 40
    kept.chunk = None  # an entry that launched no chunk
    assert rounds(ctx) is None and occupancy(ctx) is None
    monkeypatch.setattr(scopes, "last_probes", None)
    assert rounds(ctx) is None and occupancy(ctx) is None
    monkeypatch.delattr(scopes, "last_probes")  # the parent's program
    assert rounds(ctx) is None and occupancy(ctx) is None


def test_benchmark_json_names_the_cabled_configuration_and_its_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = {c["name"]: c for c in bench["configs"]}["fattree-10k-cabled"]
    assert config["file"] == "benchmarks/configs/fattree-10k-cabled.json"
    assert config["reduced"] == ["network.graph"] and len(config["source"]) <= 200
    cell = {w["name"]: w for w in bench["workloads"]}["fattree-10k-cabled.ramp"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fattree-10k-cabled", "ramp", 1)
    params = json.loads((ROOT / "benchmarks" / "cells" / "fattree-10k-cabled.ramp.json").read_text())
    assert (params["warm_sim_ms"], params["unit_sim_ms"], params["rehearse"]["hosts"]) == (2, 1, 128)
    by = {m["name"]: m for m in bench["per_layer"]}
    every = [w["name"] for w in bench["workloads"]]
    for name, better in (("drain.rounds_per_unit", "lower"), ("drain.occupancy_pct", "higher")):
        m = by[name]
        assert m["workloads"][:len(every)] == every  # every cell; later cells are appended
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_counter", "drain", "sim_s_per_wall_s", better)
    listed = {n for n, m in by.items() if "fattree-10k-cabled.ramp" in m["workloads"]}
    assert len(listed) >= 21  # the 21 of PR 34; later PRs append theirs (PR 35: the pop's)
    assert not listed & {"drain.iter_ms", "exchange.flush_ms", "exchange.flush_roofline",
                         "driver.unit_p95_ms", "exchange.land_roofline",
                         "exchange.collective_ms_per_unit"}
    for name in listed:  # a cell is only ever appended: after the five that were there
        cells = by[name]["workloads"]
        assert set(cells[:cells.index("fattree-10k-cabled.ramp")]) <= set(every[:5])


def test_chip_smoke_knows_the_cabled_deployment():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    doc, parity_stop, rehearse_hosts, _stop = chip_smoke.DEPLOYMENTS["fattree-10k-cabled"]
    assert (ROOT / doc) == CONFIGS / "fattree-10k-cabled.json"
    assert (parity_stop, rehearse_hosts) == ("3 ms", 128)


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
