"""The handler's routing lookup (graph/routing.py `node_of`, `route_lookup`).

One gather a packet lane: path latency and reliability ride one packed,
word-major table under one index, and a host's node is read from the host
groups' bounds where the host map is made of few runs. Pinned here, on the
CPU: bit for bit the plain lookups (`host_node[ids]`, `lat_ns[s, d]`,
`rel[s, d]`) on five worlds, at the runs' edges and at random, in the
handler's shape `[H, EP]` and the pump's `[H]`; which path each world
takes; and how many gathers of a routing table the lowered handler holds.
"""

import functools
import importlib.util
import pathlib
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.engine import EngineConfig, init_state
from shadow_tpu.engine.round import bootstrap, handle_one_iteration
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.graph import routing
from shadow_tpu.models import PholdModel
from shadow_tpu.simtime import NS_PER_MS, TIME_MAX

ROOT = pathlib.Path(__file__).parent.parent
EP = 5


@functools.lru_cache(maxsize=None)
def _random_graph(n_nodes=32, seed=5):
    """Self-loops everywhere, a random third of the pairs linked, the last
    node linked to nobody (unreachable: TIME_MAX) and every fourth link
    lossless (reliability exactly 1)."""
    rng_py = random.Random(seed)
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "{1 + i % 3} ms" ]')
    links = 0
    for i in range(n_nodes - 1):
        for j in range(i + 1, n_nodes - 1):
            if j == i + 1 or rng_py.random() < 0.3:
                loss = 0.0 if links % 4 == 0 else rng_py.choice([0.001, 0.02, 0.3])
                lines.append(
                    f'  edge [ source {i} target {j} latency "{rng_py.randrange(2, 4000)} us" '
                    f"packet_loss {loss} ]"
                )
                links += 1
    lines.append("]")
    return compute_routing(NetworkGraph.from_gml("\n".join(lines)))


def _fattree_k4():
    """The k=4 fat-tree through the front door: 8 host groups of 8."""
    from shadow_tpu.config.options import ConfigOptions
    from shadow_tpu.runtime.manager import Manager

    spec = importlib.util.spec_from_file_location(
        "gen_fattree", ROOT / "examples" / "fattree" / "gen_fattree.py"
    )
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    raw = gen.fattree_config(k=4, hosts_per_edge=8, rounds_per_chunk=8)
    return Manager(ConfigOptions.from_dict(raw)).build_world().tables


def _singly(n_nodes=32):
    """Hosts listed one by one on shuffled nodes: about one run a host,
    and a quarter more hosts than the limit on runs."""
    num_hosts = routing.ROUTE_RUNS_MAX * 5 // 4
    nodes = [i % n_nodes for i in range(num_hosts)]
    random.Random(9).shuffle(nodes)
    return _random_graph().with_hosts(nodes)


# name -> (tables, path, runs)
WORLDS = {
    "groups-32x4": lambda: (_random_graph().with_hosts(np.repeat(np.arange(32), 4)), "runs", 32),
    "fattree-k4": lambda: (_fattree_k4(), "runs", 8),
    "one-run": lambda: (_random_graph().with_hosts([7] * 24), "runs", 1),
    # unequal runs, node ids going down as well as up, node 5 in two runs
    "ragged": lambda: (
        _random_graph().with_hosts([5] * 3 + [31] + [2] * 9 + [5] * 2 + [30] * 6 + [0]),
        "runs", 6,
    ),
    "singly-listed": lambda: (_singly(), "gather", 0),
}


@pytest.fixture(scope="module")
def worlds():
    return {name: make() for name, make in WORLDS.items()}


def _ids(tables, shape, seed):
    """Global host ids of `shape`: every run's first and last id, 0 and
    H - 1 first, the rest at random."""
    hn = np.asarray(tables.host_node)
    h = hn.size
    first = np.flatnonzero(np.r_[True, hn[1:] != hn[:-1]])
    edges = np.unique(np.r_[first, first[1:] - 1, 0, h - 1])
    ids = np.random.default_rng(seed).integers(0, h, size=int(np.prod(shape)))
    ids[: edges.size] = edges[: ids.size]
    return ids.reshape(shape).astype(np.int32)


@pytest.mark.parametrize("caller", ["handler", "pump"])
@pytest.mark.parametrize("name", list(WORLDS))
def test_route_lookup_is_the_plain_lookups_bit_for_bit(worlds, name, caller):
    tables, _, _ = worlds[name]
    h = tables.num_global_hosts
    hn, lat_ns, rel = (np.asarray(x) for x in (tables.host_node, tables.lat_ns, tables.rel))
    src = _ids(tables, (h,), seed=1)
    dst = _ids(tables, (h, EP) if caller == "handler" else (h,), seed=2)
    if caller == "handler":  # so that the edges meet other sources too
        dst[:, 1:] = np.random.default_rng(3).permuted(dst[:, 1:], axis=0)
    else:
        dst = dst[::-1].copy()

    src_node = jax.jit(routing.node_of)(tables, jnp.asarray(src))
    np.testing.assert_array_equal(np.asarray(src_node), hn[src])
    dst_node, lat, r = jax.jit(routing.route_lookup)(tables, src_node, jnp.asarray(dst))

    s = hn[src][:, None] if caller == "handler" else hn[src]
    assert dst_node.dtype == jnp.int32 and lat.dtype == jnp.int64 and r.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(dst_node), hn[dst])
    np.testing.assert_array_equal(np.asarray(lat), lat_ns[s, hn[dst]])
    # bits, not values: a NaN or a signed zero would not get past either
    np.testing.assert_array_equal(
        np.asarray(r).view(np.uint32), rel[s, hn[dst]].view(np.uint32)
    )
    if name != "one-run" and name != "fattree-k4":
        assert (np.asarray(lat) >= TIME_MAX).any()  # the unreachable node was asked for
        assert (np.asarray(r) == 1.0).any() and (np.asarray(r) < 1.0).any()


@pytest.mark.parametrize("name", list(WORLDS))
def test_the_path_is_chosen_from_the_host_maps_runs(worlds, name):
    tables, path, runs = worlds[name]
    assert (tables.route_path, tables.route_runs) == (path, runs)
    hn = np.asarray(tables.host_node)
    changes = 1 + int(np.count_nonzero(hn[1:] != hn[:-1]))
    assert (changes <= routing.ROUTE_RUNS_MAX) == (path == "runs")
    if path == "runs":
        assert int(tables.run_lo[0]) == 0
        np.testing.assert_array_equal(
            np.cumsum(np.asarray(tables.run_delta)), hn[np.asarray(tables.run_lo)]
        )
    else:
        assert tables.run_lo is None and tables.run_delta is None
    n = tables.num_nodes
    assert tables.packed.shape == (3, n * n) and tables.packed.dtype == jnp.int32


@pytest.mark.parametrize("name,gathers", [("groups-32x4", 1), ("singly-listed", 3)])
def test_the_lowered_handler_gathers_from_a_routing_table(worlds, name, gathers):
    """One gather whose operand is a routing table (the packed one) where
    the host map is made of runs; the source's and the destination's node
    beside it where hosts are listed singly. (Before the packed table:
    four, `src_node`, `dst_node`, `lat`, `rel`.)"""
    tables, _, _ = worlds[name]
    h = tables.num_global_hosts
    cfg = EngineConfig(
        num_hosts=h, queue_capacity=16, outbox_capacity=8, runahead_ns=NS_PER_MS, seed=3
    )
    model = PholdModel(num_hosts=h, min_delay_ns=NS_PER_MS, max_delay_ns=6 * NS_PER_MS)
    st = jax.eval_shape(lambda: bootstrap(init_state(cfg, model.init()), model, cfg))
    end = jax.ShapeDtypeStruct((), jnp.int64)
    text = jax.jit(
        lambda s, we, tb: handle_one_iteration(s, we, model, tb, cfg), keep_unused=True
    ).lower(st, end, tables).as_text()
    # the entry function alone (the helpers below it number their own
    # arguments); its arguments are the leaves in order: the state's, the
    # window end, the tables'
    main = text[text.index("func.func public @main("):]
    main = main[: main.index("\n  }\n")]
    first_table = len(jax.tree.leaves((st, end)))
    last = first_table + len(jax.tree.leaves(tables)) - 1
    signature = main.splitlines()[0]
    assert f"%arg{last}:" in signature and f"%arg{last + 1}:" not in signature
    operands = [int(m) for m in re.findall(r'"stablehlo\.gather"\(%arg(\d+),', main)]
    from_tables = [a for a in operands if a >= first_table]
    assert len(from_tables) == gathers, (operands, first_table)
    packed = first_table + [
        i for i, leaf in enumerate(jax.tree.leaves(tables)) if leaf.shape[0] == 3
    ][0]
    assert from_tables.count(packed) == 1
