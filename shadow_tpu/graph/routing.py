"""All-pairs path properties on device: tropical (min-plus) matrix squaring.

The reference computes all-pairs shortest paths with a rayon-parallelized
Dijkstra per source (reference: src/main/network/graph/mod.rs:185-230) or a
direct-edges-only table (:232-254), composing per-path properties as
latency-sum / reliability-product (:300-333). On TPU the natural formulation
is matrix iteration over the (min, +) semiring: D <- min_k(D[i,k] + D[k,j]),
log2(N) squarings, each a blocked "tropical matmul" carrying reliability
along the argmin path. Ties pick the smallest intermediate node index, so
the result is deterministic.

Self-paths (diagonal) come from self-loop edges only, as in the reference
(graph/mod.rs:212-219): a node with no self-loop has no path to itself.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu import scopes
from shadow_tpu.graph.network_graph import NetworkGraph
from shadow_tpu.simtime import TIME_MAX

# A host map made of at most this many runs of equal entries is looked up
# by compares against the runs' bounds (`node_of`), one with more by a
# gather. Host groups give one run a group (32 in tgen-10k, 128 in the k=16
# fat-tree, 578 edge switches in the k=34 one), hosts listed singly on
# scattered nodes about one a host. Set once from the chip (TPU v5e, ids
# [10240, 5]): the compares cost 0.005 / 0.013 / 0.055 / 0.090 / 0.235 ms
# at 32 / 128 / 578 / 1,024 / 2,048 runs against the gather's 0.400 ms
# whatever the runs, so they meet near 3,500; 2,048 is the largest count
# measured (PERF.md section 6, PR 31).
ROUTE_RUNS_MAX = 2048


@flax.struct.dataclass
class RoutingTables:
    """Dense node-to-node path properties, device-resident.

    lat_ns[i, j] == TIME_MAX means unreachable. After `with_hosts`, the
    engine looks a packet's path up through `route_lookup`: the
    destination's node (`node_of`) and then ONE gather of the pair's three
    words out of `packed`, which reads the same values as
    lat_ns[host_node[src_host], host_node[dst_host]] and rel[...] of the
    same pair. `host_node` is indexed by *global* host id and everything
    here is replicated across shards (the engine's only per-packet routing
    state, the analogue of RoutingInfo's path table, reference
    graph/mod.rs:432-449). `lat_ns` and `rel` stay: the host-side tiers
    (cpu_ref, hostk, the hybrid runtime) read them as numpy.
    """

    lat_ns: jax.Array  # [N, N] i64
    rel: jax.Array  # [N, N] f32
    host_node: "jax.Array | None" = None  # [H_global] i32
    # The three words of a pair under one index, word-major (a minor axis
    # of 3 would pad to 128 lanes): row 0 lat_ns' low word, row 1 its high
    # word, row 2 rel's bits; column src_node * N + dst_node. A gather
    # costs per index, so one of a three-word row replaces three.
    packed: "jax.Array | None" = None  # [3, N * N] i32
    # `host_node` run-length encoded, where it is made of at most
    # ROUTE_RUNS_MAX runs (hosts that come from groups): run r starts at
    # host id run_lo[r] (run_lo[0] == 0) and its node is the sum of
    # run_delta[:r + 1]. None otherwise, and `node_of` gathers.
    run_lo: "jax.Array | None" = None  # [R] i32
    run_delta: "jax.Array | None" = None  # [R] i32
    # Per-node conservative lookahead: the minimum finite path latency out
    # of each node (self-loops included), i.e. a lower bound on how far in
    # the future ANY packet emitted by a host on that node can land. The
    # round engine's adaptive window (engine/round.py _next_window_end)
    # extends the conservative window to min over hosts of
    # (next_event_time + lookahead) — the classic Chandy–Misra/Fujimoto
    # LBTS bound — which is exactness-preserving because the round-end
    # delivery clamp provably never binds under it. TIME_MAX for nodes
    # with no finite outgoing path (their packets are all unroutable).
    lookahead_ns: "jax.Array | None" = None  # [N] i64

    @property
    def num_nodes(self) -> int:
        return self.lat_ns.shape[0]

    @property
    def num_global_hosts(self) -> int:
        return self.host_node.shape[0]

    @property
    def route_runs(self) -> int:
        """Runs of equal entries in `host_node` that `node_of` compares
        against; 0 where it gathers."""
        return 0 if self.run_lo is None else self.run_lo.shape[0]

    @property
    def route_path(self) -> str:
        """How `node_of` finds a host's node: "runs" or "gather"."""
        return "runs" if self.route_runs else "gather"

    def with_hosts(self, host_node) -> "RoutingTables":
        """Attach the host map and what the handler's lookup reads: the
        packed pair table and, where the map is made of few runs, the
        runs' bounds. Host side, once per world."""
        hn = np.asarray(host_node, np.int32)
        if hn.ndim != 1:
            raise ValueError("host_node must be 1-D [num_hosts]")
        change = np.ones(hn.shape, bool)
        change[1:] = hn[1:] != hn[:-1]
        first = np.flatnonzero(change)  # each run's first host id
        run_lo = run_delta = None
        if 0 < first.size <= ROUTE_RUNS_MAX:
            run_lo = jnp.asarray(first, jnp.int32)
            run_delta = jnp.asarray(np.diff(hn[first], prepend=0), jnp.int32)
        lat = np.asarray(self.lat_ns, np.int64).reshape(-1)
        packed = np.stack([
            (lat & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
            (lat >> 32).astype(np.int32),
            np.asarray(self.rel, np.float32).reshape(-1).view(np.int32),
        ])
        return self.replace(
            host_node=jnp.asarray(hn), packed=jnp.asarray(packed),
            run_lo=run_lo, run_delta=run_delta,
        )

    def with_lookahead(self) -> "RoutingTables":
        """Attach the per-node lookahead (row-min of the latency table).
        The min over any row equals the node's min outgoing edge latency:
        every path's latency is bounded below by its first hop."""
        row_min = jnp.min(self.lat_ns, axis=1)
        return self.replace(lookahead_ns=jnp.minimum(row_min, TIME_MAX))

    def min_path_latency_ns(self) -> int:
        """Minimum finite path latency — upper bound for a valid runahead."""
        lat = np.asarray(self.lat_ns)
        finite = lat[lat < TIME_MAX]
        if finite.size == 0:
            raise ValueError("routing table has no reachable pairs")
        return int(finite.min())


def _node_of(tables: RoutingTables, ids: jax.Array) -> jax.Array:
    if tables.run_lo is None:
        return tables.host_node[ids]
    # one compare and one select a run and element, summed over the runs:
    # a reduction over a leading [R] axis that the compiler fuses with the
    # broadcast compare (no [R, ...] array is made, and no gather)
    over = (-1,) + (1,) * ids.ndim
    step = jnp.where(
        ids[None] >= tables.run_lo.reshape(over), tables.run_delta.reshape(over), 0
    )
    return jnp.sum(step, axis=0, dtype=jnp.int32)


@scopes.scoped(scopes.ROUTE)
def node_of(tables: RoutingTables, ids: jax.Array) -> jax.Array:
    """`host_node[ids]` for global host ids in [0, H), any shape: by
    compares against the runs' bounds where the tables carry them (exact:
    the deltas of the runs at or below an id sum to its run's node), by the
    gather otherwise. The choice is the tables' structure, so it is static
    under jit."""
    return _node_of(tables, ids)


@scopes.scoped(scopes.ROUTE)
def route_lookup(
    tables: RoutingTables, src_node: jax.Array, dst: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(dst_node i32, lat i64, rel f32), each of `dst`'s shape: the path
    from each source node (`src_node`, of `dst`'s shape less its last axis,
    or of its shape) to the node of global host id `dst` (in [0, H): the
    caller clamps). Bit for bit `host_node[dst]`, `lat_ns[src_node,
    dst_node]` and `rel[src_node, dst_node]`, at one gather an index."""
    dst_node = _node_of(tables, dst)
    if src_node.ndim < dst.ndim:
        src_node = src_node[..., None]
    w = tables.packed[:, src_node * tables.num_nodes + dst_node]  # [3, ...]
    lat = (w[1].astype(jnp.int64) << 32) | w[0].astype(jnp.uint32).astype(jnp.int64)
    return dst_node, lat, jax.lax.bitcast_convert_type(w[2], jnp.float32)


def _minplus_square_once(lat: jax.Array, rel: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """One squaring step: out[i,j] = min(lat[i,j], min_k lat[i,k]+lat[k,j]).

    Blocked over rows and scanned over k-chunks so peak memory stays
    O(block * chunk * N) and XLA can fuse the broadcast-add with the min
    reduction.
    """
    n = lat.shape[0]
    nk = n // block

    lat_k = lat.reshape(nk, block, n)  # k-chunks of the "B" operand
    rel_k = rel.reshape(nk, block, n)

    def row_block(args):
        lat_blk, rel_blk = args  # [B, N] rows of the "A" operand

        la = lat_blk.reshape(lat_blk.shape[0], nk, block).transpose(1, 0, 2)  # [nk, B, C]
        ra = rel_blk.reshape(rel_blk.shape[0], nk, block).transpose(1, 0, 2)

        def body(carry, xs):
            best_lat, best_rel = carry
            la_c, ra_c, lb_c, rb_c = xs  # [B,C], [B,C], [C,N], [C,N]
            cand_lat = la_c[:, :, None] + lb_c[None, :, :]  # [B, C, N]
            k_best = jnp.argmin(cand_lat, axis=1)  # [B, N]
            cl = jnp.take_along_axis(cand_lat, k_best[:, None, :], axis=1)[:, 0, :]
            cand_rel = ra_c[:, :, None] * rb_c[None, :, :]
            cr = jnp.take_along_axis(cand_rel, k_best[:, None, :], axis=1)[:, 0, :]
            upd = cl < best_lat
            return (jnp.where(upd, cl, best_lat), jnp.where(upd, cr, best_rel)), None

        (out_lat, out_rel), _ = jax.lax.scan(body, (lat_blk, rel_blk), (la, ra, lat_k, rel_k))
        return out_lat, out_rel

    # row-blocks of the "A" operand are the same chunking as lat_k/rel_k
    out_lat, out_rel = jax.lax.map(row_block, (lat_k, rel_k))
    return out_lat.reshape(n, n), out_rel.reshape(n, n)


def _pad_to_multiple(arr: np.ndarray, block: int, fill) -> np.ndarray:
    n = arr.shape[0]
    pad = (-n) % block
    if pad == 0:
        return arr
    out = np.full((n + pad, n + pad), fill, dtype=arr.dtype)
    out[:n, :n] = arr
    return out


def compute_routing(
    graph: NetworkGraph, use_shortest_path: bool = True, block: int = 128
) -> RoutingTables:
    """Build node-to-node routing tables (runs the solve on the default device)."""
    n = graph.num_nodes
    block = min(block, max(8, 1 << (n - 1).bit_length()))

    lat0 = _pad_to_multiple(graph.lat_ns, block, TIME_MAX)
    rel0 = _pad_to_multiple(graph.rel, block, 0.0)

    if not use_shortest_path:
        # direct-edges-only mode (reference graph/mod.rs:232-254): the table
        # is just the adjacency, self-loops included.
        return RoutingTables(
            lat_ns=jnp.asarray(lat0[:n, :n]), rel=jnp.asarray(rel0[:n, :n])
        ).with_lookahead()

    np_n = lat0.shape[0]
    # transit computation runs with a free (0-cost) diagonal…
    diag = np.arange(np_n)
    lat_t = lat0.copy()
    rel_t = rel0.copy()
    lat_t[diag, diag] = 0
    rel_t[diag, diag] = 1.0

    lat_d = jnp.asarray(lat_t)
    rel_d = jnp.asarray(rel_t)

    @jax.jit
    def solve(lat, rel):
        steps = max(1, (max(n - 1, 1)).bit_length())
        for _ in range(steps):
            lat, rel = _minplus_square_once(lat, rel, block)
            # clamp so unreachable+unreachable cannot overflow i64 next round
            lat = jnp.minimum(lat, TIME_MAX)
        return lat, rel

    lat_sp, rel_sp = solve(lat_d, rel_d)

    # …then the diagonal is replaced by self-loop edge properties, matching
    # the reference's node-to-self semantics (graph/mod.rs:212-219).
    self_lat = jnp.asarray(np.ascontiguousarray(np.diagonal(lat0)))
    self_rel = jnp.asarray(np.ascontiguousarray(np.diagonal(rel0)))
    di = jnp.arange(np_n)
    lat_sp = lat_sp.at[di, di].set(self_lat)
    rel_sp = rel_sp.at[di, di].set(self_rel)

    return RoutingTables(lat_ns=lat_sp[:n, :n], rel=rel_sp[:n, :n]).with_lookahead()
