"""Simulation state: hosts as rows of HBM-resident tensors.

The per-host world the reference keeps behind `Host` (event queue, RNG,
deterministic counters — reference: src/main/host/host.rs:96-205) becomes a
struct-of-arrays pytree sharded/batched over the host axis. Model-specific
per-host state (the analogue of processes/sockets) hangs off `model` as an
opaque pytree whose leaves all lead with the host axis.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu import equeue, netstack, rng
from shadow_tpu.equeue import PAYLOAD_LANES, EventQueue
from shadow_tpu.events import MAX_HOSTS
from shadow_tpu.netstack import NetDevState
from shadow_tpu.simtime import TIME_MAX


ENGINES = ("auto", "plain", "pump")
EXCHANGES = ("all_to_all", "all_gather")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (trace-time) engine parameters."""

    num_hosts: int
    queue_capacity: int = 64
    outbox_capacity: int = 16
    runahead_ns: int = 1_000_000  # min link latency; the conservative window
    seed: int = 1
    max_iters_per_round: int = 1_000_000
    # Token-bucket relays + CoDel AQM (netstack.py). Off by default: hosts
    # with no bandwidth config are unshaped, like graph nodes without
    # bandwidth in the reference.
    use_netstack: bool = False
    # Relays are exempt during the bootstrap period (relay/mod.rs:200-230;
    # config bootstrap_end_time).
    bootstrap_end_ns: int = 0
    # Dynamic runahead (reference runahead.rs:43-56 + use_dynamic_runahead):
    # the window grows to the minimum latency actually used, which is >= the
    # graph minimum; correctness is preserved by the deliver-time clamp to
    # round end (worker.rs:399-402), identical to the reference's semantics.
    use_dynamic_runahead: bool = False
    # Adaptive conservative windows (engine/round.py _next_window_end):
    # extend each round's window to min over hosts of
    # (next_event_time + per-node lookahead), the Chandy–Misra/Fujimoto
    # LBTS bound, instead of the fixed start + runahead_ns width. Every
    # packet a host emits delivers at >= its next event time + its node's
    # min outgoing path latency, so the round-end delivery clamp provably
    # never binds: adaptive runs are leaf-identical to fixed-width runs
    # (tests/test_adaptive_window.py) while draining a cluster of events
    # in fewer, wider rounds. Requires RoutingTables.lookahead_ns (set by
    # compute_routing); hand-built tables without it fall back to the
    # fixed width. Unlike use_dynamic_runahead this cannot change any
    # delivery time, which is why it can default ON — and why the engine
    # ignores it when use_dynamic_runahead is set: under dynamic runahead
    # the round-end clamp DOES move delivery times, so window width is
    # semantics-bearing there and stays fixed.
    adaptive_window: bool = True
    # Round-boundary exchange mode (the cross-chip seam, the analogue of
    # worker.rs:619-629). Both modes land by pull (equeue.land_sorted):
    # one index sort by destination, then every destination pulls its
    # arrivals through the sort's permutation, a few arrival lanes a
    # pass, for as many passes as the round's busiest destination needs.
    # "all_to_all" (default) buckets outbox entries by destination shard
    # and exchanges only each peer's bucket over ICI; "all_gather"
    # replicates every shard's whole outbox (more traffic, never
    # overflows). Trajectory-identical by contract: delivery slot order
    # is key-driven (engine/round.py flush_outbox).
    exchange: str = "all_to_all"
    # per-peer bucket capacity for all_to_all:
    #  -1  (default) = the whole local outbox: never overflows. PDES
    #        traffic is often pair-skewed (client i -> server i+H/2 lands
    #        a shard's entire outbox on one peer), so the safe bucket is
    #        the default;
    #   0  = auto under ShardedRunner (topology-derived, ~4x
    #        local/devices, auto_a2a_capacity): cuts ICI traffic when
    #        destinations spread across the mesh; skew beyond the safety
    #        factor fails loudly via check_capacity. Direct flush_outbox
    #        callers treat 0 like -1;
    #  >0  = explicit bucket size.
    a2a_capacity: int = -1
    # Per-destination bound of the round-boundary landing
    # (equeue.land_sorted): a host takes at most its first
    # deliver_lanes arrivals of a ROUND; beyond it overflows loudly via
    # check_capacity. 0 (default) = queue_capacity: exact — a delivery
    # wave the queue could hold is never bounded by this. The landing is
    # a pull by arrival lane whose passes follow the arrivals the busiest
    # destination actually lands, so this width sizes no buffer and no
    # loop, and costs nothing at run or compile time (CHANGES.md PR 27,
    # PR 33).
    deliver_lanes: int = 0
    # Active-set compaction (engine/round.py handle_one_iteration_compact):
    # per pop-iteration, gather only the <= active_lanes hosts that actually
    # have an eligible event into a compact sub-state, run the handler
    # there, and scatter back — per-iteration cost tracks the *active* host
    # count instead of the world size. 0 = off (full-width iterations).
    # Results are bit-identical either way; hosts are independent within a
    # conservative window, so subset scheduling cannot reorder any host's
    # event sequence.
    active_lanes: int = 0
    # Packet-pump microscan (engine/pump.py): drain up to pump_k
    # consecutive pump-class events per host per iteration through
    # vectorized fast paths before the full handler runs. 0 = off.
    # Requires the model to expose `pump_spec`; results are bit-identical
    # to the unpumped engine (tests/test_pump.py).
    pump_k: int = 0
    # Engine selection for the round drain loop:
    #   "auto"  — on every backend: the pump microscan when pump_k > 0
    #             and the model is pump-capable, else the plain
    #             one-event-per-host handler loop (engine/round.py
    #             effective_engine).
    #   "plain" — always the full handler, even with pump_k set.
    #   "pump"  — the XLA pump microscan (requires pump_k > 0).
    # Bit-identical results across all three values (tests/test_pump.py).
    engine: str = "auto"
    # Device-side tracker plane (docs/observability.md; reference
    # tracker.c:407-430 + sim_stats.rs): accumulate per-host per-kind
    # event counters, byte classes, and high-water marks into
    # SimState.tracker. Static, so OFF traces zero extra ops; ON leaves
    # the simulated trajectory leaf-exact unchanged (tracker leaves are
    # write-only — nothing reads them back into the simulation).
    tracker: bool = False
    # Set (only) by engine/ensemble.py ensemble_engine_cfg: the round
    # drain body self-masks per batch element (replicas that drained
    # freeze as identity no-ops instead of accumulating iters under
    # vmap's any-reduced while condition). The mask is semantics-neutral
    # — ensemble slices stay leaf-exact vs single runs traced WITHOUT it
    # (tests/test_ensemble.py) — but costs an extra predicate + XLA
    # conditional per drain iteration, so unbatched traces keep the bare
    # body.
    ensemble: bool = False
    # draws consumed per handled event = model.DRAWS_PER_EVENT + PACKET_EMITS
    # (one loss draw per packet lane), fixed-stride for determinism.

    def __post_init__(self):
        if not 0 < self.num_hosts <= MAX_HOSTS:
            raise ValueError(f"num_hosts must be in (0, {MAX_HOSTS}]")
        if self.runahead_ns <= 0:
            raise ValueError("runahead must be > 0")
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r} (expected one of {ENGINES})"
            )
        if self.exchange not in EXCHANGES:
            raise ValueError(
                f"unknown exchange {self.exchange!r} (expected one of {EXCHANGES})"
            )
        if self.engine == "pump" and self.pump_k <= 0:
            raise ValueError("engine='pump' requires pump_k > 0")


def trace_static_cfg(cfg: EngineConfig) -> EngineConfig:
    """The executable-reuse seam: `cfg` with every trace-irrelevant field
    canonicalized, for use as a jit static argument / compile-cache key.

    The seed enters the simulation exclusively through the initial PRNG
    key grid built on the host (rng.host_keys / rng.replica_keys at
    init_state / init_ensemble_state time) — no engine, model, or
    netstack code reads `cfg.seed` inside a traced chunk. Canonicalizing
    it to 0 here means two worlds differing ONLY in seed hash to the
    same jit cache key and reuse one compiled chunk executable, which is
    what lets a sweep of N seeds pay one XLA compile
    (runtime/compile_cache.py; docs/service.md)."""
    return dataclasses.replace(cfg, seed=0)


@flax.struct.dataclass
class Outbox:
    """Per-host staging area for packets emitted during a round.

    Rows are owned by the emitting host, so writes are conflict-free; the
    round-boundary flush turns rows into a batched cross-host push (the
    all-to-all exchange when sharded). Delivery times are already computed
    (and clamped to >= round end, as in reference worker.rs:399-402).

    The payload keeps its 8 words on the second axis and the O slots on
    the last: the chip tiles the two minor axes (8 sublanes x 128 lanes),
    so [H, 8, O] is dense where a minor axis of 8 pads to 128 lanes and
    every pass over it moves 16 times its bytes (PERF.md, PR 29).
    """

    valid: jax.Array  # [H, O] bool
    dst: jax.Array  # [H, O] i32
    time: jax.Array  # [H, O] i64 delivery time
    tie: jax.Array  # [H, O] i64
    data: jax.Array  # [H, PAYLOAD_LANES, O] i32, slots minor
    aux: jax.Array  # [H, O] i32 (packet size in bytes)
    fill: jax.Array  # [H] i32 next free lane
    overflow: jax.Array  # [H] i32 emissions dropped for lack of lanes


def _empty_outbox(h: int, o: int) -> Outbox:
    return Outbox(
        valid=jnp.zeros((h, o), bool),
        dst=jnp.zeros((h, o), jnp.int32),
        time=jnp.full((h, o), TIME_MAX, jnp.int64),
        tie=jnp.zeros((h, o), jnp.int64),
        data=jnp.zeros((h, PAYLOAD_LANES, o), jnp.int32),
        aux=jnp.zeros((h, o), jnp.int32),
        fill=jnp.zeros((h,), jnp.int32),
        overflow=jnp.zeros((h,), jnp.int32),
    )


@flax.struct.dataclass
class TrackerState:
    """Device-side observability counters (the tracker plane; reference:
    src/main/host/tracker.c:407-430 heartbeat counters + sim_stats.rs
    worker-local counters). Accumulated inside the round engines when
    EngineConfig.tracker is set, zero otherwise; never read back by the
    simulation, so the trajectory is identical either way. Leaves lead
    with the host axis except the idle-round counter, a replicated scalar
    (each shard executes the same round sequence in lockstep; the live
    rounds are counted on SimState, tracker on or off).

    Event-kind split: kind == KIND_PACKET is a packet event, kinds in
    the model's declared TCP_KIND_RANGE (TCP timer/flush, model-owned
    because kind integers are only unique within a model — events.py)
    are tcp, everything else is a local task; packet events are
    derivable as events_handled - ev_local - ev_tcp; drop reasons live on
    SimState/NetDevState already (packets_dropped / packets_unroutable /
    net.codel_dropped). Byte classes mirror tracker.c's control/data
    split: a kept packet whose wire size is <= the model's
    WIRE_HEADER_BYTES is control (pure ACK/SYN/FIN), else data;
    retrans_segs counts retransmitted TCP segments (the per-event delta
    of the flow table's retransmits counter — identical across engines
    because the pump adds the exact same per-event count)."""

    ev_local: jax.Array  # [H] i64 local task/timer events handled
    ev_tcp: jax.Array  # [H] i64 TCP timer/flush events handled
    bytes_ctrl: jax.Array  # [H] i64 control bytes sent (kept packets)
    bytes_data: jax.Array  # [H] i64 data bytes sent (kept packets)
    retrans_segs: jax.Array  # [H] i64 retransmitted segments
    queue_hwm: jax.Array  # [H] i32 event-queue occupancy high-water mark
    outbox_hwm: jax.Array  # [H] i32 outbox fill high-water mark
    rounds_idle: jax.Array  # scalar i64 rounds skipped by the idle branch
    # Exchange high-water: the most events this shard flushed in any
    # single round (sum of outbox.fill at flush time), accumulated on
    # row 0 like SimState.iters_done so the leaf stays host-led under
    # sharding. This is the measured per-round traffic that sizes
    # all_to_all buckets (sharded.auto_a2a_capacity) and the exchange
    # occupancy figure CapacityError reports. Counted in every program,
    # cfg.tracker or not (one [H] sum a live round), like the two below.
    exch_hwm: jax.Array  # [H] i32
    # How the landing's loop engaged (equeue.land_sorted), on row 0 like
    # exch_hwm: the most arrivals ONE destination of this shard landed in
    # one round (the figure equeue.LAND_LANES is sized from), and the
    # passes the loop made, summed over the landings: ceil(that round's
    # mark / LAND_LANES) each. Exact for a seed on one plane; the three
    # are kept per shard on the shard's row 0 and, like iters_done,
    # land_passes depends on how the hosts are split over chips, so
    # comparisons across planes leave the three out
    # (tests/test_mesh.py per_shard_leaf).
    land_hwm: jax.Array  # [H] i32
    land_passes: jax.Array  # [H] i32
    # The outbox columns this shard's flushes flattened, on row 0 like the
    # three above: whole blocks of engine/round.py flush_block columns, as
    # many as hold the busiest row's fill over the whole mesh, 0 for a
    # skipped flush. i32 is safe: at most outbox_capacity a live round.
    flush_cols: jax.Array  # [H] i32


def _empty_tracker(h: int) -> TrackerState:
    return TrackerState(
        ev_local=jnp.zeros((h,), jnp.int64),
        ev_tcp=jnp.zeros((h,), jnp.int64),
        bytes_ctrl=jnp.zeros((h,), jnp.int64),
        bytes_data=jnp.zeros((h,), jnp.int64),
        retrans_segs=jnp.zeros((h,), jnp.int64),
        queue_hwm=jnp.zeros((h,), jnp.int32),
        outbox_hwm=jnp.zeros((h,), jnp.int32),
        rounds_idle=jnp.asarray(0, jnp.int64),
        exch_hwm=jnp.zeros((h,), jnp.int32),
        land_hwm=jnp.zeros((h,), jnp.int32),
        land_passes=jnp.zeros((h,), jnp.int32),
        flush_cols=jnp.zeros((h,), jnp.int32),
    )


@flax.struct.dataclass
class SimState:
    now: jax.Array  # scalar i64: start of the current window
    min_used_lat: jax.Array  # scalar i64: min path latency used so far
    queue: EventQueue
    outbox: Outbox
    seq: jax.Array  # [H] u32 per-host event-id counter (tie-key source)
    rng_key: jax.Array  # [H] per-host base keys
    rng_counter: jax.Array  # [H] u32 per-host draw counter
    host_id: jax.Array  # [H] i32 *global* host id of each row (shard-aware)
    net: NetDevState  # per-host relays + AQM (netstack.py)
    model: Any  # model-specific pytree, host-axis leading
    # stats (per host)
    events_handled: jax.Array  # [H] i64
    packets_sent: jax.Array  # [H] i64
    packets_dropped: jax.Array  # [H] i64  (path packet_loss)
    packets_unroutable: jax.Array  # [H] i64  (no path; reference errors hard)
    # diagnostic: pop-iterations executed, accumulated on each shard's row 0
    # (sum over the axis = total device iterations; feeds the perf probes)
    iters_done: jax.Array  # [H] i32
    # diagnostic: per-host count of drain iterations in which this host had
    # an eligible event (next_time < window_end) — the live-lane occupancy
    # numerator (occupancy = sum(lanes_live) / (iters * H)). Like
    # iters_done it depends on the engine's iteration structure (the pump
    # drains chains in fewer iterations), so engine-equivalence tests
    # exclude it alongside iters_done.
    lanes_live: jax.Array  # [H] i64
    # diagnostic: total simulated width of all live windows drained so far
    # (sum of window_end - start per live round). Mesh-uniform by
    # construction (the window agreement is pmin'd), so the scalar stays
    # replicated sharded; mean window width = win_ns_sum / rounds_live.
    win_ns_sum: jax.Array  # scalar i64
    # diagnostic: rounds that ran a drain loop (run_rounds_scan's live
    # branch), counted beside win_ns_sum whether the tracker is on or off;
    # every shard runs the same round sequence, so it stays replicated too
    rounds_live: jax.Array  # scalar i64
    # the tracker plane (zeros unless EngineConfig.tracker is set)
    tracker: TrackerState

    @property
    def num_hosts(self) -> int:
        return self.seq.shape[0]

    def donatable(self) -> "SimState":
        """A fresh private copy whose buffers a driver may donate into a
        jitted chunk (`donate_argnums`), aliasing the O(hosts x queue_cap)
        HBM state in-place instead of copying it every chunk.

        Donation invalidates the donated buffers at dispatch: any stale
        reuse of a donated state raises jax's "Array has been deleted"
        RuntimeError instead of silently reading aliased memory — that is
        the no-stale-reference assertion drivers rely on. Copying here
        (jnp.copy preserves sharding) is what keeps the CALLER's SimState
        valid: drivers call donatable() once on entry and donate only the
        private copy, so run_until(st, ...) never destroys `st`. Note
        device_put with an unchanged sharding returns the same aliased
        buffers, which is why this must be a real copy."""
        return jax.tree.map(jnp.copy, self)


@flax.struct.dataclass
class LocalEmits:
    """Up to EL local (task/timer) events per host from one handler call."""

    valid: jax.Array  # [H, EL] bool
    time: jax.Array  # [H, EL] i64 absolute fire time
    kind: jax.Array  # [H, EL] i32
    data: jax.Array  # [H, EL, PAYLOAD_LANES] i32


@flax.struct.dataclass
class PacketEmits:
    """Up to EP packets per host from one handler call."""

    valid: jax.Array  # [H, EP] bool
    dst: jax.Array  # [H, EP] i32 destination host id
    data: jax.Array  # [H, EP, PAYLOAD_LANES] i32
    size: jax.Array  # [H, EP] i32 bytes on the wire (feeds the relays)


def empty_local_emits(h: int, el: int) -> LocalEmits:
    return LocalEmits(
        valid=jnp.zeros((h, el), bool),
        time=jnp.zeros((h, el), jnp.int64),
        kind=jnp.zeros((h, el), jnp.int32),
        data=jnp.zeros((h, el, PAYLOAD_LANES), jnp.int32),
    )


def empty_packet_emits(h: int, ep: int) -> PacketEmits:
    return PacketEmits(
        valid=jnp.zeros((h, ep), bool),
        dst=jnp.zeros((h, ep), jnp.int32),
        data=jnp.zeros((h, ep, PAYLOAD_LANES), jnp.int32),
        size=jnp.zeros((h, ep), jnp.int32),
    )


def _is_key_leaf(leaf) -> bool:
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key)


def state_to_host(st: SimState) -> SimState:
    """ONE bulk device_get of the full state, with typed PRNG key leaves
    unwrapped to their raw uint32 words (numpy cannot represent extended
    dtypes). This is the host-side snapshot format shared by on-disk
    checkpoints (runtime/checkpoint.py) and the rollback-and-regrow
    retention (runtime/recovery.py): a plain-numpy pytree that stays
    valid no matter how many times the device buffers are donated
    afterwards. Invert with state_from_host.

    The "stays valid" clause needs an explicit copy of any leaf that is
    a zero-copy VIEW of a device buffer: on the CPU backend device_get
    can alias the buffer directly, and an executable reloaded through
    jax.experimental.serialize_executable reuses donated input buffers
    for its outputs — so without the copy, the pipelined driver's next
    chunk launch would rewrite a pending checkpoint snapshot under the
    writer (caught by the daemon's sha-256 digests as a corrupt file)."""

    def _owned(a):
        a = np.asarray(a)
        return a if a.flags.owndata else a.copy()

    return jax.tree.map(
        _owned,
        jax.device_get(
            jax.tree.map(
                lambda l: jax.random.key_data(l) if _is_key_leaf(l) else l, st
            )
        ),
    )


def state_from_host(host_st: SimState, like: SimState) -> SimState:
    """Rebuild a device SimState from a state_to_host snapshot. `like`
    supplies the leaf dtypes and marks which leaves are typed PRNG keys
    (their raw words are re-wrapped with the template's key impl); every
    leaf shape must match the template exactly — a shape drift means the
    snapshot belongs to a different world/config."""

    def rewrap(h, t):
        if _is_key_leaf(t):
            return jax.random.wrap_key_data(
                jnp.asarray(h), impl=jax.random.key_impl(t)
            )
        a = jnp.asarray(h, dtype=t.dtype)
        if a.shape != t.shape:
            raise ValueError(
                f"snapshot leaf shape {a.shape} != template {t.shape}; "
                "the snapshot was taken for a different world/config"
            )
        return a

    return jax.tree.map(rewrap, host_st, like)


def leaf_nbytes(leaf) -> int:
    """Device bytes of one pytree leaf: concrete arrays, numpy host
    snapshots, and jax.eval_shape ShapeDtypeStructs all price identically
    (the memory observatory's `shadow-tpu mem` prices abstract shapes so
    it never has to allocate). Typed PRNG key leaves are priced as their
    raw key words — the buffer that actually sits in HBM."""
    if _is_key_leaf(leaf):
        kd = jax.eval_shape(jax.random.key_data, leaf)
        return int(np.prod(kd.shape, dtype=np.int64)) * kd.dtype.itemsize
    nb = getattr(leaf, "nbytes", None)
    if nb is not None:
        return int(nb)
    return int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


def tree_nbytes(tree) -> int:
    """Sum of leaf_nbytes over a pytree — the exact device footprint of a
    SimState (or any sub-tree of one)."""
    return sum(leaf_nbytes(leaf) for leaf in jax.tree.leaves(tree))


def buffer_nbytes(sub, base_ndim: int, scale: float = 1.0) -> int:
    """Priced bytes of a capacity-indexed buffer sub-tree (queue/outbox).
    Leaves with more axes than `base_ndim` (the rank of the per-host
    counters, e.g. queue.count) carry the capacity axis and scale
    linearly with it, so scale=new/old projects a regrow WITHOUT
    allocating — the headroom check rollback-and-regrow runs before
    doubling a saturated buffer."""
    total = 0
    for leaf in jax.tree.leaves(sub):
        b = leaf_nbytes(leaf)
        if scale != 1.0 and len(leaf.shape) > base_ndim:
            b = int(b * scale)
        total += b
    return int(total)


def fmt_bytes(n: "int | float") -> str:
    """Human-readable bytes for error messages and the mem table."""
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.0f} {unit}" if unit == "B" else f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} GiB"


def grow_state(
    st: SimState,
    queue_capacity: "int | None" = None,
    outbox_capacity: "int | None" = None,
) -> SimState:
    """Widen the fixed-slot buffers of a state in place of a fresh init:
    existing slots keep their contents (including tombstone garbage —
    identical garbage on matched trajectories, so leaf-exactness survives),
    new slots get the canonical empty fill values of equeue.create /
    _empty_outbox. Growing is trajectory-neutral for a state that never
    overflowed: a run continued from the grown state is leaf-exact to one
    that started with the larger capacity (tests/test_robustness.py), which
    is what makes rollback-and-regrow recovery deterministic. Shrinking is
    refused — it could drop live slots."""
    from shadow_tpu.events import KIND_INVALID

    def pad(a, extra, fill, dtype, axis=1):
        shape = a.shape[:axis] + (extra,) + a.shape[axis + 1:]
        return jnp.concatenate([a, jnp.full(shape, fill, dtype)], axis=axis)

    q = st.queue
    if queue_capacity is not None and queue_capacity != q.capacity:
        if queue_capacity < q.capacity:
            raise ValueError("grow_state cannot shrink queue_capacity")
        extra = queue_capacity - q.capacity
        q = q.replace(
            time=pad(q.time, extra, TIME_MAX, jnp.int64),
            tie=pad(q.tie, extra, jnp.iinfo(jnp.int64).max, jnp.int64),
            kind=pad(q.kind, extra, KIND_INVALID, jnp.int32),
            data=pad(q.data, extra, 0, jnp.int32),
            aux=pad(q.aux, extra, 0, jnp.int32),
        )
    ob = st.outbox
    o_cap = ob.valid.shape[1]
    if outbox_capacity is not None and outbox_capacity != o_cap:
        if outbox_capacity < o_cap:
            raise ValueError("grow_state cannot shrink outbox_capacity")
        extra = outbox_capacity - o_cap
        ob = ob.replace(
            valid=pad(ob.valid, extra, False, bool),
            dst=pad(ob.dst, extra, 0, jnp.int32),
            time=pad(ob.time, extra, TIME_MAX, jnp.int64),
            tie=pad(ob.tie, extra, 0, jnp.int64),
            data=pad(ob.data, extra, 0, jnp.int32, axis=2),  # [H, 8, O]
            aux=pad(ob.aux, extra, 0, jnp.int32),
        )
    return st.replace(queue=q, outbox=ob)


def init_state(
    cfg: EngineConfig,
    model_state,
    tx_bytes_per_interval=None,
    rx_bytes_per_interval=None,
) -> SimState:
    """Build the (global) initial state. The host->graph-node map lives on
    RoutingTables (see RoutingTables.with_hosts), not here, because it must
    stay replicated when the state is sharded over hosts. Bandwidths are
    per-host bucket refills in bytes per refill interval (netstack.py);
    None/0 = unshaped."""
    h = cfg.num_hosts
    return SimState(
        now=jnp.asarray(0, jnp.int64),
        min_used_lat=jnp.asarray(TIME_MAX, jnp.int64),
        queue=equeue.create(h, cfg.queue_capacity),
        outbox=_empty_outbox(h, cfg.outbox_capacity),
        seq=jnp.zeros((h,), jnp.uint32),
        rng_key=rng.host_keys(cfg.seed, h),
        rng_counter=jnp.zeros((h,), jnp.uint32),
        host_id=jnp.arange(h, dtype=jnp.int32),
        net=netstack.create(h, tx_bytes_per_interval, rx_bytes_per_interval),
        model=model_state,
        events_handled=jnp.zeros((h,), jnp.int64),
        packets_sent=jnp.zeros((h,), jnp.int64),
        packets_dropped=jnp.zeros((h,), jnp.int64),
        packets_unroutable=jnp.zeros((h,), jnp.int64),
        iters_done=jnp.zeros((h,), jnp.int32),
        lanes_live=jnp.zeros((h,), jnp.int64),
        win_ns_sum=jnp.asarray(0, jnp.int64),
        rounds_live=jnp.asarray(0, jnp.int64),
        tracker=_empty_tracker(h),
    )
