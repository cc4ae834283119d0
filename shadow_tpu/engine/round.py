"""The conservative-PDES round engine, jitted end to end.

This is the TPU lift of the reference's scheduling loop (reference:
src/main/core/manager.rs:392-478 + src/main/host/host.rs:697-752): each round
is a window [start, start + runahead) in which every host drains its own
event queue independently (lookahead guarantees no cross-host effect lands
inside the window), cross-host packets stage into per-host outboxes with
delivery clamped to >= round end (worker.rs:399-402), and one batched
exchange at the round boundary replaces the reference's mutex push into the
destination's queue (worker.rs:619-629).

Inside a round the engine iterates: every host with an eligible event pops
its minimum-key event simultaneously; handlers are vectorized over hosts.
The iteration count is the max events any single host handles this round —
hosts are rows, the event loop is data-parallel, and the whole thing traces
into a single XLA while loop (no host<->device sync until the caller asks).

With `axis_name` set, the same code runs under shard_map with hosts block-
sharded across devices: the window min becomes a pmin over ICI and the
boundary exchange an all_gather (all-to-all refinement is a later round's
optimization).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from shadow_tpu import equeue, netstack, rng, scopes
from shadow_tpu.engine.state import EngineConfig, Outbox, SimState, trace_static_cfg
from shadow_tpu.events import KIND_PACKET, pack_tie
from shadow_tpu.graph.routing import RoutingTables, node_of, route_lookup
from shadow_tpu.netstack import AUX_SHAPED_BIT, AUX_SIZE_MASK
from shadow_tpu.simtime import TIME_MAX


@dataclasses.dataclass(frozen=True)
class Draw:
    """Per-host counter-based draw access for one handler invocation.

    Logical draw i of this event = threefry(host_key, counter + i). The
    engine advances counters by the fixed per-event stride afterwards, so
    draws are in event-execution order per host, like the reference's
    per-host RNG (host.rs:218).
    """

    key: jax.Array  # [H]
    counter: jax.Array  # [H] u32

    def uniform(self, i: int) -> jax.Array:
        return rng.uniform_f32(self.key, self.counter + jnp.uint32(i))

    def uniform_int(self, i: int, lo, hi) -> jax.Array:
        return rng.uniform_int(self.key, self.counter + jnp.uint32(i), lo, hi)

    def exponential_ns(self, i: int, mean_ns) -> jax.Array:
        return rng.exponential_ns(self.key, self.counter + jnp.uint32(i), mean_ns)


def _pmin(x: jax.Array, axis_name) -> jax.Array:
    """lax.pmin for the engine's int64 times. The chip's compiler lowers
    only SUM all-reduces of 64-bit integers ("UNIMPLEMENTED: Supported
    lowering only of Sum all reduce", asked for a described v5e 2x2 —
    tests/test_chip_compile.py), so the values are gathered and reduced
    locally: same result on every shard, and batchable under the mesh
    plane's vmap like the collective it replaces."""
    return jnp.min(jax.lax.all_gather(x, axis_name), axis=0)


def _pmax(x: jax.Array, axis_name) -> jax.Array:
    """lax.pmax for int64 (see _pmin)."""
    return jnp.max(jax.lax.all_gather(x, axis_name), axis=0)


def _lane_seqs(valid: jax.Array, base: jax.Array):
    """Per-lane sequence numbers: base + (# valid lanes before this one).
    Kept in uint32 explicitly (jnp.sum/cumsum promote unsigned ints under
    x64, which would flip the carry dtype between rounds)."""
    ranks = jnp.cumsum(valid.astype(jnp.uint32), axis=1) - valid.astype(jnp.uint32)
    lane = (base[:, None] + ranks).astype(jnp.uint32)
    nxt = (base + jnp.sum(valid.astype(jnp.uint32), axis=1)).astype(jnp.uint32)
    return lane, nxt


def bootstrap(st: SimState, model, cfg: EngineConfig) -> SimState:
    """Push the model's initial events (the analogue of Host::boot +
    add_application scheduling, reference host.rs:374-436)."""
    host_ids = st.host_id
    draw = Draw(st.rng_key, st.rng_counter)
    lemits = model.bootstrap(draw, host_ids)
    lseq, seq_final = _lane_seqs(lemits.valid, st.seq)
    queue = equeue.push_self_lanes(
        st.queue,
        valid=lemits.valid,
        time=lemits.time,
        tie=pack_tie(
            lemits.kind, jnp.broadcast_to(host_ids[:, None], lemits.valid.shape), lseq
        ),
        kind=lemits.kind,
        data=lemits.data,
    )
    return st.replace(
        queue=queue,
        seq=seq_final,
        rng_counter=st.rng_counter + jnp.uint32(model.BOOTSTRAP_DRAWS),
    )


def stage_packets(
    ob: Outbox,
    kept: jax.Array,  # [H, EP] bool
    dst: jax.Array,  # [H, EP] i32
    deliver: jax.Array,  # [H, EP] i64
    tie: jax.Array,  # [H, EP] i64
    data: jax.Array,  # [H, EP, PAYLOAD_LANES] i32
    size: jax.Array,  # [H, EP] i32
) -> Outbox:
    """Append each host's kept packet lanes, in lane order, to its own
    outbox row: lane p lands in slot fill[h], or counts on overflow[h]
    when the row is full. One fused select chain per array over the
    [H, O] grid; the payload's is over [H, 8, O], words on the sublanes
    and slots on the lanes (engine/state.py Outbox)."""
    o_cap = ob.valid.shape[1]
    lane_idx = jnp.arange(o_cap)[None, :]
    fill, overflow = ob.fill, ob.overflow
    obv, obd, obt, obtie, obdata = ob.valid, ob.dst, ob.time, ob.tie, ob.data
    obaux = ob.aux
    for p in range(kept.shape[1]):
        has_room = fill < o_cap
        write = kept[:, p] & has_room
        at = (lane_idx == fill[:, None]) & write[:, None]
        obv = obv | at
        obd = jnp.where(at, dst[:, p][:, None], obd)
        obt = jnp.where(at, deliver[:, p][:, None], obt)
        obtie = jnp.where(at, tie[:, p][:, None], obtie)
        obdata = jnp.where(at[:, None, :], data[:, p, :, None], obdata)
        obaux = jnp.where(at, (size[:, p] & AUX_SIZE_MASK)[:, None], obaux)
        fill = fill + write.astype(jnp.int32)
        overflow = overflow + (kept[:, p] & ~has_room).astype(jnp.int32)
    return ob.replace(
        valid=obv, dst=obd, time=obt, tie=obtie, data=obdata, aux=obaux,
        fill=fill, overflow=overflow,
    )


def handle_one_iteration(
    st: SimState,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
) -> SimState:
    """Pop + handle one event per eligible host; stage emissions.

    Works on local (per-shard) rows; `st.host_id` carries global ids and
    `tables.host_node` is the replicated global host->node map, so packet
    destinations are global host ids everywhere.
    """
    host_ids = st.host_id

    want = equeue.next_time(st.queue) < window_end
    ev, q = equeue.pop_min(st.queue, want)
    st = st.replace(queue=q)

    net = st.net
    defer = jnp.zeros_like(ev.valid)
    ready = ev.time
    size_in = jnp.zeros_like(ev.time)
    if cfg.use_netstack:
        with jax.named_scope(scopes.NETSTACK):
            # --- ingress: down-bw relay + CoDel at the upstream router -------
            # (relay/mod.rs:110-230 + router/mod.rs:59-115, reformulated as a
            # closed-form deferred re-enqueue; see netstack.py).
            is_pkt = ev.valid & (ev.kind == KIND_PACKET)
            size_in = (ev.aux & AUX_SIZE_MASK).astype(jnp.int64)
            shaped = (ev.aux & AUX_SHAPED_BIT) != 0
            loopback = ev.src_host == host_ids
            in_bootstrap = ev.time < cfg.bootstrap_end_ns

            # a shaped event is the deferred dequeue completing: drain backlog
            finish = is_pkt & shaped
            net = net.replace(
                rx_backlog_bytes=net.rx_backlog_bytes - jnp.where(finish, size_in, 0)
            )

            need = is_pkt & ~shaped & ~loopback & ~in_bootstrap & (net.rx_refill > 0)
            ready, rx_tok, rx_last = netstack.tb_depart(
                net.rx_tokens, net.rx_last, net.rx_refill, ev.time, size_in, need
            )
            sojourn = ready - ev.time
            codel_drop, net = netstack.codel_dequeue(net, ready, sojourn, need)
            keep_in = need & ~codel_drop
            # tokens are only consumed by packets that actually pass the relay
            net = net.replace(
                rx_tokens=jnp.where(keep_in, rx_tok, net.rx_tokens),
                rx_last=jnp.where(keep_in, rx_last, net.rx_last),
                codel_dropped=net.codel_dropped + codel_drop,
            )
            defer = keep_in & (ready > ev.time)
            net = net.replace(
                rx_backlog_bytes=net.rx_backlog_bytes + jnp.where(defer, size_in, 0)
            )
            if hasattr(model, "on_codel_drop"):
                st = st.replace(model=model.on_codel_drop(st.model, ev, codel_drop))
            ev = ev.replace(valid=ev.valid & ~(defer | codel_drop))
            net = net.replace(
                bytes_recv=net.bytes_recv
                + jnp.where(ev.valid & is_pkt, size_in, 0)
            )

    draw = Draw(st.rng_key, st.rng_counter)
    model_before = st.model  # pre-handler snapshot (tracker retrans delta)
    mstate, lemits, pemits = model.handle(st.model, ev, draw, cfg, host_ids)

    lvalid = lemits.valid & ev.valid[:, None]  # [H, EL]
    pvalid = pemits.valid & ev.valid[:, None]  # [H, EP]
    ep = pvalid.shape[1]

    # --- packet path: routing lookup, loss draw, delivery clamp ---
    dst_clamped = jnp.clip(pemits.dst, 0, tables.num_global_hosts - 1)
    _, lat, rel = route_lookup(  # [H, EP] i64, f32
        tables, node_of(tables, host_ids), dst_clamped
    )

    unroutable = pvalid & (lat >= TIME_MAX)
    loss_lane = getattr(model, "LOSS_COUNTER_LANE", None)
    if loss_lane is None:
        # one loss draw per packet lane, drawn in lane order; batched into
        # a single threefry call (identical per-counter values)
        ctrs = (
            draw.counter[:, None]
            + jnp.uint32(model.DRAWS_PER_EVENT)
            + jnp.arange(ep, dtype=jnp.uint32)[None, :]
        )
        loss_u = rng.uniform_f32_grid(draw.key, ctrs)  # [H, EP]
    else:
        # hybrid managed traffic: the loss counter was allocated from the
        # host's stream at send time on the CPU and rides the payload, so
        # the uniform is bit-identical to the serial kernel's _loss_draw
        # no matter when the event pops here
        loss_u = rng.uniform_f32_grid(
            st.rng_key, pemits.data[:, :, loss_lane].astype(jnp.uint32)
        )
    kept = pvalid & ~unroutable & (loss_u < rel)
    dropped = pvalid & ~unroutable & ~(loss_u < rel)

    if cfg.use_netstack:
        with jax.named_scope(scopes.NETSTACK):
            # --- egress: up-bw relay charged in lane order at emit time ------
            # (the loss draw happens downstream of the relay in the reference,
            # worker.rs:361-378, so loss-dropped packets still consume tokens;
            # loopback and bootstrap-period packets are exempt,
            # relay/mod.rs:144-230.)
            sizes = pemits.size.astype(jnp.int64)
            in_bootstrap_tx = ev.time < cfg.bootstrap_end_ns
            tx_tok, tx_last = net.tx_tokens, net.tx_last
            deps = []
            for p in range(ep):
                loopb = dst_clamped[:, p] == host_ids
                charge = (pvalid[:, p] & ~unroutable[:, p]) & ~loopb & ~in_bootstrap_tx
                dep_p, tx_tok, tx_last = netstack.tb_depart(
                    tx_tok, tx_last, net.tx_refill, ev.time, sizes[:, p], charge
                )
                deps.append(dep_p)
            dep = jnp.stack(deps, axis=1)  # [H, EP]
            net = net.replace(
                tx_tokens=tx_tok,
                tx_last=tx_last,
                bytes_sent=net.bytes_sent + jnp.sum(jnp.where(kept, sizes, 0), axis=1),
            )
            deliver = jnp.maximum(dep + lat, window_end)  # [H, EP]
    else:
        deliver = jnp.maximum(ev.time[:, None] + lat, window_end)  # [H, EP]

    if hasattr(model, "on_packet_outcomes"):
        mstate = model.on_packet_outcomes(
            mstate, ev, pemits, kept, dropped, unroutable, deliver, dst_clamped
        )

    # --- sequence numbers: local lanes first, then surviving packets ---
    lseq, seq_after_locals = _lane_seqs(lvalid, st.seq)
    pseq, seq_final = _lane_seqs(kept, seq_after_locals)

    # --- push local events into own queues (row-wise, conflict-free) ---
    # One batched multi-lane push: the relay-deferred re-enqueue (same tie,
    # ordering at `ready` still follows the original total-order key) rides
    # as lane 0, the model's local lanes follow in lane order — identical
    # slot assignment to sequential push_self calls, one fused pass.
    el = lvalid.shape[1]
    lane_tie = pack_tie(lemits.kind, jnp.broadcast_to(host_ids[:, None], lvalid.shape), lseq)
    if cfg.use_netstack:
        p_valid = jnp.concatenate([defer[:, None], lvalid], axis=1)
        p_time = jnp.concatenate([ready[:, None], lemits.time], axis=1)
        p_tie = jnp.concatenate([ev.tie[:, None], lane_tie], axis=1)
        p_kind = jnp.concatenate([ev.kind[:, None], lemits.kind], axis=1)
        p_data = jnp.concatenate([ev.data[:, None, :], lemits.data], axis=1)
        p_aux = jnp.concatenate(
            [(size_in.astype(jnp.int32) | jnp.int32(AUX_SHAPED_BIT))[:, None],
             jnp.zeros((host_ids.shape[0], el), jnp.int32)],
            axis=1,
        )
    else:
        p_valid, p_time, p_tie = lvalid, lemits.time, lane_tie
        p_kind, p_data = lemits.kind, lemits.data
        p_aux = jnp.zeros((host_ids.shape[0], el), jnp.int32)
    with jax.named_scope(scopes.PUSH_SELF):
        queue = equeue.push_self_lanes(
            st.queue, valid=p_valid, time=p_time, tie=p_tie, kind=p_kind,
            data=p_data, aux=p_aux,
        )

    # --- stage surviving packets into own outbox rows ---
    with jax.named_scope(scopes.STAGE):
        pkt_kind = jnp.full(kept.shape, KIND_PACKET, jnp.int32)
        ptie = pack_tie(pkt_kind, jnp.broadcast_to(host_ids[:, None], kept.shape), pseq)
        ob = stage_packets(
            st.outbox, kept, dst_clamped, deliver, ptie, pemits.data, pemits.size
        )

    min_used = st.min_used_lat
    if cfg.use_dynamic_runahead:
        # self-destined packets never cross hosts, so their (often tiny)
        # self-edge latency must not collapse the window
        cross = dst_clamped != host_ids[:, None]
        used = jnp.where(kept & cross & (lat < TIME_MAX), lat, TIME_MAX)
        min_used = jnp.minimum(min_used, jnp.min(used))

    # --- tracker plane (cfg.tracker static: OFF emits no ops) ---------
    # Per-kind event counts classify the POPPED event's kind (identical
    # in every engine); byte classes split kept emissions by wire size
    # vs the model's header size; retrans counts the per-event delta of
    # the flow table's retransmits counter — the pump adds the exact
    # same per-event count, so plain/pump tracker leaves are leaf-exact
    # identical (tests/test_tracker.py).
    tracker = st.tracker
    if cfg.tracker:
        # kind integers are only unique within a model (events.py), so
        # the protocol-kind range is model-owned: TCP models export
        # TCP_KIND_RANGE = (KIND_TCP_TIMER, TCP_KIND_USER_BASE)
        tcp_range = getattr(model, "TCP_KIND_RANGE", None)
        if tcp_range is not None:
            lo, hi = (int(x) for x in tcp_range)
            is_tcp_ev = ev.valid & (ev.kind >= lo) & (ev.kind < hi)
        else:
            is_tcp_ev = jnp.zeros_like(ev.valid)
        is_local_ev = ev.valid & (ev.kind != KIND_PACKET) & ~is_tcp_ev
        hdr = int(getattr(model, "WIRE_HEADER_BYTES", 0))
        sizes64 = pemits.size.astype(jnp.int64)
        is_ctrl = kept & (pemits.size <= hdr)
        spec = getattr(model, "pump_spec", None)
        if spec is not None:
            rtx_delta = jnp.sum(
                spec.get_tcp(mstate).retransmits
                - spec.get_tcp(model_before).retransmits,
                axis=1,
            )
        else:
            rtx_delta = jnp.zeros_like(tracker.retrans_segs)
        tracker = tracker.replace(
            ev_local=tracker.ev_local + is_local_ev,
            ev_tcp=tracker.ev_tcp + is_tcp_ev,
            bytes_ctrl=tracker.bytes_ctrl
            + jnp.sum(jnp.where(is_ctrl, sizes64, 0), axis=1),
            bytes_data=tracker.bytes_data
            + jnp.sum(jnp.where(kept & ~is_ctrl, sizes64, 0), axis=1),
            retrans_segs=tracker.retrans_segs + rtx_delta,
        )

    # carried-counter models consume no live draws for packet loss
    stride = jnp.uint32(model.DRAWS_PER_EVENT + (0 if loss_lane is not None else ep))
    return st.replace(
        queue=queue,
        min_used_lat=min_used,
        outbox=ob,
        net=net,
        model=mstate,
        seq=seq_final,
        rng_counter=st.rng_counter + stride * ev.valid.astype(jnp.uint32),
        events_handled=st.events_handled + ev.valid,
        packets_sent=st.packets_sent + jnp.sum(kept, axis=1),
        packets_dropped=st.packets_dropped + jnp.sum(dropped, axis=1),
        packets_unroutable=st.packets_unroutable + jnp.sum(unroutable, axis=1),
        tracker=tracker,
    )


def _compact_rows(st: SimState, window_end: jax.Array, lanes: int):
    """The device-side live-lane permutation: lane i -> the i-th host
    whose next event is inside the window (O(H) cumsum + scatter).
    Returns (rows_c, rows, live): `rows_c` indexes the gather (sentinel
    lanes point at row H-1), `live` marks real lanes, `rows` carries the
    un-clamped targets for the scatter-back."""
    h = st.seq.shape[0]
    elig = equeue.next_time(st.queue) < window_end  # [H]
    pos = jnp.where(elig, jnp.cumsum(elig.astype(jnp.int32)) - 1, lanes)
    rows = (
        jnp.full((lanes,), h, jnp.int32)
        .at[pos]
        .set(jnp.arange(h, dtype=jnp.int32), mode="drop")
    )
    live = rows < h
    return jnp.minimum(rows, h - 1), rows, live


def compact_step(
    st: SimState, window_end: jax.Array, lanes: int, body
) -> SimState:
    """Active-set compaction around one drain-iteration body.

    At scale most hosts are idle in any given pop-iteration (long app
    pauses, shaping backlogs concentrated on few hosts), yet a
    full-width iteration pays O(H) work regardless. Here we compact: find
    the <= `lanes` hosts whose next event is inside the window
    (_compact_rows), gather their rows of the *entire* SimState into a
    [lanes]-row sub-state, run the unchanged `body` (the plain handler,
    or the pump stage followed by the handler) there, and scatter the
    rows back — so the pump microscan covers only occupied lanes
    instead of paying full-[H] microsteps when a handful of hosts are
    active.

    Correctness: hosts are independent within a conservative window (the
    PDES invariant — packets land next round, local emits stay on-row),
    and every op in the bodies is row-local, so handling any subset per
    iteration yields bit-identical per-host sequences; eligible hosts
    beyond `lanes` are simply handled on a later iteration of the same
    round. Sentinel lanes (when fewer than `lanes` hosts are active)
    gather row H-1 but are neutralized by forcing their head_time to
    TIME_MAX (both bodies are identity on rows with no popped event) and
    their write-back is dropped.
    """
    h = st.seq.shape[0]
    rows_c, rows, live = _compact_rows(st, window_end, lanes)

    def take(a):
        return a if jnp.ndim(a) == 0 else a[rows_c]

    sub = jax.tree.map(take, st)
    sub = sub.replace(
        queue=sub.queue.replace(
            head_time=jnp.where(live, sub.queue.head_time, TIME_MAX)
        )
    )
    sub = body(sub)

    back = jnp.where(live, rows, h)  # sentinel writes dropped

    def put(full, g):
        if jnp.ndim(full) == 0:
            return g  # scalars (min_used_lat) already fold the old value in
        return full.at[back].set(g, mode="drop")

    return jax.tree.map(put, st, sub)


def handle_one_iteration_compact(
    st: SimState,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    lanes: int,
) -> SimState:
    """compact_step around the plain handler (kept as the named seam the
    docs/config reference; run_round compacts the whole stage+handler
    body through compact_step directly)."""
    return compact_step(
        st,
        window_end,
        lanes,
        lambda s: handle_one_iteration(s, window_end, model, tables, cfg),
    )


def model_pump_capable(model) -> bool:
    """Whether the pump fast path can honor this model: it
    must publish a pump_spec and use none of the hooks the microscan
    cannot replay (loss counters, packet-outcome / codel-drop callbacks).
    Models failing this always take the plain handler — bit-identical on
    every engine value — so run_round's engine selection AND the drivers'
    reported engine (runtime/scheduler.py) share this predicate."""
    return (
        getattr(model, "pump_spec", None) is not None
        and getattr(model, "LOSS_COUNTER_LANE", None) is None
        and not hasattr(model, "on_packet_outcomes")
        and not hasattr(model, "on_codel_drop")
    )


def flush_block(o_cap: int) -> int:
    """The slot columns a flush hands the landing at a time: an eighth of
    the outbox's capacity (the largest divisor of it that is no more, none
    below 1). Static, and a function of the capacity alone: how MANY
    blocks a round's flush takes is read from the state (flush_outbox)."""
    c = max(1, o_cap // 8)
    while o_cap % c:
        c -= 1
    return c


def _staged_width(st: SimState, axis_name: Optional[str]) -> jax.Array:
    """The busiest outbox row's fill (scalar i32), mesh-uniform (pmax):
    every shard must make the same number of passes over its outbox,
    because the collectives inside are entered by all or none.
    stage_packets and the pump write lane p into slot fill[h] and then
    raise fill[h], the flush clears valid, time and fill together and
    grow_state pads on the right, so valid[h, o] == (o < fill[h]) always:
    the first max_h fill[h] columns hold everything a round staged."""
    w = jnp.max(st.outbox.fill)
    if axis_name is not None:
        w = jax.lax.pmax(w, axis_name)
    return w


def _has_traffic(st: SimState, axis_name: Optional[str]) -> jax.Array:
    """Mesh-uniform "any packet staged in an outbox". Shared by
    flush_outbox's skip-cond and run_rounds_scan's quiescence gate — the
    two MUST agree, or the early-exit idle branch could skip a flush that
    would have delivered traffic."""
    return _staged_width(st, axis_name) > 0


def flush_outbox(
    st: SimState, axis_name: Optional[str], cfg: "EngineConfig | None" = None
) -> SimState:
    """Round-boundary exchange: deliver staged packets into destination queues.

    Sharded, this is the cross-chip step (the analogue of the locked
    cross-host EventQueue push, worker.rs:619-629), with two modes:

      * all_to_all (default): bucket outbox entries
        by destination shard, exchange only each peer's bucket over ICI
        — per-shard traffic is O(devices x bucket) instead of
        O(devices x whole outbox). Bucket capacity is static (XLA
        shapes); overflow is counted and fails loudly via
        check_capacity, like every other fixed-slot resource.
      * all_gather: every shard receives every shard's staged columns
        and filters its own rows (simple, never overflows, more traffic).

    In both modes the destination pops by the (time, tie) key, so
    delivery slot order — which differs between the modes — cannot
    affect results.

    What a flush flattens, buckets, exchanges, sorts, counts and packs is
    the staged COLUMNS, not the outbox's capacity: a row's staged entries
    are its first fill[h] slots, so a lax.while_loop hands the landing's
    sort (equeue.land_sort) one block of flush_block(O) slot columns at a
    time, for as many blocks as hold the busiest row's fill, and ONE pull
    (equeue.land_pull) lands what the blocks grouped: a destination's
    arrivals are block 0's, then block 1's, so every leaf is what one
    sort of the whole outbox gives, bit for bit. One loop body whatever
    the width (a lax.switch over static widths held a copy of the
    landing's code a width); one row staging O entries puts its round on
    all the blocks. Under cfg.ensemble the block count is batched and a
    batched loop would carry every replica's buffers through selects, so
    that trace keeps ONE block, the whole outbox. Every flush books how
    it engaged (TrackerState.land_hwm / land_passes / flush_cols),
    cfg.tracker or not.
    """
    o_cap = st.outbox.valid.shape[1]
    one_block = cfg is not None and cfg.ensemble
    block = o_cap if one_block else flush_block(o_cap)
    # Empty rounds skip the exchange entirely (lax.cond on the busiest
    # row's fill). Sharded, the fill is made mesh-uniform with a pmax,
    # because the all_to_all/all_gather inside must be entered by every
    # shard or none, as often.
    with jax.named_scope(scopes.EXCHANGE):
        blocks = (_staged_width(st, axis_name) + (block - 1)) // block

        def _skip(st):
            return st, jnp.zeros((), jnp.int32)

        def _do_flush(st):
            return _flush_outbox_traffic(st, axis_name, cfg, block, blocks)

        if not isinstance(blocks, jax.core.Tracer):
            # eager path (round_body_debug/tests): concrete count — an
            # eager lax.cond over this state is pathological for the tracer
            st, max_land = _do_flush(st) if int(blocks) else _skip(st)
        else:
            st, max_land = jax.lax.cond(blocks > 0, _do_flush, _skip, st)
    # how the flush and the landing's loop engaged (row 0, like exch_hwm):
    # the columns this flush flattened (0 for a skipped one), the most
    # arrivals one destination landed in one round — what LAND_LANES is
    # sized from — and the passes made over all landings. Counted in every
    # program (cfg.tracker or not): three one-element updates a flush.
    with jax.named_scope(scopes.PROBE):
        tr = st.tracker
        return st.replace(
            tracker=tr.replace(
                land_hwm=tr.land_hwm.at[0].max(max_land),
                land_passes=tr.land_passes.at[0].add(equeue.land_passes(max_land)),
                flush_cols=tr.flush_cols.at[0].add(blocks * block),
            )
        )


def _flush_outbox_traffic(
    st: SimState,
    axis_name: Optional[str],
    cfg: "EngineConfig | None" = None,
    block: "int | None" = None,
    blocks=None,
) -> "tuple[SimState, jax.Array]":
    """The flush of a round that staged something: the first `blocks`
    blocks of `block` slot columns each (all of the outbox when None),
    which must hold every staged entry: (state, the most arrivals one
    destination landed: land_pull's max_land).

    Slot (h, o) of a block is its flat entry o * H + h, slots major and
    hosts minor: the order the outbox arrays lie in on the chip ([H, O]
    is held hosts on the lanes, slots on the sublanes), so the flatten is
    no transposition and pads nothing. A destination's arrivals keep this
    order (the sorts are stable, blocks land in order), so they take its
    free slots slot-column by slot-column; pop order is key-driven."""
    ob = st.outbox
    h_local, o_cap = ob.valid.shape
    if block is None:
        block, blocks = o_cap, 1
    nb = o_cap // block
    m = h_local * block
    lanes = ob.data.shape[1]
    mode = None
    if axis_name is not None:
        mode = getattr(cfg, "exchange", "all_to_all") if cfg is not None else "all_gather"
        d = jax.lax.axis_size(axis_name)
        base = jax.lax.axis_index(axis_name) * h_local
    if mode == "all_to_all":
        cap = getattr(cfg, "a2a_capacity", 0) or 0
        # safe default: each peer bucket can hold everything a block
        # flattens (PDES traffic is often pair-skewed — e.g. client i ->
        # server i+H/2 lands a shard's entire outbox on one peer), which
        # is also the most a block can send one peer. Tuning a2a_capacity
        # below m is where the ICI traffic saving comes from.
        cap = m if cap <= 0 else min(cap, m)
        n = d * cap
    else:
        n = m if mode is None else d * m

    def one_block(b, carry):
        """Block b's columns, exchanged and grouped by destination into
        segment b of the landing's buffers."""
        orders, words, cnts, begins, n_pushed, overflow_extra = carry

        def flat(x):  # [H, O] -> block b's [block * H], slots major
            return jax.lax.dynamic_slice_in_dim(x, b * block, block, axis=1).T.reshape(m)

        valid, dst, time, tie = flat(ob.valid), flat(ob.dst), flat(ob.time), flat(ob.tie)
        # the landing reads the payload word-major (land_sort's data.T
        # folds with this one); the sharded buckets read it as [M, 8] rows
        data = jax.lax.dynamic_slice_in_dim(ob.data, b * block, block, axis=2)
        data, aux = jnp.transpose(data, (2, 0, 1)).reshape(m, lanes), flat(ob.aux)

        if mode == "all_to_all":
            # bucket by destination shard; stable sort keeps emission order
            # within each bucket (determinism is key-driven anyway)
            with jax.named_scope(scopes.BUCKET):
                pos = jnp.arange(m)
                shard_of = jnp.where(valid, dst // h_local, d).astype(jnp.int32)
                order = jnp.argsort(shard_of, stable=True)
                sh_s = shard_of[order]
                valid_s = valid[order]
                seg_start = jnp.concatenate(
                    [jnp.ones((1,), bool), sh_s[1:] != sh_s[:-1]]
                )
                start_pos = jax.lax.cummax(jnp.where(seg_start, pos, -1))
                rank = (pos - start_pos).astype(jnp.int32)
                fits = valid_s & (rank < cap)
                sdst = jnp.where(fits, sh_s, d)
                sslot = jnp.where(fits, rank, cap)
                overflow_extra += jnp.sum(valid_s & ~fits).astype(jnp.int32)

            def to_peers(x, fill):
                with jax.named_scope(scopes.BUCKET):
                    buf = jnp.full((d, cap) + x.shape[1:], fill, x.dtype)
                    buf = buf.at[sdst, sslot].set(x[order], mode="drop")
                with jax.named_scope(scopes.COLLECTIVE):
                    got = jax.lax.all_to_all(buf, axis_name, 0, 0, tiled=False)
                return got.reshape((n,) + x.shape[1:])

            valid = to_peers(valid, False)
            dst = to_peers(dst, 0)
            time = to_peers(time, TIME_MAX)
            tie = to_peers(tie, 0)
            data = to_peers(data, 0)
            aux = to_peers(aux, 0)
        elif mode == "all_gather":
            with jax.named_scope(scopes.COLLECTIVE):
                valid = jax.lax.all_gather(valid, axis_name, tiled=True)
                dst = jax.lax.all_gather(dst, axis_name, tiled=True)
                time = jax.lax.all_gather(time, axis_name, tiled=True)
                tie = jax.lax.all_gather(tie, axis_name, tiled=True)
                data = jax.lax.all_gather(data, axis_name, tiled=True)
                aux = jax.lax.all_gather(aux, axis_name, tiled=True)

        local_dst = dst if mode is None else dst - base
        mine = valid & (local_dst >= 0) & (local_dst < h_local)
        at = b * n
        with jax.named_scope(scopes.LAND):
            pushed, cnt, begin, order, packed = equeue.land_sort(
                h_local, local_dst, mine, time, tie,
                jnp.full(valid.shape, KIND_PACKET, jnp.int32), data, aux,
            )
            # into segment b of the landing's buffers, each under the scope
            # of the step that made it
            with jax.named_scope(scopes.SORT):
                orders = jax.lax.dynamic_update_slice_in_dim(orders, order + at, at, 0)
            with jax.named_scope(scopes.PACK):
                words = jax.lax.dynamic_update_slice_in_dim(words, packed, at, 1)
        return (
            orders,
            words,
            jax.lax.dynamic_update_index_in_dim(cnts, cnt, b, 0),
            jax.lax.dynamic_update_index_in_dim(begins, begin, b, 0),
            n_pushed + pushed,
            overflow_extra,
        )

    zero = jnp.zeros((), jnp.int32)
    carry = (
        jnp.zeros((nb * n,), jnp.int32), jnp.zeros((14, nb * n), jnp.int32),
        jnp.zeros((nb, h_local), jnp.int32), jnp.zeros((nb, h_local), jnp.int32), zero, zero,
    )
    if nb == 1:
        carry = one_block(0, carry)
    elif isinstance(blocks, jax.core.Tracer):
        _, carry = jax.lax.while_loop(
            lambda c: c[0] < blocks,
            lambda c: (c[0] + 1, one_block(c[0], c[1])),
            (zero, carry),
        )
    else:  # eager (round_body_debug/tests)
        for b in range(int(blocks)):
            carry = one_block(b, carry)
    orders, words, cnts, begins, n_pushed, overflow_extra = carry

    lanes_d = getattr(cfg, "deliver_lanes", 0) if cfg is not None else 0
    with jax.named_scope(scopes.LAND):
        queue, max_land = equeue.land_pull(
            st.queue, n_pushed, cnts, begins, orders, words,
            lanes_d if lanes_d > 0 else st.queue.capacity,
        )

    fresh = ob.replace(
        valid=jnp.zeros_like(ob.valid),
        time=jnp.full_like(ob.time, TIME_MAX),
        fill=jnp.zeros_like(ob.fill),
    )
    if mode == "all_to_all":
        fresh = fresh.replace(overflow=fresh.overflow.at[0].add(overflow_extra))
    return st.replace(queue=queue, outbox=fresh), max_land


def run_round(
    st: SimState,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    axis_name: Optional[str] = None,
) -> SimState:
    """Drain all events < window_end on every host, then exchange packets."""

    lanes = cfg.active_lanes
    h_local = st.seq.shape[0]
    compact = 0 < lanes < h_local
    # max_iters_per_round bounds *work* per round (one full-width pop wave
    # per iteration). A compact iteration handles at most `lanes` hosts, so
    # scale the cap by the wave split factor — otherwise a compact run
    # could truncate a round a full-width run completes.
    max_iters = cfg.max_iters_per_round
    if compact:
        max_iters *= -(-h_local // lanes)

    # Engine selection ("auto" resolved by effective_engine: pump when
    # pump_k > 0, else plain, on every backend). Models without a
    # pump_spec (or with hooks the fast paths can't honor) always take the
    # plain handler, so every engine value is bit-identical on every model.
    # With compaction, the WHOLE iteration body — pump stage plus the
    # rejection-handler pass — runs on the gathered [active_lanes]-row
    # sub-state, so the stage's microsteps cover only occupied lanes.
    use_pump = effective_engine(cfg) == "pump" and model_pump_capable(model)
    if use_pump:
        from shadow_tpu.engine.pump import pump_stage

    def cond(carry):
        s, iters = carry
        return jnp.any(equeue.next_time(s.queue) < window_end) & (
            iters < max_iters
        )

    def _handle(s):
        with jax.named_scope(scopes.HANDLE):
            return handle_one_iteration(s, window_end, model, tables, cfg)

    def _body(s):
        """One iteration over whatever rows `s` holds (full or compacted)."""
        if use_pump:
            with jax.named_scope(scopes.PUMP):
                s, rej = pump_stage(s, window_end, model, tables, cfg)
            # the full handler only runs when some host's head event
            # failed pump classification — pump-only iterations cover the
            # steady packet streams (chains longer than pump_k keep
            # pumping next iteration without a handler pass)
            return jax.lax.cond(rej, _handle, lambda x: x, s)
        return _handle(s)

    def _step(carry):
        s, iters = carry
        # live-lane occupancy diagnostic: hosts eligible this iteration
        elig = equeue.next_time(s.queue) < window_end
        s = s.replace(lanes_live=s.lanes_live + elig)
        if compact:
            s = compact_step(s, window_end, lanes, _body)
        else:
            s = _body(s)
        return s, iters + 1

    if cfg.ensemble:
        # Per-replica done-mask (engine/ensemble.py): under jax.vmap the
        # while_loop condition is any-reduced across the replica batch,
        # so the body keeps running until the SLOWEST replica drains its
        # round. Re-testing the predicate inside the body and taking an
        # identity branch freezes a drained replica's carry — including
        # `iters`, hence iters_done — instead of accumulating no-op
        # iterations, which is what keeps every ensemble slice leaf-exact
        # to its single-replica run. Static flag: unbatched traces keep
        # the bare step (no second predicate on the hottest loop).

        def body(carry):
            return jax.lax.cond(cond(carry), _step, lambda c: c, carry)

    else:
        body = _step

    with jax.named_scope(scopes.DRAIN):
        st, iters = jax.lax.while_loop(
            cond, body, (st, jnp.asarray(0, jnp.int32))
        )
    with jax.named_scope(scopes.PROBE):
        # per-round exchange traffic high-water (row 0, like iters_done):
        # sum of staged events right before the flush — the measured
        # figure that sizes a2a buckets (sharded.auto_a2a_capacity) and
        # the exchange occupancy CapacityError reports. Counted in every
        # program (cfg.tracker or not): one [H] sum a live round.
        tr = st.tracker.replace(
            exch_hwm=st.tracker.exch_hwm.at[0].max(
                jnp.sum(st.outbox.fill).astype(jnp.int32)
            )
        )
        if cfg.tracker:
            # Sample occupancy high-water marks at the two per-round
            # peaks: the outbox right before the flush empties it, and the
            # queue right after the flush delivers the exchanged packets.
            # Sampled per round (not per iteration), identically in every
            # engine. A pass over [H] each: the tracker plane's.
            tr = tr.replace(
                outbox_hwm=jnp.maximum(tr.outbox_hwm, st.outbox.fill),
                queue_hwm=jnp.maximum(tr.queue_hwm, st.queue.count),
            )
        st = st.replace(tracker=tr)
    st = flush_outbox(st, axis_name, cfg)
    if cfg.tracker:
        with jax.named_scope(scopes.PROBE):
            st = st.replace(
                tracker=st.tracker.replace(
                    queue_hwm=jnp.maximum(
                        st.tracker.queue_hwm, st.queue.count
                    )
                )
            )
    with jax.named_scope(scopes.WINDOW):
        return st.replace(
            now=jnp.maximum(st.now, window_end),
            iters_done=st.iters_done.at[0].add(iters),
        )


def _next_window_end(
    st: SimState, end_time, cfg: EngineConfig, axis_name, start=None,
    tables: "RoutingTables | None" = None,
):
    if start is None:
        start = jnp.min(equeue.next_time(st.queue))
        if axis_name is not None:
            start = _pmin(start, axis_name)
    start = jnp.minimum(start, end_time)
    runahead = jnp.asarray(cfg.runahead_ns, jnp.int64)
    if cfg.use_dynamic_runahead:
        # window length = min latency actually used (>= graph min); until a
        # packet has flown, stay at the conservative graph minimum
        used = st.min_used_lat
        if axis_name is not None:
            used = _pmin(used, axis_name)
        runahead = jnp.maximum(
            runahead, jnp.where(used == TIME_MAX, runahead, used)
        )
    floor = jnp.minimum(start + runahead, end_time)
    # Adaptive windows are gated OFF under dynamic runahead: there the
    # delivery clamp max(t + lat, window_end) is load-bearing (deliveries
    # of faster-than-observed paths snap to the round end — that IS the
    # approximation), so widening the window would move those snapped
    # delivery times and silently change trajectories vs prior releases.
    # The leaf-identity proof below covers only the static floor, where
    # the clamp provably never binds.
    adaptive = (
        cfg.adaptive_window
        and not cfg.use_dynamic_runahead
        and tables is not None
        and tables.lookahead_ns is not None
        and tables.host_node is not None
    )
    if not adaptive:
        return floor
    # Adaptive window: the LBTS bound min over hosts of (next event time +
    # the host's node lookahead). Host h cannot make ANY cross- or
    # self-host effect land before next_time[h] + lookahead[h] (every path
    # latency out of its node is >= lookahead), so draining [start, bound)
    # in one round is exactness-preserving: the delivery clamp
    # max(t + lat, window_end) provably never binds, which is what makes
    # adaptive runs leaf-identical to fixed-width runs — empty hosts
    # (next_time = TIME_MAX) do not constrain the window at all, so sparse
    # worlds drain whole event clusters per round. The fixed width is kept
    # as a floor: runahead_ns <= every per-node lookahead
    # (validate_runahead), so the bound can only widen the window.
    nt = equeue.next_time(st.queue)  # [H] local rows
    la = tables.lookahead_ns[tables.host_node[st.host_id]]  # [H] i64
    bound = nt + jnp.minimum(la, TIME_MAX - nt)  # saturating add
    w = jnp.min(bound)
    if axis_name is not None:
        w = _pmin(w, axis_name)
    return jnp.maximum(floor, jnp.minimum(w, end_time))


def run_rounds_scan(
    st: SimState,
    end_time: jax.Array,
    num_rounds: int,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    axis_name: Optional[str] = None,
) -> SimState:
    """Run a fixed number of rounds fully on device (rounds past the end of
    the simulation, or past the last pending event, are no-ops).

    Quiescence early-exit: once no event remains before `end_time` (and no
    packet is staged in an outbox), the remaining rounds of the scan take a
    no-op `cond` branch — a single window-advance write — instead of paying
    a full drain `while_loop` + flush per round. Bit-exact either way: on a
    quiescent state the drain loop runs zero iterations and flush_outbox's
    own empty-outbox cond returns the state untouched (this predicate's
    `has_traffic` term guarantees the idle branch is only taken when that
    cond would skip), so `run_round` reduces to exactly the idle branch's
    write. tests/test_pipeline.py rerun-stability pins the equivalence.
    Sharded, both predicates are made mesh-uniform (pmin/psum) because the
    live branch contains the exchange collectives."""

    def one(s, _):
        with jax.named_scope(scopes.WINDOW):
            start = jnp.min(equeue.next_time(s.queue))
            if axis_name is not None:
                start = _pmin(start, axis_name)
            has_traffic = _has_traffic(s, axis_name)
            window_end = _next_window_end(
                s, end_time, cfg, axis_name, start=start, tables=tables
            )
            is_live = (start < end_time) | has_traffic

        def live(s):
            with jax.named_scope(scopes.WINDOW):
                width = window_end - jnp.minimum(start, window_end)
                # replicated scalars: every shard runs the same round
                # sequence, so no mesh reduction is needed
                s = s.replace(
                    win_ns_sum=s.win_ns_sum + width,
                    rounds_live=s.rounds_live + 1,
                )
            return run_round(s, window_end, model, tables, cfg, axis_name)

        def idle(s):
            with jax.named_scope(scopes.WINDOW):
                s = s.replace(now=jnp.maximum(s.now, window_end))
            if cfg.tracker:
                # a replicated scalar like rounds_live; the pipelined driver
                # restores it from the probe on the quiescent-extra-chunk
                # path, like `now`
                with jax.named_scope(scopes.PROBE):
                    s = s.replace(
                        tracker=s.tracker.replace(
                            rounds_idle=s.tracker.rounds_idle + 1
                        )
                    )
            return s

        return jax.lax.cond(is_live, live, idle, s), None

    st, _ = jax.lax.scan(one, st, None, length=num_rounds)
    return st


def validate_runahead(cfg: EngineConfig, tables: RoutingTables) -> None:
    """The conservative window must not exceed the minimum possible path
    latency, or cross-host deliveries would be silently delayed by the
    round-end clamp (the reference derives the window from the graph for
    the same reason, runahead.rs:43-56)."""
    min_lat = tables.min_path_latency_ns()
    if cfg.runahead_ns > min_lat:
        raise ValueError(
            f"runahead_ns={cfg.runahead_ns} exceeds the minimum path latency "
            f"{min_lat}ns; use runahead_ns <= graph.min_latency_ns()"
        )


@jax.jit
def _peek_capacity(st: SimState) -> jax.Array:
    """[6] i64: queue overflow, outbox overflow, queue hwm, outbox hwm,
    exchange hwm, landing hwm — the split check_capacity reports so a
    blowup names the saturated counter without a rerun. With
    state_probe's overflow lanes, the only two places that define what
    counts as a dropped slot."""
    return jnp.stack(
        [
            jnp.sum(st.queue.overflow).astype(jnp.int64),
            jnp.sum(st.outbox.overflow).astype(jnp.int64),
            jnp.max(st.tracker.queue_hwm).astype(jnp.int64),
            jnp.max(st.tracker.outbox_hwm).astype(jnp.int64),
            jnp.max(st.tracker.exch_hwm).astype(jnp.int64),
            jnp.max(st.tracker.land_hwm).astype(jnp.int64),
        ]
    )


# --- dispatch probe ----------------------------------------------------
# Everything the host needs to decide whether to keep dispatching chunks,
# packed into ONE small device array so the driver fetches a handful of
# scalars per chunk instead of syncing any [H]-shaped state. Core lanes:
#   next_time  — min pending event time across all hosts (quiescence test)
#   overflow   — queue+outbox slots dropped (capacity check, every chunk)
#   now        — current window start (progress/heartbeats)
#   events_handled / packets_sent — totals (heartbeat/rate lines)
# The remaining lanes are the tracker plane's sync-free aggregates
# (docs/observability.md): the queue/outbox overflow split (capacity
# diagnostics — always live), drop reasons (always live), and the
# TrackerState sums/maxima (zero unless cfg.tracker, but the exchange's
# four counts, lanes 22-25, which every program keeps). Heartbeats read
# these instead of ever fetching [H]-shaped state mid-run.

PROBE_NEXT_TIME = 0
PROBE_OVERFLOW = 1
PROBE_NOW = 2
PROBE_EVENTS = 3
PROBE_PACKETS = 4
PROBE_QUEUE_OV = 5
PROBE_OUTBOX_OV = 6
PROBE_EV_LOCAL = 7
PROBE_EV_TCP = 8
PROBE_DROP_LOSS = 9
PROBE_DROP_CODEL = 10
PROBE_DROP_UNROUTABLE = 11
PROBE_BYTES_CTRL = 12
PROBE_BYTES_DATA = 13
PROBE_RETRANS = 14
PROBE_QUEUE_HWM = 15
PROBE_OUTBOX_HWM = 16
PROBE_ROUNDS_LIVE = 17
PROBE_ROUNDS_IDLE = 18
# adaptivity lanes (always live, like the drop reasons): total drain
# iterations, total eligible-host lanes across iterations (occupancy
# numerator), and the summed simulated width of all live windows; their
# denominator, PROBE_ROUNDS_LIVE above, is always live too (the idle
# rounds beside it are the tracker plane's)
PROBE_ITERS = 19
PROBE_LANES_LIVE = 20
PROBE_WIN_NS = 21
# exchange traffic high-water: most events any shard flushed in one
# round (always live, pmax'd sharded) — feeds measured a2a bucket
# sizing (sharded.auto_a2a_capacity) and the exchange-occupancy figure
# in CapacityError
PROBE_EXCH_HWM = 22
# how the landing's loop engaged (always live; equeue.land_sorted): the
# most arrivals one destination landed in one round (pmax'd sharded) and
# the passes its loop made over all landings (psum'd: a shard's loop runs
# to its own busiest destination)
PROBE_LAND_HWM = 23
PROBE_LAND_PASSES = 24
# the outbox columns the flushes flattened (always live; flush_outbox):
# whole blocks of flush_block columns, as many as hold the busiest row's
# fill, 0 for a skipped flush, summed over the rounds and psum'd over the
# shards (each takes the same, pmax'd count)
PROBE_FLUSH_COLS = 25
PROBE_LANES = 26


def state_probe(st: SimState, axis_name: Optional[str] = None) -> jax.Array:
    """[PROBE_LANES] i64 summary of a chunk's outcome, computed on device
    as part of the chunk itself (no separate peek dispatch; a driver's
    entry computes it once on the state it is handed: entry_probe).
    Sharded, the lanes are reduced over the mesh axis (psum for sums,
    pmin/pmax for extrema) so the probe comes out replicated."""
    tr = st.tracker
    nt = jnp.min(equeue.next_time(st.queue))
    qov = jnp.sum(st.queue.overflow).astype(jnp.int64)
    oov = jnp.sum(st.outbox.overflow).astype(jnp.int64)
    sums = [
        qov + oov,  # PROBE_OVERFLOW: always the sum of the split lanes
        jnp.sum(st.events_handled),
        jnp.sum(st.packets_sent),
        qov,
        oov,
        jnp.sum(tr.ev_local),
        jnp.sum(tr.ev_tcp),
        jnp.sum(st.packets_dropped),
        jnp.sum(st.net.codel_dropped),
        jnp.sum(st.packets_unroutable),
        jnp.sum(tr.bytes_ctrl),
        jnp.sum(tr.bytes_data),
        jnp.sum(tr.retrans_segs),
        jnp.sum(st.iters_done).astype(jnp.int64),
        jnp.sum(st.lanes_live),
        jnp.sum(tr.land_passes).astype(jnp.int64),
        jnp.sum(tr.flush_cols).astype(jnp.int64),
    ]
    maxes = [
        st.now,
        jnp.max(tr.queue_hwm).astype(jnp.int64),
        jnp.max(tr.outbox_hwm).astype(jnp.int64),
        jnp.max(tr.exch_hwm).astype(jnp.int64),
        jnp.max(tr.land_hwm).astype(jnp.int64),
    ]
    # replicated scalars (win_ns_sum is mesh-uniform: pmin'd window math)
    rounds = [st.rounds_live, tr.rounds_idle, st.win_ns_sum]
    if axis_name is not None:
        nt = _pmin(nt, axis_name)
        sums = [jax.lax.psum(x, axis_name) for x in sums]
        maxes = [_pmax(x, axis_name) for x in maxes]
        rounds = [_pmax(x, axis_name) for x in rounds]
    now, qh, oh, xh, lh = maxes
    (ov, ev, pk, qov, oov, evl, evt, dl, dc, du, bc, bd, rx, it, ll, lp, fc) = sums
    rl, ri, wn = rounds
    return jnp.stack(
        [nt, ov, now, ev, pk, qov, oov, evl, evt, dl, dc, du, bc, bd, rx,
         qh, oh, rl, ri, it, ll, wn, xh, lh, lp, fc]
    ).astype(jnp.int64)


@dataclasses.dataclass(frozen=True)
class ChunkProbe:
    """Host-side view of one fetched probe (plain ints). This is what
    `on_chunk` callbacks receive: progress/heartbeat lines read these
    fields instead of forcing a device sync on the full state. Field
    order matches the PROBE_* lane map."""

    next_time: int
    overflow: int
    now: int
    events_handled: int
    packets_sent: int
    queue_overflow: int
    outbox_overflow: int
    ev_local: int
    ev_tcp: int
    drop_loss: int
    drop_codel: int
    drop_unroutable: int
    bytes_ctrl: int
    bytes_data: int
    retrans_segs: int
    queue_hwm: int
    outbox_hwm: int
    rounds_live: int
    rounds_idle: int
    iters: int
    lanes_live: int
    win_ns_sum: int
    # most events any shard flushed in one round (cfg.tracker on or off)
    # — the measured per-round exchange traffic
    exch_hwm: int
    # the landing's loop (cfg.tracker on or off): the most arrivals one
    # destination landed in one round, and the passes made over all
    # landings (equeue.land_passes of each round's mark; like iters it
    # depends on how the hosts are split over chips)
    land_hwm: int
    land_passes: int
    # outbox columns the flushes flattened (cfg.tracker on or off): the
    # width each live round's flush took, summed over rounds and shards;
    # over shards x rounds_live x outbox_capacity it is the share of the
    # outbox's capacity the exchange paid for
    flush_cols: int

    @property
    def ev_packet(self) -> int:
        """Packet events handled (total minus the local/tcp classes)."""
        return self.events_handled - self.ev_local - self.ev_tcp

    @property
    def window_ns_mean(self) -> float:
        """Mean simulated width of the live windows drained so far
        (tracker on or off: both terms are always counted)."""
        return self.win_ns_sum / self.rounds_live if self.rounds_live else 0.0

    def occupancy(self, num_hosts: int, num_shards: int = 1) -> float:
        """Mean fraction of host lanes holding an eligible event per drain
        iteration — the quantity live-host compaction exploits. `iters`
        aggregates per-shard (or per-replica) loop counts while each of
        those iterations scans only num_hosts/num_shards lanes, so a
        sharded probe must pass its shard count or occupancy under-reports
        by exactly that factor."""
        denom = self.iters * (num_hosts // max(num_shards, 1))
        return self.lanes_live / denom if denom else 0.0

    @classmethod
    def from_array(cls, arr) -> "ChunkProbe":
        return cls(*(int(x) for x in arr))


# the probe of a state at rest, as its own tiny program: one launch and one
# fetch of PROBE_LANES words at a driver's entry (a sharded state's lanes
# come out reduced over the whole host axis, as the chunk's psum'd probe
# gives them)
_state_probe_jit = jax.jit(state_probe)


def entry_probe(st: SimState) -> ChunkProbe:
    """The probe of the state a driver entry starts from. Its `next_time`
    lane answers "is there anything before end_time?"; the rest is what
    the entry's chunks' probes are read against (a caller's warm state
    does not start its counters at zero). Kept as `scopes.last_probes`,
    where `_drive` puts the newest chunk's probe beside it."""
    probe = ChunkProbe.from_array(jax.device_get(_state_probe_jit(st)))
    scopes.last_probes = scopes.EntryProbes(
        st.num_hosts, int(st.outbox.valid.size), probe
    )
    return probe


class CapacityError(RuntimeError):
    """Fixed-slot capacity exhausted — user-remediable via config, or
    recoverable in place via rollback-and-regrow (runtime/recovery.py).
    Instances carry the overflow split as attributes so recovery can
    target the saturated buffer without parsing the message:
    queue_overflow / outbox_overflow / queue_hwm / outbox_hwm (ints,
    0 when unknown) and shard_detail (per-shard breakdown string from
    the sharded driver, or None)."""

    queue_overflow: int = 0
    outbox_overflow: int = 0
    queue_hwm: int = 0
    outbox_hwm: int = 0
    # memory observatory: priced bytes of the saturated buffer(s) now and
    # after the x2 regrow rollback-and-regrow would apply (0 when no live
    # state was available to price at raise time)
    bytes_current: int = 0
    bytes_regrown: int = 0
    # exchange-pool occupancy high-water (most events flushed in one
    # round, PROBE_EXCH_HWM) — the figure that says whether an a2a
    # bucket was sized too small
    exchange_hwm: int = 0
    shard_detail: "str | None" = None
    # ensemble runs (engine/ensemble.py): index of the replica whose
    # probe row carried the overflow (None for single-world runs)
    replica: "int | None" = None
    # 2-D mesh runs (engine/mesh.py): host-shard index of the first
    # saturated (replica, shard) cell, with the full per-cell breakdown
    # on mesh_cells (None outside the mesh plane)
    shard: "int | None" = None
    mesh_cells: "list | None" = None


class RunInterrupted(RuntimeError):
    """The run was stopped by SIGINT/SIGTERM (runtime/checkpoint.py
    InterruptGuard): the driver committed a final checkpoint (when one
    could be verified clean) before raising. The partial state is NOT
    returned — resume from the checkpoint instead."""


class WatchdogExpired(RuntimeError):
    """A chunk dispatch (launch + probe fetch) exceeded the configured
    watchdog deadline (experimental.chunk_watchdog_s). The in-flight
    chunk is abandoned; runtime/recovery.py rolls back to the retained
    clean snapshot and re-dispatches, counting it like a recovery in
    sim-stats (docs/robustness.md). Past the recovery budget it
    propagates as a structured failure — never an indefinite hang."""

    def __init__(self, chunk: int, deadline_s: float):
        super().__init__(
            f"chunk {chunk} dispatch exceeded the {deadline_s:.3g}s "
            "watchdog deadline; abandoning the in-flight chunk"
        )
        self.chunk = chunk
        self.deadline_s = deadline_s


class DeviceLossError(RuntimeError):
    """A device (or the runtime under it) failed mid-run: the chunk
    launch or its probe fetch died with an XLA runtime error instead of
    returning. Recoverable by mesh DEGRADATION (docs/robustness.md
    "Device loss"): runtime/recovery.py rolls back to the retained clean
    snapshot, the MeshRunner re-plans the batch onto the surviving
    device set (MeshPlan.degraded — R×S → R×S/2 → 1×S → single device),
    recompiles through the usual seams, and replays leaf-exact — the
    state is layout-free, so losing devices can never change results.
    Outside the mesh plane (nothing to degrade onto) it is terminal but
    structured. `device_id` is the lost device's jax id when known
    (chaos faults name it via target=N); `injected` marks the chaos
    plane's simulated loss."""

    def __init__(self, chunk: int, cause: "BaseException | None" = None,
                 device_id: "int | None" = None):
        detail = f": {cause}" if cause is not None else " (chaos plane)"
        dev = f"device {device_id}" if device_id is not None else "a device"
        super().__init__(
            f"lost {dev} at chunk {chunk}{detail}"
        )
        self.chunk = chunk
        self.device_id = device_id
        self.injected = cause is None


# XLA runtime failures the drivers translate into DeviceLossError:
# jax.errors.JaxRuntimeError (surfacing device resets, DMA failures, dead
# PJRT clients). Deliberately NOT a
# plain-RuntimeError catch — jax's "Array has been deleted" donation
# error and engine bugs must keep propagating as what they are.
_DEVICE_ERROR_TYPES = (jax.errors.JaxRuntimeError,)

# XLA status prefixes that plausibly mean a device/runtime died — the
# ALLOWLIST the translation below keys on. Anything else (OOM,
# argument/shape errors, precondition and deadline failures) surfaces
# as what it is: misclassifying a deterministic error as a loss would
# spiral the mesh down the degradation ladder replaying into the same
# failure, and for RESOURCE_EXHAUSTED fewer devices makes it WORSE. A
# missed real loss merely restores the pre-elastic behavior (the raw
# error is terminal), so the conservative direction is to allowlist.
_DEVICE_LOSS_STATUSES = (
    "INTERNAL",
    "UNAVAILABLE",
    "ABORTED",
    "CANCELLED",
    "UNKNOWN",
)


def device_loss_from(err: BaseException, chunk: int) -> "DeviceLossError | None":
    """Translate a raw dispatch/fetch exception into a DeviceLossError
    when it is an XLA runtime failure whose status plausibly means a
    device/runtime died (_DEVICE_LOSS_STATUSES), else None (the caller
    re-raises the original). The one detection seam the ensemble/mesh
    drivers share (engine/ensemble.py _drive_ensemble probe fetch)."""
    if isinstance(err, DeviceLossError):
        return err
    if isinstance(err, _DEVICE_ERROR_TYPES):
        msg = str(err).lstrip()
        if any(msg.startswith(p) for p in _DEVICE_LOSS_STATUSES):
            return DeviceLossError(chunk, cause=err)
    return None


class EngineCompileError(RuntimeError):
    """The selected engine failed to compile/trace its chunk program.
    The engines are leaf-exact bit-identical, so this is recoverable by
    degradation: runtime/chaos.py run_with_engine_ladder falls one rung
    (pump → plain), logging the reason; only a plain-engine failure is
    terminal."""

    def __init__(self, engine: str, cause: "BaseException | None" = None):
        super().__init__(
            f"{engine} engine failed to compile its chunk program: "
            f"{cause if cause is not None else 'injected fault (chaos plane)'}"
        )
        self.engine = engine


def effective_engine(cfg) -> str:
    """The engine an "auto" config actually runs — the single resolution
    seam run_round's engine selection, the chaos `compile` fault targets,
    and the fallback-ladder records all share (runtime/chaos.py,
    runtime/scheduler.py). One rule on every backend:

      1. an explicit engine name always wins;
      2. "auto" is the pump when pump_k > 0, else the plain handler.

    What the rule rests on — compile walls of the whole chunk program
    asked of the chip's compiler for a described v5e at the tgen-10k
    world's 10,240 hosts (tools/compile_for_chip.py, PR 22, 8 cores
    shared by 2-4 compiles): plain 152-182 s, pump k=2 241 s, k=4 257 s,
    k=8 284 s; at 256 hosts plain 57 s, pump k=8 90 s. On the chip's own
    host (chip_smoke.py, PR 22, TPU v5 lite): compile+launch plain 185 s,
    pump k=8 279 s; after it, 500 ms of the world took plain 64.8 s and
    the pump 67.4 s — no win for the pump, so pump_k stays an opt-in.
    Before PR 22 re-spelled the int64 divides
    (intmath.py) and the delivery-grid sorts (equeue.push_many_sorted),
    plain took 735 s at 1,024 hosts and neither engine finished in
    1,500 s at 10,240.
    """
    if cfg.engine != "auto":
        return cfg.engine
    return "pump" if cfg.pump_k > 0 else "plain"


def check_capacity(st: SimState) -> None:
    """Fail loudly if fixed-slot capacity was exhausted: past that point the
    simulation has silently dropped events and no longer matches the
    determinism contract (the tensor-shaped analogue of the reference's
    unbounded queues never dropping)."""
    qov, oov, qh, oh, xh, lh = (int(x) for x in _peek_capacity(st))
    if qov or oov:
        err = _capacity_error(
            qov + oov, queue_ov=qov, outbox_ov=oov, queue_hwm=qh,
            outbox_hwm=oh, exch_hwm=xh, land_hwm=lh,
        )
        attach_capacity_bytes(err, st)
        raise err


def host_stats(st: SimState) -> dict:
    """ONE bulk device_get of every per-host tracker/stat tensor — the
    only way per-host data ever leaves the device (heartbeat cadence or
    end-of-run; the per-chunk path reads only the probe). Returns plain
    numpy arrays keyed by counter name, plus the replicated round
    scalars."""
    return jax.device_get(
        {
            "host_id": st.host_id,
            "events_handled": st.events_handled,
            "packets_sent": st.packets_sent,
            "packets_dropped": st.packets_dropped,
            "packets_unroutable": st.packets_unroutable,
            "codel_dropped": st.net.codel_dropped,
            "bytes_sent": st.net.bytes_sent,
            "bytes_recv": st.net.bytes_recv,
            "ev_local": st.tracker.ev_local,
            "ev_tcp": st.tracker.ev_tcp,
            "bytes_ctrl": st.tracker.bytes_ctrl,
            "bytes_data": st.tracker.bytes_data,
            "retrans_segs": st.tracker.retrans_segs,
            "queue_hwm": st.tracker.queue_hwm,
            "outbox_hwm": st.tracker.outbox_hwm,
            "rounds_live": st.rounds_live,
            "rounds_idle": st.tracker.rounds_idle,
            "exch_hwm": st.tracker.exch_hwm,
            "land_hwm": st.tracker.land_hwm,
            "land_passes": st.tracker.land_passes,
            "iters_done": st.iters_done,
            "lanes_live": st.lanes_live,
            "win_ns_sum": st.win_ns_sum,
        }
    )


@scopes.keyed
def _run_chunk(st, end, num_rounds, model, tables, cfg):
    st = run_rounds_scan(st, end, num_rounds, model, tables, cfg)
    with jax.named_scope(scopes.PROBE):
        return st, state_probe(st)


# model/cfg are hashable frozen dataclasses -> proper jit cache keys, so
# repeated run_until calls reuse the compiled chunk executable. The state
# is DONATED: the O(hosts x queue_cap) HBM pytree is aliased in-place
# across chunks instead of copied per chunk — drivers must feed this only
# states they own (SimState.donatable()), never a caller's buffers.
_run_chunk_jit = jax.jit(_run_chunk, static_argnums=(2, 3, 5), donate_argnums=(0,))


def _capacity_error(
    dropped: int,
    queue_ov: "int | None" = None,
    outbox_ov: "int | None" = None,
    queue_hwm: "int | None" = None,
    outbox_hwm: "int | None" = None,
    exch_hwm: "int | None" = None,
    land_hwm: "int | None" = None,
) -> CapacityError:
    """The split (when known — it rides the probe's dedicated lanes, so
    every driver has it) names WHICH fixed-slot counter saturated; the
    per-host high-water marks (tracker plane, nonzero only with
    cfg.tracker) say how close to the rim the other one ran, the exchange
    high-water (PROBE_EXCH_HWM, counted in every program) reports the
    pool occupancy an exchange-side drop was up against, and the
    landing's (PROBE_LAND_HWM, likewise) the fan-in of the busiest
    destination of one round."""
    if queue_ov is None:
        which = "queue.overflow/outbox.overflow"
    else:
        sat = [
            name
            for name, n in (("queue", queue_ov), ("outbox/exchange", outbox_ov))
            if n
        ]
        which = (
            f"saturated: {' + '.join(sat) or 'unknown'} "
            f"[queue.overflow={queue_ov}, outbox.overflow={outbox_ov}"
        )
        if queue_hwm or outbox_hwm:
            which += f"; high-water queue={queue_hwm}, outbox={outbox_hwm}"
        if exch_hwm:
            which += f"; exchange pool occupancy hwm={exch_hwm} events/round"
        if land_hwm:
            which += f"; busiest destination landed {land_hwm} arrivals in one round"
        which += "]"
    err = CapacityError(
        f"event capacity exhausted: {dropped} events/packets dropped "
        f"({which}); increase queue_capacity/"
        f"outbox_capacity — or, for sharded all_to_all runs with "
        f"pair-skewed destinations, set a2a_capacity=-1 (whole-outbox "
        f"buckets, never overflow)"
    )
    err.queue_overflow = int(queue_ov or 0)
    err.outbox_overflow = int(outbox_ov or 0)
    err.queue_hwm = int(queue_hwm or 0)
    err.outbox_hwm = int(outbox_hwm or 0)
    err.exchange_hwm = int(exch_hwm or 0)
    return err


def attach_capacity_bytes(err: CapacityError, st) -> None:
    """Memory observatory satellite: price the saturated buffer(s) now
    and after the x2 regrow recovery would apply, from the live state's
    shapes (metadata only — no device sync), and render the figures next
    to the high-water marks. Best-effort: diagnostics never mask the
    error. Works on single, ensemble [R, ...] and mesh states alike —
    buffer_nbytes keys the capacity axis off the per-host counter rank."""
    from shadow_tpu.engine.state import buffer_nbytes, fmt_bytes

    try:
        cur = grown = 0
        for sub, counts, saturated in (
            (st.queue, st.queue.count, err.queue_overflow),
            (st.outbox, st.outbox.fill, err.outbox_overflow),
        ):
            if not saturated:
                continue
            base = len(counts.shape)
            cur += buffer_nbytes(sub, base)
            grown += buffer_nbytes(sub, base, scale=2.0)
        if not cur:
            return
        err.bytes_current = int(cur)
        err.bytes_regrown = int(grown)
        err.args = (
            f"{err.args[0]}\n  saturated buffer bytes: {fmt_bytes(cur)} now, "
            f"{fmt_bytes(grown)} after the x2 regrow",
        ) + err.args[1:]
    except Exception:  # noqa: BLE001 — diagnostics must not mask the error
        pass


def capacity_topk(st: SimState, k: int = 5) -> str:
    """Failure-path diagnostic: the top-k destination hosts by landed
    events (queue occupancy / overflow / high-water), one bulk fetch of
    the [H] counters — the local-rows analogue of the sharded driver's
    `_capacity_detail` probe-lane breakdown, naming WHERE the landing
    side saturated. Wired as `_drive`'s capacity_detail for
    single-device runs and appended to the sharded per-shard rows."""
    import numpy as np

    cnt, ov, hwm, hid = (
        np.asarray(a)
        for a in jax.device_get(
            (st.queue.count, st.queue.overflow, st.tracker.queue_hwm, st.host_id)
        )
    )
    score = ov.astype(np.int64) * 1_000_000 + np.maximum(
        hwm.astype(np.int64), cnt.astype(np.int64)
    )
    order = np.argsort(-score, kind="stable")[:k]
    rows = [
        f"host {int(hid[i])} (count={int(cnt[i])}, overflow={int(ov[i])}, "
        f"hwm={int(hwm[i])})"
        for i in order
        if score[i] > 0
    ]
    if not rows:
        return ""
    return "top destination hosts by landed events: " + "; ".join(rows)


def _tspan(tracker, name, **args):
    """A tracker span, or a no-op when no tracker is attached (the hot
    path pays one `if`)."""
    if tracker is None:
        return contextlib.nullcontext()
    return tracker.span(name, **args)


def _fetch_probe(arr, watchdog_s: float, chunk_idx: int):
    """Fetch a chunk's probe, under the chunk-dispatch watchdog when one
    is configured (experimental.chunk_watchdog_s > 0): the blocking
    device_get runs in a helper thread bounded by the deadline, so a
    wedged dispatch surfaces as WatchdogExpired instead of blocking the
    driver forever. Watchdog off = the plain blocking fetch, no thread.
    The chaos plane's `stall` fault injects its delay here — inside the
    watchdog-measured region — which is how the watchdog is exercised
    deterministically (tests/test_chaos.py)."""
    from shadow_tpu.runtime import chaos

    t0 = time.perf_counter()
    stall = chaos.fire("stall", at=chunk_idx)
    if stall is not None:
        time.sleep(stall.stall_s)
    if watchdog_s <= 0:
        return jax.device_get(arr)
    remaining = watchdog_s - (time.perf_counter() - t0)
    if remaining <= 0:
        raise WatchdogExpired(chunk_idx, watchdog_s)
    box: list = []
    fetcher = threading.Thread(
        target=lambda: box.append(_try_get(arr)), daemon=True
    )
    fetcher.start()
    fetcher.join(remaining)
    if not box:
        raise WatchdogExpired(chunk_idx, watchdog_s)
    ok, val = box[0]
    if not ok:
        raise val
    return val


def _try_get(arr):
    try:
        return True, jax.device_get(arr)
    except BaseException as e:  # surfaced in the caller's thread
        return False, e


def _launch_chunk0(launch, st, tracker, engine: str, compile_chunk=None):
    """Chunk 0 is where the engine's chunk program traces and compiles.
    `compile_chunk(st)` — the driver's jitted chunk lowered and compiled
    ahead of time for this state — runs inside the shared compile seam
    (runtime/chaos.py compile_seam), so a trace/compile failure (or an
    injected `compile` chaos fault) surfaces as a typed
    EngineCompileError the fallback ladder can act on. The launch itself
    runs OUTSIDE the seam and finds the executable in JAX's in-memory
    cache: what the device raises when the program runs (out of memory,
    a lost device) stays what it is. A driver handed an executable that
    was compiled elsewhere (the sweep cache's entry, compiled inside its
    own seam) passes no compile_chunk; the seam is still entered so an
    injected fault fires at the same place either way (and nothing is
    kept). The executable compiled here is kept as `scopes.last_chunk`,
    where `scopes.chunk_table()` finds the text that maps a device
    trace's operations back to the engine's layers; nobody reads it in a
    run that does not ask."""
    from shadow_tpu.runtime import chaos

    with _tspan(tracker, "compile+launch", chunk=0):
        with chaos.compile_seam(engine):
            scopes.last_chunk = None
            if compile_chunk is not None:
                with _tspan(tracker, "chunk_compile"):
                    scopes.last_chunk = compile_chunk(st)
        return launch(st)


def _drive(launch, st, end_time, max_chunks, on_chunk, pipeline, desc,
           tracker=None, on_state=None, capacity_detail=None,
           watchdog_s: float = 0.0, engine: str = "plain",
           compile_chunk=None):
    """The shared chunk-dispatch loop behind run_until and
    ShardedRunner.run_until.

    `launch(state) -> (state, probe)` dispatches one device chunk,
    donating its input. With `pipeline` on (depth 2), chunk N+1 is
    launched BEFORE chunk N's probe is fetched, so the device starts the
    next chunk while the host is still blocked on (and then deciding
    from) the previous probe; the probe transfer is a few scalars, never
    the state. The probe's overflow lane is checked every chunk, so a
    capacity blowup raises at the chunk it occurs. The driver hard-syncs
    only at termination: quiescence (probe.next_time >= end_time),
    capacity error, or max_chunks exhaustion.

    On quiescence with a chunk already in flight, that extra chunk ran
    entirely on a quiescent state — every round took run_rounds_scan's
    idle branch — so its output is leaf-identical and is returned as-is.

    With a `tracker` attached (utils/tracker.py), the loop is recorded
    as spans, children of the caller's `run` span: `compile+launch` for
    chunk 0 (with the per-entry compile as its child `chunk_compile`),
    `chunk_launch` for every later dispatch, `probe_fetch` while the
    host is blocked on a probe, `probe_decide` from the fetched probe to
    the next launch (`host_stats_fetch` and `state_snapshot` inside it,
    at their cadences) and `quiescent_restore` for the last chunk's
    put-back. Whenever the tracker says a per-host heartbeat is due —
    decided from the already-fetched probe, never an extra sync — the
    full per-host counter tensors are pulled in ONE bulk device_get from
    the live (never-donated) pending state and rendered as
    reference-style tracker lines. The driver calls `span` and
    `host_heartbeat_due` on a tracker and, only where the latter says
    yes, `emit_host_heartbeat`: the seam is duck-typed.

    `on_state` (runtime/checkpoint.py StateTap) taps chunk-boundary
    states for checkpoints / recovery snapshots / interrupt handling:
    `due(probe, chunk)` decides from the already-fetched probe,
    `commit(host_state)` receives a VERIFIED plain-numpy snapshot
    (state_to_host), `interrupted()` asks for an immediate stop. Under
    pipelining the live state at probe time is one chunk ahead of the
    verified probe, so a snapshot is held pending and committed only
    after its own chunk's probe passes the capacity check — a committed
    snapshot can never contain silently-dropped events. `capacity_detail`
    (sharded driver) turns a live state into a per-shard overflow
    breakdown appended to the CapacityError.

    `watchdog_s` > 0 arms the chunk-dispatch watchdog: a probe fetch
    that exceeds the deadline raises WatchdogExpired (the in-flight
    chunk is abandoned; runtime/recovery.py re-dispatches from the
    retained clean snapshot). `engine` labels the engine whose chunk
    program `compile_chunk` compiles before chunk 0 launches — a
    compile/trace failure there raises a typed EngineCompileError for
    the fallback ladder (_launch_chunk0). Both, plus the
    chaos plane's capacity/stall injections, are consulted through
    runtime/chaos.py hooks that cost one global read when no fault plan
    is installed.
    """
    from shadow_tpu.runtime import chaos, flightrec

    # every _drive entry (first attempt, fallback rung, recovery replay)
    # restarts the cumulative probe lanes: new delta segment
    flightrec.begin_segment()
    pend_st, pend_probe = _launch_chunk0(
        launch, st, tracker, engine, compile_chunk
    )
    launched = 1
    fetched = 0  # index of the chunk whose probe is fetched next
    pending_snap = None  # (chunk_idx, host_state) awaiting its own probe
    while True:
        nxt = None
        if pipeline and launched < max_chunks:
            with _tspan(tracker, "chunk_launch", chunk=launched):
                nxt = launch(pend_st)  # donates pend_st; device stays busy
            launched += 1
        with _tspan(tracker, "probe_fetch", chunk=fetched):
            probe = ChunkProbe.from_array(
                _fetch_probe(pend_probe, watchdog_s, fetched)
            )
        fetched += 1
        scopes.last_probes.chunk = probe  # beside the entry's (entry_probe)
        with _tspan(tracker, "probe_decide", chunk=fetched - 1):
            # flight recorder (runtime/flightrec.py): fold this probe into
            # the installed recorder's ring BEFORE the capacity checks, so a
            # post-mortem's last sample is the chunk that failed — reading
            # the already-fetched probe costs zero extra device syncs
            flightrec.observe_probe(probe, chunk=fetched - 1)
            injected = chaos.fire("capacity", at=fetched - 1)
            if injected is not None:
                raise chaos.injected_capacity_error(fetched - 1, injected)
            if probe.overflow:
                err = _capacity_error(
                    probe.overflow,
                    queue_ov=probe.queue_overflow,
                    outbox_ov=probe.outbox_overflow,
                    queue_hwm=probe.queue_hwm,
                    outbox_hwm=probe.outbox_hwm,
                    exch_hwm=probe.exch_hwm,
                    land_hwm=probe.land_hwm,
                )
                # price the saturated buffers from the live state (the
                # pipelined in-flight chunk's output when pend_st was
                # donated into it) — shape metadata only, no device sync
                attach_capacity_bytes(
                    err, nxt[0] if nxt is not None else pend_st
                )
                if capacity_detail is not None:
                    try:
                        src = nxt[0] if nxt is not None else pend_st
                        err.shard_detail = capacity_detail(src)
                        if err.shard_detail:
                            err.args = (f"{err.args[0]}\n{err.shard_detail}",)
                    except Exception:  # diagnostics must not mask the error
                        pass
                raise err
            if on_chunk is not None:
                on_chunk(probe)
            if tracker is not None and tracker.host_heartbeat_due(probe.now):
                # pend_st was donated into `nxt` under pipelining; the bulk
                # fetch must read a live state, so use the in-flight chunk's
                # output (one window later — immaterial at heartbeat cadence)
                src = nxt[0] if nxt is not None else pend_st
                with _tspan(tracker, "host_stats_fetch"):
                    tracker.emit_host_heartbeat(probe, host_stats(src))
            if on_state is not None:
                # chunk `fetched-1`'s probe just passed the capacity check:
                # any snapshot waiting on it is now verified clean
                if pending_snap is not None and pending_snap[0] <= fetched - 1:
                    on_state.commit(pending_snap[1])
                    pending_snap = None
                interrupted = on_state.interrupted()
                if (
                    pending_snap is None and on_state.due(probe, fetched - 1)
                ) or interrupted:
                    from shadow_tpu.engine.state import state_to_host

                    src = nxt[0] if nxt is not None else pend_st
                    with _tspan(tracker, "state_snapshot", chunk=launched - 1):
                        host = state_to_host(src)
                    if nxt is None:
                        on_state.commit(host)  # src IS the verified chunk
                    elif interrupted:
                        # cannot wait a chunk for verification: check the
                        # overflow counters on the host copy directly
                        if (
                            int(host.queue.overflow.sum()) == 0
                            and int(host.outbox.overflow.sum()) == 0
                        ):
                            on_state.commit(host)
                    else:
                        pending_snap = (launched - 1, host)
                if interrupted:
                    raise RunInterrupted(
                        f"run interrupted at sim time {probe.now} ns"
                    )
        if probe.next_time >= end_time:
            if nxt is None:
                return pend_st
            # The extra in-flight chunk ran on a quiescent state, so every
            # round took the idle branch: leaf-identical output, except
            # that when quiescence landed exactly on the chunk boundary
            # the idle rounds clamp `now` to end_time where the
            # synchronous driver stopped at the last productive window —
            # and, under cfg.tracker, count themselves as idle rounds.
            # Restore chunk N's `now` and idle-round count (they ride the
            # probe) so pipelined and synchronous results are leaf-exact
            # in every case.
            out = nxt[0]
            with _tspan(tracker, "quiescent_restore"):
                return out.replace(
                    now=jnp.asarray(probe.now, out.now.dtype),
                    tracker=out.tracker.replace(
                        rounds_idle=jnp.asarray(
                            probe.rounds_idle, out.tracker.rounds_idle.dtype
                        ),
                    ),
                )
        if nxt is None:
            if launched < max_chunks:  # synchronous mode: launch after probe
                with _tspan(tracker, "chunk_launch", chunk=launched):
                    nxt = launch(pend_st)
                launched += 1
            else:
                raise RuntimeError(
                    f"simulation did not reach end_time={end_time} within "
                    f"{desc}; raise max_chunks/rounds_per_chunk"
                )
        pend_st, pend_probe = nxt


def run_until(
    st: SimState,
    end_time: int,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    rounds_per_chunk: int = 64,
    max_chunks: int = 10_000,
    on_chunk=None,
    pipeline: bool = True,
    tracker=None,
    on_state=None,
    watchdog_s: float = 0.0,
) -> SimState:
    """Host-side driver: chunked device scans until no work remains before
    end_time. Single-device variant; the sharded driver lives in
    engine/sharded.py.

    Chunks are dispatched through a depth-2 async pipeline with the state
    donated between chunks (see _drive): the host never blocks on more
    than the [PROBE_LANES] probe array, and the HBM state is aliased
    in-place across chunks. `pipeline=False` keeps the same executable but
    fetches each chunk's probe before launching the next — the synchronous
    reference the equivalence tests pin the pipeline against.

    `on_chunk(probe: ChunkProbe)` is invoked once per completed chunk
    (heartbeats/progress); it receives the fetched probe, not the state.
    `tracker` (utils/tracker.py) records the entry as one `run` span
    with the entry's own steps (`validate_runahead`, `peek_next_time`:
    the fetch of the entry's probe, `put_end_time`, `donate_copy`) and
    the dispatch loop's spans (see _drive) below it, and per-host
    heartbeats.
    """
    with _tspan(tracker, "run"):
        with _tspan(tracker, "validate_runahead"):
            validate_runahead(cfg, tables)
        with _tspan(tracker, "peek_next_time"):
            quiescent = entry_probe(st).next_time >= end_time
        if quiescent:
            # already quiescent: the zero-work fast path of the old driver
            # — no copy, no chunk dispatch, caller's state returned untouched
            check_capacity(st)
            return st
        with _tspan(tracker, "put_end_time"):
            end = jnp.asarray(end_time, jnp.int64)
        with _tspan(tracker, "donate_copy"):
            st = st.donatable()  # the caller's buffers are never donated

        # the seed never enters the traced chunk (it lives in the state's
        # key grid), so canonicalizing it out of the static cfg lets
        # same-shape worlds that differ only in seed share one compiled
        # executable
        jit_cfg = trace_static_cfg(cfg)

        chunk_args = (end, rounds_per_chunk, model, tables, jit_cfg)

        def launch(s):
            return _run_chunk_jit(s, *chunk_args)

        return _drive(
            launch, st, end_time, max_chunks, on_chunk, pipeline,
            desc=f"{max_chunks}x{rounds_per_chunk} rounds",
            tracker=tracker, on_state=on_state,
            capacity_detail=capacity_topk,
            watchdog_s=watchdog_s, engine=effective_engine(cfg),
            compile_chunk=lambda s: _run_chunk_jit.lower(
                s, *chunk_args
            ).compile(),
        )


def round_body_debug(
    st: SimState,
    window_end,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    trace: "list | None" = None,
) -> SimState:
    """Eager (non-while_loop) version of a round's drain phase for tests:
    records every popped event into `trace` as
    (time, tie, kind, data, host) tuples in pop order per iteration."""
    window_end = jnp.asarray(window_end, jnp.int64)
    while bool(jnp.any(equeue.next_time(st.queue) < window_end)):
        if trace is not None:
            want = equeue.next_time(st.queue) < window_end
            ev, _ = equeue.pop_min(st.queue, want)
            for hh in range(st.num_hosts):
                if bool(ev.valid[hh]):
                    trace.append(
                        (
                            int(ev.time[hh]),
                            int(ev.tie[hh]),
                            int(ev.kind[hh]),
                            tuple(int(x) for x in ev.data[hh]),
                            hh,
                        )
                    )
        st = handle_one_iteration(st, window_end, model, tables, cfg)
    st = flush_outbox(st, None)
    return st.replace(now=jnp.maximum(st.now, window_end))
