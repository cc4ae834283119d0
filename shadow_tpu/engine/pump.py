"""Packet-pump microscan: break the one-event-per-host-per-iteration bound.

The round engine's iteration count equals the max per-host backlog in a
window (engine/round.py), and profiling shows busy hosts pop runs of
10-25 *consecutive packet events* (shaping defer/completion chains and
in-order data/ACK streams) with the full ~4k-op handler re-dispatched per
event — the exact economics the reference avoids with its per-host drain
loop (reference: src/main/host/host.rs:697-752). This stage drains up to
K such events per host per iteration through three narrowly-conditioned
vectorized fast paths, each a few hundred ops per step instead of the
whole handler:

  P1  ingress defer/drop: an unshaped arrival that the rx token-bucket
      defers (or CoDel drops) — pure netstack arithmetic, no TCP.
  P2  data completion at a receiver: ESTABLISHED, no flags beyond ACK,
      no piggy-backed ACK advance, empty sender-side scoreboard, send
      side fully flushed and no FIN pending. In-order AND out-of-order
      arrivals qualify (the shaping relay's closed-form bucket
      legitimately lets a later packet pass while an earlier one is
      deferred, so OOO is the NORM in backlogged rounds): effects are
      the handler's accept/absorb/insert receive flow plus one ACK out
      advertising the lowest buffered OOO range (SACK).
  P3  cumulative ACK at a sender: ESTABLISHED, advancing snd_una, not
      in recovery, FIN not yet sent — effects are snd_una advance, Reno
      ss/ca step, RTO re-arm, RTT sample, SACK scoreboard merge/drop,
      and the send-engine lane loop releasing up to segs_per_flush new
      segments (including the FIN-after-data lane: tgen-style servers
      run their whole response with fin_pending set).

Anything else (handshakes, FIN/RST arrivals, dupacks, recovery,
timer events, model triggers like "request complete -> respond") falls
through to the unchanged full handler in the same iteration, so the pump
is a pure accelerator: the per-host event *sequence* — state updates,
emissions, draws, sequence numbers, byte counters — is bit-identical to
running the full handler per event (proven against the independent scalar
oracle by tests/test_pump.py and the tests/test_cpu_ref_* suites).

Ordering correctness: each microstep re-selects the host's true next
event by the total-order key, comparing the queue head against a small
pending-defer FIFO (deferred re-enqueues have monotonically increasing
ready times per host, so the FIFO stays sorted). This preserves the exact
scalar interleaving of defers and completions — including CoDel's
backlog-sensitive decisions. Pump emissions are packets only (delivery
clamped to the next round); a step that would emit a *local* event (flush
continuation, timer maintenance) is rejected and left to the full
handler, so nothing the pump produces can sort before a later pump step.

The carry landing (pump_carry_finish) only ever touches the host's OWN
row — defer-FIFO leftovers re-enter via conflict-free self-lane pushes
and packet emissions re-enter the per-host outbox — so the pump is
exchange-mode agnostic: the round-boundary cross-host landing happens
entirely in flush_outbox afterwards (all_to_all or all_gather per
cfg.exchange, landed by pull), identically for every engine.

Models opt in by exposing `pump_spec` (see TcpPumpSpec); the spec's
`block` hook vetoes steps where the embedding model itself would act on
the new state (e.g. tgen's request-complete -> respond trigger).

Structure (round 6): the per-microstep body is factored into an explicit
carry — `pump_carry_init` / `pump_microstep` / `pump_carry_finish` —
which `pump_stage` strings together: init, pump_k cond-guarded
microsteps, finish. One microstep is a pure function of the carry, so it
can be traced, compiled and tested alone (tools/compile_for_chip.py
`pump_microstep`, tests/test_chip_compile.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

import flax.struct

from shadow_tpu import equeue, netstack, rng, scopes
from shadow_tpu.engine.state import EngineConfig, SimState
from shadow_tpu.events import KIND_PACKET, pack_tie, tie_src_host
from shadow_tpu.graph.routing import RoutingTables, node_of, route_lookup
from shadow_tpu.netstack import AUX_SHAPED_BIT, AUX_SIZE_MASK
from shadow_tpu.simtime import TIME_MAX
from shadow_tpu.transport import tcp as T
from shadow_tpu.transport.header import (
    FLAG_ACK,
    FLAG_FIN,
    FLAG_RST,
    FLAG_SYN,
    LANE_ACK,
    LANE_FLAGS_LEN,
    LANE_PORTS,
    LANE_SACK_E,
    LANE_SACK_S,
    LANE_SEQ,
    LANE_WND,
    unpack_flags_len,
    unpack_ports,
    unwrap32,
)

_I64_MAX = jnp.iinfo(jnp.int64).max


@dataclasses.dataclass(frozen=True)
class TcpPumpSpec:
    """Model-side pump contract for models embedding transport/tcp.py.

    get_tcp/set_tcp map between the model-state pytree and its TcpState;
    `block(mstate, host_id, v_st, v_snd_end, delivered_new, delta)`
    returns hosts where the model would react to the candidate post-event
    slot state (those steps fall back to the full handler);
    `apply(mstate, take, host_id, delivered_delta)` applies the model's
    passive per-event bookkeeping (e.g. tgen byte counters) for taken
    steps.
    """

    params: T.TcpParams
    get_tcp: Callable[[Any], T.TcpState]
    set_tcp: Callable[[Any, T.TcpState], Any]
    block: Callable[..., jax.Array]
    apply: Callable[..., Any]


@flax.struct.dataclass
class PumpCarry:
    """Everything a pump microstep reads or writes, host-axis leading.

    This is the exact working set of the microsteps; every leaf leads
    with the (local) host axis except `min_used` (scalar). `ts` is the
    focus TcpState extracted by spec.get_tcp at init and merged back by
    spec.set_tcp at finish; `mstate` carries the rest of the model pytree
    (its embedded TcpState copy is stale during the scan and unused).
    `key_data` is the raw-u32 view of the per-host threefry keys
    (wrap_key_data inside the step restores bit-identical draws).
    """

    # mutated simulation state
    q: equeue.EventQueue
    net: Any  # NetDevState
    ts: T.TcpState
    mstate: Any
    # outbox columns (written lane-at-a-time; rebuilt into Outbox at finish)
    obv: jax.Array
    obd: jax.Array
    obt: jax.Array
    obtie: jax.Array
    obdata: jax.Array  # [H, PAYLOAD_LANES, O], as Outbox.data
    obaux: jax.Array
    obfill: jax.Array
    obover: jax.Array
    # pending-defer FIFO (ready times are monotone per host -> sorted)
    f_time: jax.Array
    f_tie: jax.Array
    f_kind: jax.Array
    f_data: jax.Array
    f_aux: jax.Array
    f_head: jax.Array
    f_cnt: jax.Array
    # per-host counters/stats
    seq: jax.Array
    rng_counter: jax.Array
    events_handled: jax.Array
    packets_sent: jax.Array
    packets_dropped: jax.Array
    packets_unroutable: jax.Array
    # tracker plane ([H] i64 when cfg.tracker, else None — a None leaf
    # is absent from the flattened pytree, so the carry holds NOTHING
    # for them with the plane off. These are the only
    # TrackerState leaves a pump microstep can touch: pump-taken events
    # are all packets, so the per-kind local/tcp counters never move
    # here.)
    trk_bytes_ctrl: "jax.Array | None"
    trk_bytes_data: "jax.Array | None"
    trk_retrans: "jax.Array | None"
    min_used: jax.Array  # scalar
    # scan control
    alive: jax.Array
    rejected: jax.Array
    # read-only per-row context
    host_ids: jax.Array
    src_node: jax.Array
    key_data: jax.Array  # [H, ...] u32 raw threefry key words


def _fifo_peek(f_time, f_tie, f_head, f_cnt):
    k = f_time.shape[1]
    oh = jnp.arange(k)[None, :] == f_head[:, None]
    has = f_head < f_cnt
    t = jnp.where(
        has, jnp.sum(jnp.where(oh, f_time, 0), axis=1), TIME_MAX
    )
    tie = jnp.where(
        has, jnp.sum(jnp.where(oh, f_tie, 0), axis=1), _I64_MAX
    )
    return has, t, tie, oh


def pump_carry_init(
    st: SimState, model, tables: RoutingTables, cfg: EngineConfig
) -> PumpCarry:
    """Build the microstep carry from a SimState (plain XLA; one routing
    gather). The FIFO is sized cfg.pump_k: at most one defer can be
    inserted per taken step."""
    spec: TcpPumpSpec = model.pump_spec
    k = cfg.pump_k
    h = st.seq.shape[0]
    ob = st.outbox
    return PumpCarry(
        q=st.queue,
        net=st.net,
        ts=spec.get_tcp(st.model),
        mstate=st.model,
        obv=ob.valid,
        obd=ob.dst,
        obt=ob.time,
        obtie=ob.tie,
        obdata=ob.data,
        obaux=ob.aux,
        obfill=ob.fill,
        obover=ob.overflow,
        f_time=jnp.full((h, k), TIME_MAX, jnp.int64),
        f_tie=jnp.full((h, k), _I64_MAX, jnp.int64),
        f_kind=jnp.zeros((h, k), jnp.int32),
        f_data=jnp.zeros((h, k, equeue.PAYLOAD_LANES), jnp.int32),
        f_aux=jnp.zeros((h, k), jnp.int32),
        f_head=jnp.zeros((h,), jnp.int32),
        f_cnt=jnp.zeros((h,), jnp.int32),
        seq=st.seq,
        rng_counter=st.rng_counter,
        events_handled=st.events_handled,
        packets_sent=st.packets_sent,
        packets_dropped=st.packets_dropped,
        packets_unroutable=st.packets_unroutable,
        trk_bytes_ctrl=st.tracker.bytes_ctrl if cfg.tracker else None,
        trk_bytes_data=st.tracker.bytes_data if cfg.tracker else None,
        trk_retrans=st.tracker.retrans_segs if cfg.tracker else None,
        min_used=st.min_used_lat,
        alive=jnp.ones((h,), bool),
        rejected=jnp.zeros((h,), bool),
        host_ids=st.host_id,
        src_node=node_of(tables, st.host_id),
        key_data=jax.random.key_data(st.rng_key),
    )


def pump_microstep(
    c: PumpCarry,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    debug_out: "list | None" = None,
) -> PumpCarry:
    """One pump microstep: select each live host's true next event,
    classify against P1/P2/P3, commit taken steps, mark the rest
    rejected. Pure function of the carry — every op is row-local
    (elementwise over [H] / [H, S] / [H, K]), which is what lets
    active-set compaction hand it any subset of rows.

    Cost shape: every per-step update is elementwise over [H] or [H, S]
    with a slot-one-hot mask — no gather/scatter of the TcpState (the
    round-5 first cut gathered/scattered a fused view per step, which was
    ~720 of ~2900 eqns per step). Emission token-bucket charges use the
    closed-form multi-lane tb (netstack.tb_depart_lanes). `debug_out`
    (eager/tests only) collects per-step mask tallies.
    """
    spec: TcpPumpSpec = model.pump_spec
    p = spec.params
    k = c.f_time.shape[1]
    h = c.seq.shape[0]
    host_ids = c.host_ids
    mss = jnp.int64(p.mss)
    draws = jnp.uint32(model.DRAWS_PER_EVENT)
    ep = model.PACKET_EMITS
    stride = jnp.uint32(model.DRAWS_PER_EVENT + ep)
    nseg = p.segs_per_flush
    rng_keys = jax.random.wrap_key_data(c.key_data)

    q = c.q
    net = c.net
    mstate = c.mstate
    ts = c.ts
    o_cap = c.obv.shape[1]
    lane_idx_ob = jnp.arange(o_cap)[None, :]

    seq = c.seq
    rng_counter = c.rng_counter
    events_handled = c.events_handled
    packets_sent = c.packets_sent
    packets_dropped = c.packets_dropped
    packets_unroutable = c.packets_unroutable
    min_used = c.min_used

    obv, obd, obt, obtie = c.obv, c.obd, c.obt, c.obtie
    obdata, obaux, obfill, obover = c.obdata, c.obaux, c.obfill, c.obover

    f_time, f_tie, f_kind = c.f_time, c.f_tie, c.f_kind
    f_data, f_aux = c.f_data, c.f_aux
    f_head, f_cnt = c.f_head, c.f_cnt

    alive = c.alive
    rejected = c.rejected
    src_node = c.src_node

    # ---- select each host's true next event: queue vs defer FIFO
    # (the FIFO exists only under shaping; without the netstack no
    # defer can ever be inserted, so the select is queue-only) ----
    qv, q_slot = equeue.peek_min(q, alive)
    if cfg.use_netstack:
        fh_has, fh_t, fh_tie, fh_oh = _fifo_peek(f_time, f_tie, f_head, f_cnt)
        use_f = (
            alive
            & fh_has
            & (
                ~qv.valid
                | (fh_t < qv.time)
                | ((fh_t == qv.time) & (fh_tie < qv.tie))
            )
        )
    else:
        use_f = jnp.zeros((h,), bool)
        fh_t = jnp.full((h,), TIME_MAX, jnp.int64)
        fh_tie = jnp.full((h,), _I64_MAX, jnp.int64)
        fh_oh = jnp.zeros((h, k), bool)
    ev_valid = alive & (use_f | qv.valid)
    ev_time = jnp.where(use_f, fh_t, qv.time)
    ev_valid = ev_valid & (ev_time < window_end)
    ev_tie = jnp.where(use_f, fh_tie, qv.tie)
    # explicit int32: jnp.sum promotes int under x64
    ev_kind = jnp.where(
        use_f,
        jnp.sum(jnp.where(fh_oh, f_kind, 0), axis=1).astype(jnp.int32),
        qv.kind,
    )
    ev_data = jnp.where(
        use_f[:, None],
        jnp.sum(jnp.where(fh_oh[:, :, None], f_data, 0), axis=1).astype(
            jnp.int32
        ),
        qv.data,
    )
    ev_aux = jnp.where(
        use_f,
        jnp.sum(jnp.where(fh_oh, f_aux, 0), axis=1).astype(jnp.int32),
        qv.aux,
    )
    ev_src = tie_src_host(ev_tie).astype(jnp.int32)
    now = ev_time

    is_pkt = ev_valid & (ev_kind == KIND_PACKET)
    size_in = (ev_aux & AUX_SIZE_MASK).astype(jnp.int64)
    shaped = (ev_aux & AUX_SHAPED_BIT) != 0
    loopback = ev_src == host_ids
    in_bootstrap = ev_time < cfg.bootstrap_end_ns

    # ---- ingress relay/CoDel (tentative; committed only where taken)
    if cfg.use_netstack:
        need = (
            is_pkt & ~shaped & ~loopback & ~in_bootstrap & (net.rx_refill > 0)
        )
        ready, rx_tok, rx_last = netstack.tb_depart(
            net.rx_tokens, net.rx_last, net.rx_refill, ev_time, size_in, need
        )
        sojourn = ready - ev_time
        codel_drop, net_c = netstack.codel_dequeue(net, ready, sojourn, need)
        keep_in = need & ~codel_drop
        defer = keep_in & (ready > ev_time)
        p1_take = is_pkt & ~shaped & (defer | codel_drop)
        arrived = is_pkt & ~(defer | codel_drop)
    else:
        need = jnp.zeros((h,), bool)
        ready = ev_time
        codel_drop = jnp.zeros((h,), bool)
        defer = jnp.zeros((h,), bool)
        p1_take = jnp.zeros((h,), bool)
        arrived = is_pkt
        net_c = net

    # ---- TCP classification on arrived packets ----------------------
    # `oh` is the event's slot as a one-hot over [H, S]; every state
    # read is a masked reduction, every write a masked where — the
    # TcpState never round-trips through a gathered view.
    sport, dport = unpack_ports(ev_data[:, LANE_PORTS])
    exact = (
        (ts.st != T.CLOSED)
        & (ts.st != T.LISTEN)
        & (ts.lport == dport[:, None])
        & (ts.rhost == ev_src[:, None])
        & (ts.rport == sport[:, None])
    )
    rx_exact = arrived & jnp.any(exact, axis=1)
    oh = exact & arrived[:, None]  # [H, S] one-hot (zero row if none)

    def rd(a):
        if a.dtype == jnp.bool_:
            return jnp.any(oh & a, axis=1)
        return jnp.sum(jnp.where(oh, a, 0), axis=1).astype(a.dtype)

    def rd4(a):  # [H, S, R, 2] -> [H, R, 2]
        o4 = oh[:, :, None, None]
        return jnp.sum(jnp.where(o4, a, 0), axis=1).astype(a.dtype)

    v_st = rd(ts.st)
    v_lport = rd(ts.lport)
    v_rport = rd(ts.rport)
    v_rhost = rd(ts.rhost)
    v_snd_una = rd(ts.snd_una)
    v_snd_nxt = rd(ts.snd_nxt)
    v_snd_max = rd(ts.snd_max)
    v_snd_end = rd(ts.snd_end)
    v_fin_pending = rd(ts.fin_pending)
    v_fin_sent = rd(ts.fin_sent)
    v_rcv_nxt = rd(ts.rcv_nxt)
    v_rcv_fin = rd(ts.rcv_fin)
    v_cwnd = rd(ts.cwnd)
    v_ssthresh = rd(ts.ssthresh)
    v_dupacks = rd(ts.dupacks)
    v_in_rec = rd(ts.in_rec)
    v_srtt = rd(ts.srtt)
    v_rttvar = rd(ts.rttvar)
    v_rto = rd(ts.rto)
    v_rtt_pending = rd(ts.rtt_pending)
    v_rtt_seq = rd(ts.rtt_seq)
    v_rtt_ts = rd(ts.rtt_ts)
    v_rto_expire = rd(ts.rto_expire)
    v_tev_time = rd(ts.tev_time)
    v_ooo = rd4(ts.ooo)
    v_sacked = rd4(ts.sacked)

    flags, plen = unpack_flags_len(ev_data[:, LANE_FLAGS_LEN])
    f_ackf = (flags & FLAG_ACK) != 0
    clean_flags = f_ackf & (
        (flags & (FLAG_SYN | FLAG_FIN | FLAG_RST)) == 0
    )
    wnd = ev_data[:, LANE_WND].astype(jnp.int64)
    abs_seq = unwrap32(v_rcv_nxt, ev_data[:, LANE_SEQ])
    abs_ack = unwrap32(v_snd_una, ev_data[:, LANE_ACK])
    sack_present = ev_data[:, LANE_SACK_S] != ev_data[:, LANE_SACK_E]

    sacked_empty = jnp.all(v_sacked[:, :, 0] < 0, axis=1)
    quiet = (
        rx_exact
        & (v_st == T.ESTABLISHED)
        & clean_flags
        & (v_rcv_fin < 0)
        & ~v_fin_sent
        # timer-event invariant: nothing for the output pass to re-arm
        & (v_rto_expire >= v_tev_time)
    )

    # P2: data at a receiver (in-order, out-of-order — the shaping
    # relay's closed-form bucket legitimately lets a later packet pass
    # while an earlier one is deferred — or stale duplicate), no
    # piggy-backed ACK advance, send side fully flushed so the output
    # pass is a proven no-op.
    seg_s = abs_seq
    seg_e = abs_seq + plen.astype(jnp.int64)
    p2 = (
        quiet
        & (plen > 0)
        & (seg_s <= v_rcv_nxt + p.rcv_wnd)
        & (abs_ack <= v_snd_una)
        & (v_snd_end <= v_snd_nxt)
        & ~v_in_rec
        & (v_dupacks == 0)
        & ~sack_present
        & sacked_empty
        # a pending FIN could go out the output pass; receivers never
        # half-close mid-stream, senders take P3's FIN-capable path
        & ~v_fin_pending
    )
    acceptable = p2 & (seg_e > v_rcv_nxt)
    in_order = acceptable & (seg_s <= v_rcv_nxt)
    ooo_seg = acceptable & ~in_order
    rcv1 = jnp.where(in_order, seg_e, v_rcv_nxt)
    rcv1, ooo1 = T._ooo_absorb(rcv1, v_ooo, in_order)
    ooo1 = T._ooo_insert(ooo1, ooo_seg, seg_s, seg_e)
    delivered_delta = jnp.where(p2, rcv1 - v_rcv_nxt, 0)

    # P3: pure cumulative ACK advancing snd_una, outside recovery
    p3 = (
        quiet
        & (plen == 0)
        & ~v_in_rec
        & (abs_ack > v_snd_una)
        & (abs_ack <= v_snd_max)
    )

    # model veto on the candidate outcome (e.g. tgen's respond trigger)
    blocked = spec.block(
        mstate, host_ids, v_st, v_snd_end,
        rd(ts.delivered) + delivered_delta, delivered_delta,
    )
    p2 = p2 & ~blocked
    p3 = p3 & ~blocked

    # ---- P3 state update --------------------------------------------
    m_rtt = p3 & v_rtt_pending & (abs_ack >= v_rtt_seq)
    ss = p3 & (v_cwnd < v_ssthresh)
    ca = p3 & ~ss
    acked = jnp.where(p3, abs_ack - v_snd_una, 0)
    cwnd1 = jnp.where(ss, v_cwnd + jnp.minimum(acked, mss), v_cwnd)
    cwnd1 = jnp.where(
        ca, cwnd1 + jnp.maximum(T.ca_increment(p.mss, cwnd1), 1), cwnd1
    )
    una1 = jnp.where(p3, abs_ack, v_snd_una)
    nxt1 = jnp.where(p3, jnp.maximum(v_snd_nxt, abs_ack), v_snd_nxt)
    outstanding = una1 < v_snd_max
    expire1 = jnp.where(
        p3, jnp.where(outstanding, now + v_rto, TIME_MAX), v_rto_expire
    )
    # RFC 6298 sample (the handler's _rtt_update, scalar-field form)
    rtt = now - v_rtt_ts
    first = v_srtt < 0
    rttvar1 = jnp.where(
        first, rtt >> 1, (3 * v_rttvar + jnp.abs(v_srtt - rtt)) >> 2
    )
    srtt1 = jnp.where(first, rtt, (7 * v_srtt + rtt) >> 3)
    rto1 = jnp.clip(
        srtt1 + jnp.maximum(p.granularity_ns, 4 * rttvar1),
        p.rto_min_ns,
        p.rto_max_ns,
    )
    n_srtt = jnp.where(m_rtt, srtt1, v_srtt)
    n_rttvar = jnp.where(m_rtt, rttvar1, v_rttvar)
    n_rto = jnp.where(m_rtt, rto1, v_rto)
    n_rtt_pending = jnp.where(m_rtt, False, v_rtt_pending)

    # sender-side SACK scoreboard merge + cumulative-ACK drop
    if p.use_sack:
        has_sack = p3 & sack_present
        abs_ss = unwrap32(una1, ev_data[:, LANE_SACK_S])
        abs_se = unwrap32(una1, ev_data[:, LANE_SACK_E])
        sacked1 = T._ooo_insert(v_sacked, has_sack, abs_ss, abs_se)
        dropm = (
            p3[:, None]
            & (sacked1[:, :, 0] >= 0)
            & (sacked1[:, :, 1] <= una1[:, None])
        )
        sacked2 = jnp.where(dropm[:, :, None], jnp.int64(-1), sacked1)
    else:
        sacked2 = v_sacked

    # ---- P3 send engine (rtx_hole/SYN lanes provably inactive; the
    # FIN lane live — tgen-style servers run their whole response with
    # fin_pending set) ------------------------------------------------
    peer_wnd1 = jnp.where(p2 | p3, wnd, rd(ts.peer_wnd))
    wnd_lim = una1 + jnp.minimum(cwnd1, peer_wnd1)
    fin_lim = v_snd_end + v_fin_pending.astype(jnp.int64)
    cursor = nxt1
    can_send = p3
    rp = n_rtt_pending
    rs = v_rtt_seq
    rt = v_rtt_ts
    sent_any = jnp.zeros((h,), bool)
    fin_goes = jnp.zeros((h,), bool)
    rtx_count = jnp.zeros((h,), jnp.int64)
    lane_valid = []
    lane_seq_w = []
    lane_len = []
    lane_fin = []
    for _i in range(nseg):
        room = jnp.minimum(jnp.minimum(v_snd_end, wnd_lim), cursor + mss)
        dlen = jnp.maximum(room - cursor, 0)
        send_data = can_send & (dlen > 0)
        send_fin = (
            can_send
            & ~send_data
            & v_fin_pending
            & (cursor == v_snd_end)
            & (cursor + 1 <= wnd_lim)
            & ~fin_goes
        )
        lane_valid.append(send_data | send_fin)
        lane_seq_w.append(cursor)
        lane_len.append(jnp.where(send_data, dlen, 0).astype(jnp.int32))
        lane_fin.append(send_fin)
        is_rtx = send_data & (cursor < v_snd_max)
        rtx_count = rtx_count + is_rtx
        fresh = send_data & (cursor >= v_snd_max)
        start_rtt = fresh & ~rp
        rp = rp | start_rtt
        rs = jnp.where(start_rtt, cursor + dlen, rs)
        rt = jnp.where(start_rtt, now, rt)
        cursor = cursor + jnp.where(send_data, dlen, 0) + send_fin
        fin_goes = fin_goes | send_fin
        sent_any = sent_any | send_data | send_fin
    new_nxt = jnp.where(can_send, jnp.maximum(nxt1, cursor), nxt1)
    new_max = jnp.maximum(v_snd_max, new_nxt)
    arm = p3 & (una1 < new_max) & (expire1 >= TIME_MAX) & sent_any
    new_expire = jnp.where(arm, now + n_rto, expire1)
    more = can_send & (jnp.minimum(fin_lim, wnd_lim) > cursor)
    need_tev = (p2 | p3) & (new_expire < v_tev_time)
    # a step that would emit a local event falls back to the handler
    p3 = p3 & ~more & ~need_tev
    p2 = p2 & ~need_tev

    take_tcp = p2 | p3
    take = p1_take | take_tcp
    rejected = rejected | (ev_valid & ~take)
    if debug_out is not None:
        debug_out.append(
            {
                k_: int(jnp.sum(v_))
                for k_, v_ in dict(
                    ev_valid=ev_valid, is_pkt=is_pkt, shaped=shaped & ev_valid,
                    p1=p1_take, arrived=arrived, rx_exact=rx_exact,
                    quiet=quiet, p2=p2, p3=p3, blocked=blocked & arrived,
                    more=more & arrived, need_tev=need_tev,
                    take=take, use_f=use_f,
                ).items()
            }
        )
    # consume the event from its source
    q = equeue.clear_slot(q, q_slot, take & ~use_f)
    f_head = f_head + (take & use_f).astype(jnp.int32)

    # ---- commit netstack state -------------------------------------
    if cfg.use_netstack:
        commit_n = take & need
        net = net.replace(
            rx_tokens=jnp.where(commit_n & keep_in, rx_tok, net.rx_tokens),
            rx_last=jnp.where(commit_n & keep_in, rx_last, net.rx_last),
            codel_first_above=jnp.where(
                commit_n, net_c.codel_first_above, net.codel_first_above
            ),
            codel_drop_next=jnp.where(
                commit_n, net_c.codel_drop_next, net.codel_drop_next
            ),
            codel_count=jnp.where(
                commit_n, net_c.codel_count, net.codel_count
            ),
            codel_dropping=jnp.where(
                commit_n, net_c.codel_dropping, net.codel_dropping
            ),
            codel_dropped=net.codel_dropped + (commit_n & codel_drop),
            rx_backlog_bytes=net.rx_backlog_bytes
            + jnp.where(take & defer, size_in, 0)
            - jnp.where(take_tcp & shaped, size_in, 0),
            bytes_recv=net.bytes_recv + jnp.where(take_tcp, size_in, 0),
        )
        # deferred re-enqueue -> FIFO (ready is monotone per host)
        ins = take & defer
        ins_oh = (jnp.arange(k)[None, :] == f_cnt[:, None]) & ins[:, None]
        f_time = jnp.where(ins_oh, ready[:, None], f_time)
        f_tie = jnp.where(ins_oh, ev_tie[:, None], f_tie)
        f_kind = jnp.where(ins_oh, ev_kind[:, None], f_kind)
        f_data = jnp.where(ins_oh[:, :, None], ev_data[:, None, :], f_data)
        f_aux = jnp.where(
            ins_oh,
            (size_in.astype(jnp.int32) | jnp.int32(AUX_SHAPED_BIT))[:, None],
            f_aux,
        )
        f_cnt = f_cnt + ins.astype(jnp.int32)

    # ---- commit TCP state (slot-one-hot wheres, no scatter) ---------
    w2 = oh & p2[:, None]
    w3 = oh & p3[:, None]
    w23 = oh & take_tcp[:, None]

    def wr(a, new, m):
        return jnp.where(m, new[:, None], a)

    def wr4(a, new, m):
        return jnp.where(m[:, :, None, None], new[:, None], a)

    fin3 = p3 & fin_goes
    ts = ts.replace(
        st=wr(ts.st, jnp.full((h,), T.FINWAIT1, jnp.int32), oh & fin3[:, None]),
        fin_sent=ts.fin_sent | (oh & fin3[:, None]),
        snd_una=wr(ts.snd_una, una1, w3),
        snd_nxt=wr(ts.snd_nxt, new_nxt, w3),
        snd_max=wr(ts.snd_max, new_max, w3),
        cwnd=wr(ts.cwnd, cwnd1, w3),
        dupacks=wr(ts.dupacks, jnp.zeros((h,), jnp.int32), w3),
        backoff=wr(ts.backoff, jnp.zeros((h,), jnp.int32), w3),
        rto_expire=wr(ts.rto_expire, new_expire, w3),
        srtt=wr(ts.srtt, n_srtt, w3),
        rttvar=wr(ts.rttvar, n_rttvar, w3),
        rto=wr(ts.rto, n_rto, w3),
        rtt_pending=jnp.where(w3, rp[:, None], ts.rtt_pending),
        rtt_seq=wr(ts.rtt_seq, rs, w3),
        rtt_ts=wr(ts.rtt_ts, rt, w3),
        retransmits=ts.retransmits + jnp.where(w3, rtx_count[:, None], 0),
        peer_wnd=wr(ts.peer_wnd, peer_wnd1, w23),
        rcv_nxt=wr(ts.rcv_nxt, rcv1, w2),
        ooo=wr4(ts.ooo, ooo1, w2),
        sacked=wr4(ts.sacked, sacked2, w3),
        delivered=ts.delivered + jnp.where(w2, delivered_delta[:, None], 0),
        segs_in=ts.segs_in + w23,
        # data lanes only — the handler's segs_out counts pv[:, :nseg],
        # never the control-lane ACK
        segs_out=ts.segs_out
        + jnp.where(
            w3,
            sum(lv.astype(jnp.int64) for lv in lane_valid)[:, None],
            0,
        ),
    )
    mstate = spec.apply(mstate, take_tcp, host_ids, delivered_delta)

    # ---- emissions: P3 data/FIN lanes; the P2 ACK rides lane 0 (P2
    # and P3 are disjoint per host, and for P2 the handler's data
    # lanes are all invalid, so lane order — and therefore the
    # relay-charge and draw order — is preserved either way. The P2
    # loss draw index is remapped to the handler's control lane. ----
    dst = jnp.clip(v_rhost, 0, tables.num_global_hosts - 1)
    _, lat, rel = route_lookup(tables, src_node, dst)
    loopb = dst == host_ids
    in_btx = now < cfg.bootstrap_end_ns

    if p.use_sack:
        starts = ooo1[:, :, 0]
        present = starts >= 0
        min_start = jnp.min(
            jnp.where(present, starts, jnp.int64(1) << 62), axis=1
        )
        at_min = present & (starts == min_start[:, None])
        blk_e = jnp.max(
            jnp.where(at_min, ooo1[:, :, 1], jnp.int64(-1)), axis=1
        )
        has_blk = jnp.any(present, axis=1)
        sack_s = jnp.where(has_blk, min_start, jnp.int64(0))
        sack_e = jnp.where(has_blk, blk_e, jnp.int64(0))
    else:
        sack_s = sack_e = jnp.zeros((h,), jnp.int64)

    l_valid2 = []
    l_data2 = []
    l_size2 = []
    for lane in range(nseg):
        lv3 = lane_valid[lane] & p3
        use_ack = p2 if lane == 0 else jnp.zeros((h,), bool)
        lv = lv3 | use_ack
        lflags = jnp.where(
            lane_fin[lane],
            FLAG_FIN | FLAG_ACK,
            FLAG_ACK,
        ).astype(jnp.int32)
        ldata = T._mk_seg(
            v_lport,
            v_rport,
            jnp.where(use_ack, new_nxt, lane_seq_w[lane]),
            rcv1,
            lflags,
            jnp.where(use_ack, 0, lane_len[lane]),
            jnp.full((h,), p.rcv_wnd, jnp.int64),
            sack_s=jnp.where(use_ack, sack_s, 0),
            sack_e=jnp.where(use_ack, sack_e, 0),
        )
        l_valid2.append(lv)
        l_data2.append(ldata)
        l_size2.append(
            jnp.where(use_ack, 0, lane_len[lane]) + p.header_bytes
        )

    lv_all = jnp.stack(l_valid2, axis=1)  # [H, nseg]
    lsz_all = jnp.stack(l_size2, axis=1).astype(jnp.int64)
    unroutable_l = lv_all & (lat >= TIME_MAX)[:, None]
    # loss draws: handler lane index (P2's ACK is the control lane)
    draw_lane = jnp.where(p2, jnp.uint32(nseg), jnp.uint32(0))[:, None] + (
        jnp.arange(nseg, dtype=jnp.uint32)[None, :]
        * (~p2[:, None]).astype(jnp.uint32)
    )
    ctrs = rng_counter[:, None] + draws + draw_lane
    loss_u = rng.uniform_f32_grid(rng_keys, ctrs)  # [H, nseg]
    kept_l = lv_all & ~unroutable_l & (loss_u < rel[:, None])
    dropped_l = lv_all & ~unroutable_l & ~(loss_u < rel[:, None])
    if cfg.use_netstack:
        charge_l = (lv_all & ~unroutable_l) & ~loopb[:, None] & ~in_btx[:, None]
        deps, tx_tok, tx_last = netstack.tb_depart_lanes(
            net.tx_tokens, net.tx_last, net.tx_refill, now, lsz_all, charge_l
        )
        deliver_l = jnp.maximum(deps + lat[:, None], window_end)
        net = net.replace(
            tx_tokens=tx_tok,
            tx_last=tx_last,
            bytes_sent=net.bytes_sent
            + jnp.sum(jnp.where(kept_l, lsz_all, 0), axis=1),
        )
    else:
        deliver_l = jnp.broadcast_to(
            jnp.maximum(now + lat, window_end)[:, None], (h, nseg)
        )

    # outbox append, lane order (per-host running fill)
    new_seq = seq
    for lane in range(nseg):
        kept = kept_l[:, lane]
        has_room = obfill < o_cap
        write = kept & has_room
        at = (lane_idx_ob == obfill[:, None]) & write[:, None]
        ptie = pack_tie(
            jnp.full((h,), KIND_PACKET, jnp.int32),
            host_ids,
            new_seq.astype(jnp.uint32),
        )
        obv = obv | at
        obd = jnp.where(at, dst[:, None], obd)
        obt = jnp.where(at, deliver_l[:, lane][:, None], obt)
        obtie = jnp.where(at, ptie[:, None], obtie)
        obdata = jnp.where(at[:, None, :], l_data2[lane][:, :, None], obdata)
        obaux = jnp.where(
            at, (lsz_all[:, lane].astype(jnp.int32) & AUX_SIZE_MASK)[:, None],
            obaux,
        )
        obfill = obfill + write.astype(jnp.int32)
        obover = obover + (kept & ~has_room).astype(jnp.int32)
        new_seq = new_seq + kept.astype(jnp.uint32)
    seq = new_seq
    packets_sent = packets_sent + jnp.sum(kept_l, axis=1)
    packets_dropped = packets_dropped + jnp.sum(dropped_l, axis=1)
    packets_unroutable = packets_unroutable + jnp.sum(unroutable_l, axis=1)
    trk_bytes_ctrl = c.trk_bytes_ctrl
    trk_bytes_data = c.trk_bytes_data
    trk_retrans = c.trk_retrans
    if cfg.tracker:
        # identical classification to the full handler's tracker pass
        # (engine/round.py): control = wire size <= the model's header
        # size (the P2 ACK / P3 FIN lanes), data = the rest; retrans is
        # the same per-event segment count the step adds to
        # ts.retransmits — so the pump's tracker leaves stay leaf-exact
        # vs the plain engine.
        hdr = int(getattr(model, "WIRE_HEADER_BYTES", 0))
        is_ctrl = kept_l & (lsz_all <= hdr)
        trk_bytes_ctrl = trk_bytes_ctrl + jnp.sum(
            jnp.where(is_ctrl, lsz_all, 0), axis=1
        )
        trk_bytes_data = trk_bytes_data + jnp.sum(
            jnp.where(kept_l & ~is_ctrl, lsz_all, 0), axis=1
        )
        trk_retrans = trk_retrans + jnp.where(p3, rtx_count, 0)
    if cfg.use_dynamic_runahead:
        cross = kept_l & (dst != host_ids)[:, None] & (lat < TIME_MAX)[:, None]
        min_used = jnp.minimum(
            min_used, jnp.min(jnp.where(cross, lat[:, None], TIME_MAX))
        )

    events_handled = events_handled + take_tcp
    rng_counter = rng_counter + stride * take_tcp.astype(jnp.uint32)
    alive = alive & take

    return c.replace(
        q=q,
        net=net,
        ts=ts,
        mstate=mstate,
        obv=obv, obd=obd, obt=obt, obtie=obtie,
        obdata=obdata, obaux=obaux, obfill=obfill, obover=obover,
        f_time=f_time, f_tie=f_tie, f_kind=f_kind,
        f_data=f_data, f_aux=f_aux, f_head=f_head, f_cnt=f_cnt,
        seq=seq,
        rng_counter=rng_counter,
        events_handled=events_handled,
        packets_sent=packets_sent,
        packets_dropped=packets_dropped,
        packets_unroutable=packets_unroutable,
        trk_bytes_ctrl=trk_bytes_ctrl,
        trk_bytes_data=trk_bytes_data,
        trk_retrans=trk_retrans,
        min_used=min_used,
        alive=alive,
        rejected=rejected,
    )


def pump_carry_finish(
    st: SimState, c: PumpCarry, model, cfg: EngineConfig
) -> tuple[SimState, jax.Array]:
    """Merge the scanned carry back into the SimState: flush the leftover
    defer FIFO into the queue (one batched push; without the netstack the
    FIFO is provably empty — skip the lanes), rebuild the outbox, and
    merge the focus TcpState into the model pytree."""
    spec: TcpPumpSpec = model.pump_spec
    q = c.q
    if cfg.use_netstack:
        k = c.f_time.shape[1]
        lanes_live = (jnp.arange(k)[None, :] >= c.f_head[:, None]) & (
            jnp.arange(k)[None, :] < c.f_cnt[:, None]
        )
        with jax.named_scope(scopes.PUSH_SELF):
            q = equeue.push_self_lanes(
                q,
                valid=lanes_live,
                time=c.f_time,
                tie=c.f_tie,
                kind=c.f_kind,
                data=c.f_data,
                aux=c.f_aux,
            )

    ob = st.outbox.replace(
        valid=c.obv, dst=c.obd, time=c.obt, tie=c.obtie, data=c.obdata,
        aux=c.obaux, fill=c.obfill, overflow=c.obover,
    )
    mstate = spec.set_tcp(c.mstate, c.ts)
    st = st.replace(
        queue=q,
        net=c.net,
        model=mstate,
        outbox=ob,
        seq=c.seq,
        rng_counter=c.rng_counter,
        events_handled=c.events_handled,
        packets_sent=c.packets_sent,
        packets_dropped=c.packets_dropped,
        packets_unroutable=c.packets_unroutable,
        min_used_lat=c.min_used,
    )
    if cfg.tracker:
        st = st.replace(
            tracker=st.tracker.replace(
                bytes_ctrl=c.trk_bytes_ctrl,
                bytes_data=c.trk_bytes_data,
                retrans_segs=c.trk_retrans,
            )
        )
    return st, jnp.any(c.rejected)


def pump_stage(
    st: SimState,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    debug_out: "list | None" = None,
) -> tuple[SimState, jax.Array]:
    """Run up to cfg.pump_k pump microsteps per host (plain-XLA engine).

    Returns (state, any_rejected): any_rejected is True when some host's
    eligible head event failed classification this call — only then does
    the caller need to run the full handler this iteration (hosts whose
    chains simply exceeded pump_k keep pumping next iteration).

    Once every lane is dead (all chains ended before pump_k — the common
    case: typical chains run 2-3 events), the remaining microsteps take an
    identity `cond` branch that aliases the whole carry through unchanged
    instead of paying the full microstep arithmetic. Bit-exact: a
    microstep on an all-dead carry is the identity (every write is masked
    by `take`/`alive`, both all-False). The eager debug path keeps the
    plain loop — its per-step tallies need concrete values.
    """
    c = pump_carry_init(st, model, tables, cfg)
    if debug_out is not None:
        for _step in range(cfg.pump_k):
            c = pump_microstep(c, window_end, model, tables, cfg, debug_out)
        return pump_carry_finish(st, c, model, cfg)

    def step(c):
        return pump_microstep(c, window_end, model, tables, cfg)

    for _step in range(cfg.pump_k):
        c = jax.lax.cond(jnp.any(c.alive), step, lambda c: c, c)
    return pump_carry_finish(st, c, model, cfg)
