"""Vectorized TCP: the flow table as [H, S] tensor rows.

The reference implements TCP as a per-socket C state machine with
self-scheduling timers and closure-based retransmit queues (reference:
src/main/host/descriptor/tcp.c:38-2875 — states :38, per-socket struct
:118-247, send engine `_tcp_flush` :1265-1444, receive engine
`_tcp_processPacket` :2006-2372, RFC-6298 RTT :1135-1170, retransmit
timers :1062-1134, Reno congestion control tcp_cong_reno.c). A TPU-native
stack cannot chase pointers per socket; instead every field of every
socket of every host lives in one struct-of-arrays, and the three entry
points (segment arrival, timer expiry, app demand) are branch-free masked
updates over the active slot of each host.

Because the engine pops exactly one event per host per iteration, at most
one slot per host changes per call — demux/gather/scatter over the slot
axis (S small, e.g. 4) keeps everything data-parallel over hosts.

Semantics kept from the reference (re-specified, not translated):
  - the state machine: CLOSED/LISTEN/SYNSENT/SYNRECEIVED/ESTABLISHED/
    FINWAIT1/FINWAIT2/CLOSING/TIMEWAIT/CLOSEWAIT/LASTACK with TIMEWAIT
    expiring on a 60 s timer (tcp.c:660-780);
  - listener child-socket multiplexing: a SYN to a LISTEN slot allocates
    a fresh slot as the child connection (tcp.c:2087-2101);
  - byte-sequence send/receive windows, cumulative ACKs, out-of-order
    buffering (the tally's range bookkeeping, tcp_retransmit_tally.cc,
    becomes a fixed set of [start,end) ranges per socket);
  - RFC 6298 RTT/RTO in integer ns with Karn's rule, exponential backoff;
  - Reno: slow start, congestion avoidance, 3-dupack fast retransmit with
    NewReno partial-ACK hole repair (tcp_cong_reno.c);
  - lazy timer cancellation: one pending timer event per socket tracks
    the earliest deadline; stale wakeups re-arm (the reference's
    `desiredTimerExpiration`, tcp.c:1062-1134).

SACK (use_sack, default on): receivers advertise their lowest buffered
out-of-order range on every ACK (one full-precision block on wire lanes
6-7); senders keep a scoreboard of peer-reported ranges
(tcp_retransmit_tally.cc role), retransmit the first *unsacked* hole, and
march one hole per dupack during recovery — managed-tier parity
(hostk/tcp.py sacked/tally). A timeout clears the scoreboard (RFC 2018
reneging safety).

Remaining divergences, with reasons: no delayed ACKs (the managed tier
also ACKs immediately — matching it is the cross-tier contract); no
zero-window probes or receive-buffer accounting (scripted apps consume
instantly, so the advertised window is constant and can never close —
the persist machinery lives in the managed tier, hostk/tcp.py:414-439,
where real apps exist); deterministic ISS of 0 (both tiers; the
reference draws it from the host RNG — an unpredictability property with
no simulation-fidelity effect, since sequence numbers never leave the
simulation).

Sequence numbers are absolute i64 byte offsets internally (SYN occupies
offset 0, data starts at 1, FIN occupies the offset after the last data
byte); the wire carries the low 32 bits, unwrapped on receipt.
"""

from __future__ import annotations

import dataclasses

import flax.struct
import jax
import jax.numpy as jnp

from shadow_tpu import scopes
from shadow_tpu.equeue import PAYLOAD_LANES
from shadow_tpu.events import KIND_MODEL_BASE
from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC, TIME_MAX
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
    pack_flags_len,
    pack_ports,
    to_wire32,
    unpack_flags_len,
    unpack_ports,
    unwrap32,
)

# --- connection states (tcp.c:38-48) ---
CLOSED = 0
LISTEN = 1
SYNSENT = 2
SYNRECEIVED = 3
ESTABLISHED = 4
FINWAIT1 = 5
FINWAIT2 = 6
CLOSING = 7
TIMEWAIT = 8
CLOSEWAIT = 9
LASTACK = 10

# Event kinds owned by the TCP layer; models embedding TCP start their own
# kinds at TCP_KIND_USER_BASE.
KIND_TCP_TIMER = KIND_MODEL_BASE + 0
KIND_TCP_FLUSH = KIND_MODEL_BASE + 1
TCP_KIND_USER_BASE = KIND_MODEL_BASE + 8


@dataclasses.dataclass(frozen=True)
class TcpParams:
    """Static TCP parameters (units: bytes, ns)."""

    num_sockets: int = 4  # S: socket slots per host
    mss: int = 1460
    header_bytes: int = 40  # IPv4 + TCP header overhead added to wire size
    rcv_wnd: int = 256 * 1024  # advertised window (autotuning: future work)
    init_cwnd_segs: int = 10
    rto_init_ns: int = NS_PER_SEC  # RFC 6298 initial RTO
    rto_min_ns: int = 200 * NS_PER_MS  # Linux-style floor
    rto_max_ns: int = 60 * NS_PER_SEC
    granularity_ns: int = NS_PER_MS
    timewait_ns: int = 60 * NS_PER_SEC  # tcp.c:771 close timer
    ooo_ranges: int = 4  # R: out-of-order ranges buffered per socket
    segs_per_flush: int = 4  # data segments emitted per handler call
    # SACK (tcp_retransmit_tally.cc role): receivers advertise their first
    # out-of-order range on every ACK; senders keep a scoreboard of sacked
    # ranges and retransmit the first *unsacked* hole instead of blindly
    # resending at snd_una (managed-tier parity, hostk/tcp.py sacked/tally)
    use_sack: bool = True

    @property
    def packet_lanes(self) -> int:
        # data segments + one control lane (ACK / RST / dup-ACK)
        return self.segs_per_flush + 1

    @property
    def local_lanes(self) -> int:
        # flush continuation + timer maintenance
        return 2


@flax.struct.dataclass
class TcpState:
    """All fields [H, S] unless noted. i64 seq fields are absolute offsets."""

    st: jax.Array  # i32 connection state
    lport: jax.Array  # i32 local port
    rport: jax.Array  # i32 remote port
    rhost: jax.Array  # i32 remote *global* host id (-1 none)
    # send machine
    snd_una: jax.Array  # i64 oldest unacked
    snd_nxt: jax.Array  # i64 next to send (rewinds on RTO)
    snd_max: jax.Array  # i64 highest ever sent (does not rewind)
    snd_end: jax.Array  # i64 end of app data written so far
    fin_pending: jax.Array  # bool app closed; FIN goes out after snd_end
    fin_sent: jax.Array  # bool our FIN has been transmitted at least once
    peer_wnd: jax.Array  # i64 peer's advertised window
    # receive machine
    rcv_nxt: jax.Array  # i64 next expected
    rcv_fin: jax.Array  # i64 peer FIN offset (-1 unknown)
    delivered: jax.Array  # i64 bytes handed to the app in order
    ooo: jax.Array  # [H, S, R, 2] i64 out-of-order [start, end); -1 empty
    # sender-side SACK scoreboard: peer-reported received ranges above
    # snd_una (the vectorized tally, tcp_retransmit_tally.cc)
    sacked: jax.Array  # [H, S, R, 2] i64 [start, end); -1 empty
    # highest hole already retransmitted this recovery episode — each hole
    # is resent once per episode (the managed tier's _last_rexmit marks);
    # without it a rtx's own dupack re-triggers the march forever
    rtx_mark: jax.Array  # i64
    # congestion control (Reno/NewReno)
    cwnd: jax.Array  # i64 bytes
    ssthresh: jax.Array  # i64 bytes
    dupacks: jax.Array  # i32
    recover: jax.Array  # i64 NewReno recovery point
    in_rec: jax.Array  # bool in fast recovery
    # RTT / RTO (RFC 6298, integer ns)
    srtt: jax.Array  # i64 (-1 = no sample yet)
    rttvar: jax.Array  # i64
    rto: jax.Array  # i64 current RTO
    rtt_pending: jax.Array  # bool a segment is being timed (Karn)
    rtt_seq: jax.Array  # i64 ack that completes the timed sample
    rtt_ts: jax.Array  # i64 send time of the timed segment
    # timer machinery
    rto_expire: jax.Array  # i64 pending RTO (or TIMEWAIT) deadline; TIME_MAX none
    backoff: jax.Array  # i32 consecutive RTOs
    tev_time: jax.Array  # i64 earliest outstanding timer *event*; TIME_MAX none
    # stats (tracker feed)
    retransmits: jax.Array  # i64
    segs_in: jax.Array  # i64
    segs_out: jax.Array  # i64


def create(num_hosts: int, p: TcpParams) -> TcpState:
    h, s, r = num_hosts, p.num_sockets, p.ooo_ranges

    def z(dt=jnp.int64):
        return jnp.zeros((h, s), dt)

    def full(v, dt=jnp.int64):
        return jnp.full((h, s), v, dt)

    return TcpState(
        st=z(jnp.int32),
        lport=z(jnp.int32),
        rport=z(jnp.int32),
        rhost=full(-1, jnp.int32),
        snd_una=z(),
        snd_nxt=z(),
        snd_max=z(),
        snd_end=full(1),  # data starts after the SYN at offset 0
        fin_pending=z(bool),
        fin_sent=z(bool),
        peer_wnd=full(p.rcv_wnd),
        rcv_nxt=z(),
        rcv_fin=full(-1),
        delivered=z(),
        ooo=jnp.full((h, s, r, 2), -1, jnp.int64),
        sacked=jnp.full((h, s, r, 2), -1, jnp.int64),
        rtx_mark=z(),
        cwnd=full(p.init_cwnd_segs * p.mss),
        ssthresh=full(1 << 40),
        dupacks=z(jnp.int32),
        recover=z(),
        in_rec=z(bool),
        srtt=full(-1),
        rttvar=z(),
        rto=full(p.rto_init_ns),
        rtt_pending=z(bool),
        rtt_seq=z(),
        rtt_ts=z(),
        rto_expire=full(TIME_MAX),
        backoff=z(jnp.int32),
        tev_time=full(TIME_MAX),
        retransmits=z(),
        segs_in=z(),
        segs_out=z(),
    )


# --- slot gather/scatter -------------------------------------------------


def _g(a: jax.Array, slot: jax.Array) -> jax.Array:
    """a[h, slot[h], ...] for every host h.

    One-hot masked reduction rather than take_along_axis: gather HLOs do
    not fuse on TPU (each costs a fixed dispatch, and gather_slot touches
    every TcpState field), while the mask+select+sum chain fuses across
    all fields into one pass. S is tiny, so the redundant reads are free.
    """
    onehot = jnp.arange(a.shape[1])[None, :] == slot[:, None]  # [H, S]
    oh = onehot.reshape(onehot.shape + (1,) * (a.ndim - 2))
    if a.dtype == jnp.bool_:
        return jnp.any(oh & a, axis=1)
    return jnp.sum(jnp.where(oh, a, 0), axis=1).astype(a.dtype)


def _s(a: jax.Array, slot: jax.Array, mask: jax.Array, new: jax.Array) -> jax.Array:
    """a[h, slot[h], ...] = new[h, ...] where mask[h]."""
    onehot = (jnp.arange(a.shape[1])[None, :] == slot[:, None]) & mask[:, None]
    oh = onehot.reshape(onehot.shape + (1,) * (a.ndim - 2))
    return jnp.where(oh, jnp.expand_dims(new, 1), a)


def gather_slot(ts: TcpState, slot: jax.Array) -> TcpState:
    """View of one slot per host (leaves lose the S axis)."""
    return jax.tree.map(lambda a: _g(a, slot), ts)


def scatter_slot(ts: TcpState, slot: jax.Array, mask: jax.Array, view: TcpState) -> TcpState:
    return jax.tree.map(lambda a, v: _s(a, slot, mask, v), ts, view)


def _reset_view(v: TcpState, m, p: TcpParams) -> TcpState:
    """Reinitialize every per-connection field of the view where `m` —
    slots are reused after CLOSED, so stale send/recv/cc state must never
    leak into a new connection (tcp.c allocates a fresh struct per socket;
    tensor rows are recycled instead)."""

    def w(cur, fresh):
        fresh = jnp.broadcast_to(jnp.asarray(fresh, cur.dtype), cur.shape)
        if cur.ndim > m.ndim:
            mm = m.reshape(m.shape + (1,) * (cur.ndim - m.ndim))
        else:
            mm = m
        return jnp.where(mm, fresh, cur)

    return v.replace(
        snd_una=w(v.snd_una, 0),
        snd_nxt=w(v.snd_nxt, 0),
        snd_max=w(v.snd_max, 0),
        snd_end=w(v.snd_end, 1),
        fin_pending=w(v.fin_pending, False),
        fin_sent=w(v.fin_sent, False),
        peer_wnd=w(v.peer_wnd, p.rcv_wnd),
        rcv_nxt=w(v.rcv_nxt, 0),
        rcv_fin=w(v.rcv_fin, -1),
        delivered=w(v.delivered, 0),
        ooo=w(v.ooo, -1),
        sacked=w(v.sacked, -1),
        rtx_mark=w(v.rtx_mark, 0),
        cwnd=w(v.cwnd, p.init_cwnd_segs * p.mss),
        ssthresh=w(v.ssthresh, 1 << 40),
        dupacks=w(v.dupacks, 0),
        recover=w(v.recover, 0),
        in_rec=w(v.in_rec, False),
        srtt=w(v.srtt, -1),
        rttvar=w(v.rttvar, 0),
        rto=w(v.rto, p.rto_init_ns),
        rtt_pending=w(v.rtt_pending, False),
        rtt_seq=w(v.rtt_seq, 0),
        rtt_ts=w(v.rtt_ts, 0),
        rto_expire=w(v.rto_expire, TIME_MAX),
        backoff=w(v.backoff, 0),
    )


# --- app-side operations (the socket API surface) ------------------------


def listen(ts: TcpState, mask, slot, port) -> TcpState:
    """bind+listen on `port` at slot (tcp.c:1652-1700 connect/accept side)."""
    v = gather_slot(ts, slot)
    v = v.replace(
        st=jnp.where(mask, LISTEN, v.st),
        lport=jnp.where(mask, port, v.lport),
    )
    return scatter_slot(ts, slot, mask, v)


def connect(ts: TcpState, mask, slot, lport, rhost, rport, p: TcpParams) -> TcpState:
    """Active open: the SYN itself is emitted by the next output pass."""
    v = gather_slot(ts, slot)
    m = mask & (v.st == CLOSED)
    v = _reset_view(v, m, p)
    v = v.replace(
        st=jnp.where(m, SYNSENT, v.st),
        lport=jnp.where(m, lport, v.lport),
        rport=jnp.where(m, rport, v.rport),
        rhost=jnp.where(m, rhost, v.rhost),
    )
    return scatter_slot(ts, slot, m, v)


def app_write(ts: TcpState, mask, slot, nbytes) -> TcpState:
    """Queue nbytes of app data (tcp_sendUserData, tcp.c:2401). Only byte
    *counts* are simulated; managed-process payload bytes live CPU-side."""
    v = gather_slot(ts, slot)
    m = mask & (v.st != CLOSED) & (v.st != LISTEN) & ~v.fin_pending
    v = v.replace(snd_end=jnp.where(m, v.snd_end + nbytes, v.snd_end))
    return scatter_slot(ts, slot, m, v)


def app_close(ts: TcpState, mask, slot) -> TcpState:
    """Half-close: FIN after all queued data (tcp.c:1751-1771)."""
    v = gather_slot(ts, slot)
    m = mask & (v.st != CLOSED) & (v.st != LISTEN)
    v = v.replace(fin_pending=jnp.where(m, True, v.fin_pending))
    return scatter_slot(ts, slot, m, v)


# --- RTT / RTO (RFC 6298, tcp.c:1135-1170) -------------------------------


def ca_increment(mss: int, cwnd: jax.Array) -> jax.Array:
    """mss * mss // max(cwnd, 1), the congestion-avoidance step, computed
    in int32: mss squared fits, and a divisor clamped to mss * mss + 1
    gives the same quotient (0) as any larger window. Exact, and spares
    the chip's compiler an int64 divide (intmath.py)."""
    sq = mss * mss
    div = jnp.clip(cwnd, 1, sq + 1).astype(jnp.int32)
    return (jnp.int32(sq) // div).astype(jnp.int64)


def _rtt_update(v: TcpState, m, rtt, p: TcpParams) -> TcpState:
    first = v.srtt < 0
    # `>> k` IS floor division by 2**k on signed ints; an int64 `//`
    # costs the chip's compiler tens of seconds each (intmath.py)
    rttvar1 = jnp.where(first, rtt >> 1, (3 * v.rttvar + jnp.abs(v.srtt - rtt)) >> 2)
    srtt1 = jnp.where(first, rtt, (7 * v.srtt + rtt) >> 3)
    rto1 = jnp.clip(
        srtt1 + jnp.maximum(p.granularity_ns, 4 * rttvar1), p.rto_min_ns, p.rto_max_ns
    )
    return v.replace(
        srtt=jnp.where(m, srtt1, v.srtt),
        rttvar=jnp.where(m, rttvar1, v.rttvar),
        rto=jnp.where(m, rto1, v.rto),
        rtt_pending=jnp.where(m, False, v.rtt_pending),
    )


# --- out-of-order range set ----------------------------------------------


def _ooo_absorb(rcv_nxt, ooo, m):
    """Advance rcv_nxt over any buffered ranges it now reaches; clear them.
    (The receive-side reassembly the reference keeps in unorderedInput +
    the tally's range merge, tcp.c:2197-2235.)"""
    r = ooo.shape[1]
    for _ in range(r):
        start, end = ooo[:, :, 0], ooo[:, :, 1]
        hit = m[:, None] & (start >= 0) & (start <= rcv_nxt[:, None])
        reach = jnp.max(jnp.where(hit, end, -1), axis=1)
        rcv_nxt = jnp.maximum(rcv_nxt, reach)
        ooo = jnp.where(hit[:, :, None], jnp.int64(-1), ooo)
    return rcv_nxt, ooo


def _ooo_insert(ooo, m, s, e):
    """Merge-insert [s, e) into the range set; drop if full and disjoint."""
    start, end = ooo[:, :, 0], ooo[:, :, 1]
    empty = start < 0
    overlap = m[:, None] & ~empty & (s[:, None] <= end) & (e[:, None] >= start)
    ms = jnp.minimum(s, jnp.min(jnp.where(overlap, start, jnp.int64(1) << 60), axis=1))
    me = jnp.maximum(e, jnp.max(jnp.where(overlap, end, -1), axis=1))
    avail = overlap | (empty & m[:, None])
    ins = jnp.argmax(avail, axis=1)
    can = jnp.any(avail, axis=1) & m
    cleared = jnp.where(overlap[:, :, None], jnp.int64(-1), ooo)
    merged = jnp.stack([ms, me], axis=-1)  # [H, 2]
    at = (jnp.arange(ooo.shape[1])[None, :] == ins[:, None]) & can[:, None]
    return jnp.where(at[:, :, None], merged[:, None, :], cleared)


# --- fused-view app intents ----------------------------------------------


@flax.struct.dataclass
class AppOpen:
    """Pre-TCP application intents for this event, applied on the fused
    view (connect + optional write/close, the tgen/bulk stream-start
    pattern). `slot` becomes the event's focus slot when `mask`; all
    other fields are ignored where ~mask."""

    mask: jax.Array  # [H] bool
    slot: jax.Array  # [H] i32
    lport: jax.Array  # [H] i32
    rhost: jax.Array  # [H] i32
    rport: jax.Array  # [H] i32
    write_bytes: jax.Array  # [H] i64 (0 = none)
    close: jax.Array  # [H] bool half-close right after the write


def no_app_open(h: int) -> AppOpen:
    z32 = jnp.zeros((h,), jnp.int32)
    return AppOpen(
        mask=jnp.zeros((h,), bool), slot=z32, lport=z32, rhost=z32, rport=z32,
        write_bytes=jnp.zeros((h,), jnp.int64), close=jnp.zeros((h,), bool),
    )


def view_write(v: TcpState, mask, nbytes) -> TcpState:
    """app_write on a fused view (tcp_sendUserData, tcp.c:2401)."""
    m = mask & (v.st != CLOSED) & (v.st != LISTEN) & ~v.fin_pending
    return v.replace(snd_end=jnp.where(m, v.snd_end + nbytes, v.snd_end))


def view_close(v: TcpState, mask) -> TcpState:
    """app_close on a fused view (half-close, tcp.c:1751-1771)."""
    m = mask & (v.st != CLOSED) & (v.st != LISTEN)
    return v.replace(fin_pending=jnp.where(m, True, v.fin_pending))


@scopes.scoped(scopes.TCP)
def commit_slot(ts: TcpState, slot, touched, view: TcpState) -> TcpState:
    """Write the fused view back — the ONE scatter of the whole event."""
    return scatter_slot(ts, slot, touched, view)


# --- emissions ------------------------------------------------------------


@flax.struct.dataclass
class TcpEmits:
    """Packet lanes [H, EP] + local-event lanes [H, 2]."""

    p_valid: jax.Array
    p_dst: jax.Array
    p_data: jax.Array  # [H, EP, PAYLOAD_LANES]
    p_size: jax.Array
    l_valid: jax.Array
    l_time: jax.Array
    l_kind: jax.Array
    l_data: jax.Array  # [H, 2, PAYLOAD_LANES]


@flax.struct.dataclass
class TcpSignals:
    """Per-host edges for the embedding model, all referring to `slot`."""

    slot: jax.Array  # i32 the slot this invocation acted on (-1 none)
    established: jax.Array  # bool rose to ESTABLISHED this call
    fin_seen: jax.Array  # bool peer FIN consumed (EOF readable)
    closed: jax.Array  # bool reached CLOSED this call
    reset: jax.Array  # bool killed by RST


def _empty_emits(h: int, p: TcpParams) -> TcpEmits:
    ep = p.packet_lanes
    return TcpEmits(
        p_valid=jnp.zeros((h, ep), bool),
        p_dst=jnp.zeros((h, ep), jnp.int32),
        p_data=jnp.zeros((h, ep, PAYLOAD_LANES), jnp.int32),
        p_size=jnp.zeros((h, ep), jnp.int32),
        l_valid=jnp.zeros((h, 2), bool),
        l_time=jnp.zeros((h, 2), jnp.int64),
        l_kind=jnp.zeros((h, 2), jnp.int32),
        l_data=jnp.zeros((h, 2, PAYLOAD_LANES), jnp.int32),
    )


def _mk_seg(lport, rport, seq, ack, flags, plen, wnd, sack_s=None, sack_e=None):
    """Build one segment's payload lanes ([H, PAYLOAD_LANES]).

    LANE_APP (lane 5) is deliberately left zero: embedding models demux
    their own control packets from TCP segments by a nonzero value there
    (transport/header.py lane contract; models/overlay/onion.py SETUP
    cells) — writing it here would silently break that demux."""
    h = lport.shape[0]
    data = jnp.zeros((h, PAYLOAD_LANES), jnp.int32)
    data = data.at[:, LANE_PORTS].set(pack_ports(lport, rport))
    data = data.at[:, LANE_SEQ].set(to_wire32(seq))
    data = data.at[:, LANE_ACK].set(to_wire32(ack))
    data = data.at[:, LANE_FLAGS_LEN].set(pack_flags_len(flags, plen))
    data = data.at[:, LANE_WND].set(wnd.astype(jnp.int32))
    if sack_s is not None:
        data = data.at[:, LANE_SACK_S].set(to_wire32(sack_s))
        data = data.at[:, LANE_SACK_E].set(to_wire32(sack_e))
    return data


# --- the unified handler --------------------------------------------------


@scopes.scoped(scopes.TCP)
def tcp_handle(
    ts: TcpState,
    ev,
    host_id: jax.Array,
    p: TcpParams,
    is_tcp_packet: jax.Array,
    app: AppOpen | None = None,
):
    """Process one event per host through the TCP machine, on a single
    fused slot view.

    `ev` is the engine's Popped batch; `is_tcp_packet` marks hosts whose
    popped event is a TCP segment (the embedding model decides — e.g. it
    may also run UDP traffic). Timer events (KIND_TCP_TIMER) are detected
    here. `app` carries pre-TCP application intents (connect/write/close
    on a model-chosen slot, e.g. a stream start).

    Every phase of one event acts on ONE slot per host — the spawned
    child, the rx match, the timer/flush slot, or the app's slot (event
    kinds are mutually exclusive per pop) — so the whole handler runs on
    one gathered view and the caller writes it back with a single
    commit_slot. The previous shape (gather/scatter around every phase,
    plus the model's connect/app_write/app_close each doing their own
    pair) made the handler ~15k HLO ops and the pop-iteration ~6-9 ms on
    TPU; the fused view is the op-count fix, with identical semantics.

    Returns (focus_slot, touched, view, TcpEmits, TcpSignals,
    delivered_open) — the caller applies its post-TCP actions on the view
    (view_write/view_close) and MUST call commit_slot(ts, focus_slot,
    touched, view). `delivered_open` is the view's delivered counter
    right after the spawn/app-open phase (byte-accounting baseline).
    """
    h = host_id.shape[0]
    now = ev.time
    mss = jnp.int64(p.mss)
    emits = _empty_emits(h, p)
    if app is None:
        app = no_app_open(h)

    m_rx = is_tcp_packet & ev.valid
    m_tmr = ev.valid & (ev.kind == KIND_TCP_TIMER)
    m_flush = ev.valid & (ev.kind == KIND_TCP_FLUSH)

    # ---------------- RX: demux ------------------------------------------
    sport, dport = unpack_ports(ev.data[:, LANE_PORTS])
    src = ev.src_host
    exact = (
        (ts.st != CLOSED)
        & (ts.st != LISTEN)
        & (ts.lport == dport[:, None])
        & (ts.rhost == src[:, None])
        & (ts.rport == sport[:, None])
    )
    lsn = (ts.st == LISTEN) & (ts.lport == dport[:, None])
    score = exact * 2 + lsn  # [H, S]
    rx_slot = jnp.argmax(score, axis=1).astype(jnp.int32)
    rx_match = m_rx & (jnp.max(score, axis=1) > 0)
    rx_exact = m_rx & jnp.any(exact, axis=1)
    rx_listen = rx_match & ~rx_exact

    flags, plen = unpack_flags_len(ev.data[:, LANE_FLAGS_LEN])
    f_syn = (flags & FLAG_SYN) != 0
    f_ack = (flags & FLAG_ACK) != 0
    f_fin = (flags & FLAG_FIN) != 0
    f_rst = (flags & FLAG_RST) != 0
    wnd = ev.data[:, LANE_WND].astype(jnp.int64)

    # --- passive open: SYN to a listener spawns a child slot -------------
    # (tcp.c:2087-2101; the child registers under (peer ip, peer port))
    m_spawn = rx_listen & f_syn & ~f_ack
    free = ts.st == CLOSED
    child = jnp.argmax(free, axis=1).astype(jnp.int32)
    m_spawn = m_spawn & jnp.any(free, axis=1)  # backlog full -> drop
    act_slot = jnp.where(m_spawn, child, rx_slot)
    m_act = rx_exact | m_spawn

    # --- the focus slot: the one slot this event acts on, all phases -----
    t_slot = jnp.clip(ev.data[:, 0].astype(jnp.int32), 0, p.num_sockets - 1)
    focus = jnp.where(
        m_act,
        act_slot,
        jnp.where(m_tmr | m_flush, t_slot, app.slot),
    ).astype(jnp.int32)
    v = gather_slot(ts, focus)  # the ONE gather

    # spawn init (recycled slots must start clean)
    v = _reset_view(v, m_spawn, p)
    v = v.replace(
        st=jnp.where(m_spawn, SYNRECEIVED, v.st),
        lport=jnp.where(m_spawn, dport, v.lport),
        rport=jnp.where(m_spawn, sport, v.rport),
        rhost=jnp.where(m_spawn, src, v.rhost),
        rcv_nxt=jnp.where(m_spawn, jnp.int64(1), v.rcv_nxt),
        peer_wnd=jnp.where(m_spawn, wnd, v.peer_wnd),
    )

    # app open: connect (+ optional write/close) on the app's slot
    m_conn = app.mask & (v.st == CLOSED)
    v = _reset_view(v, m_conn, p)
    v = v.replace(
        st=jnp.where(m_conn, SYNSENT, v.st),
        lport=jnp.where(m_conn, app.lport, v.lport),
        rport=jnp.where(m_conn, app.rport, v.rport),
        rhost=jnp.where(m_conn, app.rhost, v.rhost),
    )
    v = view_write(v, app.mask & (app.write_bytes > 0), app.write_bytes)
    v = view_close(v, app.mask & app.close)
    delivered_open = v.delivered

    # --- established-path processing on the focus view -------------------
    v = v.replace(segs_in=v.segs_in + m_act)

    abs_seq = unwrap32(v.rcv_nxt, ev.data[:, LANE_SEQ])
    abs_ack = unwrap32(v.snd_una, ev.data[:, LANE_ACK])

    sig_est = jnp.zeros((h,), bool)
    sig_rst = jnp.zeros((h,), bool)
    sig_fin = jnp.zeros((h,), bool)
    sig_closed = jnp.zeros((h,), bool)

    # RST kills the connection (tcp.c:2020-2035)
    m_rst = rx_exact & f_rst & (v.st != CLOSED)
    v = v.replace(
        st=jnp.where(m_rst, CLOSED, v.st),
        rto_expire=jnp.where(m_rst, TIME_MAX, v.rto_expire),
    )
    sig_rst = sig_rst | m_rst
    live = m_act & ~m_rst

    # SYNSENT: SYN|ACK completes the active open
    m_sa = live & (v.st == SYNSENT) & f_syn & f_ack & (abs_ack >= 1)
    v = v.replace(
        st=jnp.where(m_sa, ESTABLISHED, v.st),
        rcv_nxt=jnp.where(m_sa, jnp.int64(1), v.rcv_nxt),
        snd_una=jnp.where(m_sa, jnp.int64(1), v.snd_una),
        peer_wnd=jnp.where(m_sa, wnd, v.peer_wnd),
        rto_expire=jnp.where(m_sa, TIME_MAX, v.rto_expire),
        backoff=jnp.where(m_sa, 0, v.backoff),
    )
    m_sa_rtt = m_sa & v.rtt_pending
    v = _rtt_update(v, m_sa_rtt, now - v.rtt_ts, p)
    sig_est = sig_est | m_sa
    need_ack = m_sa  # ACK the SYN|ACK

    # SYNRECEIVED: the handshake-completing ACK
    m_sr = live & (v.st == SYNRECEIVED) & f_ack & ~f_syn & (abs_ack >= 1)
    v = v.replace(
        st=jnp.where(m_sr, ESTABLISHED, v.st),
        snd_una=jnp.where(m_sr, jnp.maximum(v.snd_una, jnp.int64(1)), v.snd_una),
        peer_wnd=jnp.where(m_sr, wnd, v.peer_wnd),
        rto_expire=jnp.where(m_sr, TIME_MAX, v.rto_expire),
        backoff=jnp.where(m_sr, 0, v.backoff),
    )
    m_sr_rtt = m_sr & v.rtt_pending
    v = _rtt_update(v, m_sr_rtt, now - v.rtt_ts, p)
    sig_est = sig_est | m_sr

    # data-bearing states
    datast = (
        (v.st == ESTABLISHED)
        | (v.st == FINWAIT1)
        | (v.st == FINWAIT2)
        | (v.st == CLOSING)
        | (v.st == TIMEWAIT)
        | (v.st == CLOSEWAIT)
        | (v.st == LASTACK)
    )
    m_data_st = live & datast

    # ---- ACK processing (tcp.c:2237-2330 + tcp_cong_reno.c) ----
    m_ackp = m_data_st & f_ack
    snd_una_pre = v.snd_una  # dupack detection is against the pre-ACK state
    valid_ack = m_ackp & (abs_ack > v.snd_una) & (abs_ack <= v.snd_max)
    acked = jnp.where(valid_ack, abs_ack - v.snd_una, 0)

    # RTT sample (Karn: only if the timed segment is covered and never rtx'd)
    m_rtt = valid_ack & v.rtt_pending & (abs_ack >= v.rtt_seq)
    v = _rtt_update(v, m_rtt, now - v.rtt_ts, p)

    # NewReno recovery accounting
    full_ack = valid_ack & v.in_rec & (abs_ack >= v.recover)
    part_ack = valid_ack & v.in_rec & ~full_ack
    # slow start / congestion avoidance outside recovery
    ss = valid_ack & ~v.in_rec & (v.cwnd < v.ssthresh)
    ca = valid_ack & ~v.in_rec & ~ss
    cwnd1 = jnp.where(ss, v.cwnd + jnp.minimum(acked, mss), v.cwnd)
    cwnd1 = jnp.where(ca, cwnd1 + jnp.maximum(ca_increment(p.mss, cwnd1), 1), cwnd1)
    cwnd1 = jnp.where(full_ack, v.ssthresh, cwnd1)
    # partial ack: deflate by amount acked, inflate by one MSS, stay in rec
    cwnd1 = jnp.where(part_ack, jnp.maximum(cwnd1 - acked + mss, mss), cwnd1)
    rtx_hole = part_ack  # retransmit the next hole right away

    v = v.replace(
        snd_una=jnp.where(valid_ack, abs_ack, v.snd_una),
        snd_nxt=jnp.where(valid_ack, jnp.maximum(v.snd_nxt, abs_ack), v.snd_nxt),
        cwnd=cwnd1,
        in_rec=jnp.where(full_ack, False, v.in_rec),
        dupacks=jnp.where(valid_ack, 0, v.dupacks),
        backoff=jnp.where(valid_ack, 0, v.backoff),
        peer_wnd=jnp.where(m_ackp, wnd, v.peer_wnd),
    )
    # re-arm or clear the RTO on forward progress
    outstanding = v.snd_una < v.snd_max
    v = v.replace(
        rto_expire=jnp.where(
            valid_ack, jnp.where(outstanding, now + v.rto, TIME_MAX), v.rto_expire
        )
    )

    # ---- SACK scoreboard update (tcp_retransmit_tally.cc role) ----
    # Merge the peer-reported block in, then drop ranges the cumulative
    # ACK has covered. Unwrap is relative to the post-advance snd_una.
    if p.use_sack:
        sack_s_w = ev.data[:, LANE_SACK_S]
        sack_e_w = ev.data[:, LANE_SACK_E]
        has_sack = m_ackp & (sack_s_w != sack_e_w)
        abs_ss = unwrap32(v.snd_una, sack_s_w)
        abs_se = unwrap32(v.snd_una, sack_e_w)
        sacked1 = _ooo_insert(v.sacked, has_sack, abs_ss, abs_se)
        drop = m_ackp[:, None] & (sacked1[:, :, 0] >= 0) & (
            sacked1[:, :, 1] <= v.snd_una[:, None]
        )
        v = v.replace(sacked=jnp.where(drop[:, :, None], jnp.int64(-1), sacked1))

    # duplicate ACKs -> fast retransmit at 3 (tcp_cong_reno.c). A dupack is
    # a pure ACK that does NOT advance snd_una (checked against the pre-ACK
    # value — the advancing ACK itself must not count).
    dup = (
        m_ackp & ~valid_ack & (abs_ack == snd_una_pre) & (plen == 0) & ~f_fin & outstanding
    )
    dup3 = dup & (v.dupacks == 2) & ~v.in_rec
    flight = v.snd_max - v.snd_una
    v = v.replace(
        dupacks=jnp.where(dup, v.dupacks + 1, v.dupacks),
        ssthresh=jnp.where(dup3, jnp.maximum(flight >> 1, 2 * mss), v.ssthresh),
        cwnd=jnp.where(
            dup3,
            jnp.maximum(flight >> 1, 2 * mss) + 3 * mss,
            jnp.where(dup & v.in_rec, v.cwnd + mss, v.cwnd),
        ),
        recover=jnp.where(dup3, v.snd_max, v.recover),
        in_rec=jnp.where(dup3, True, v.in_rec),
    )
    if p.use_sack:
        # first unsacked hole per the tally (same march the output pass
        # performs — state is unchanged in between, so the values agree)
        hole_rx = v.snd_una
        for _ in range(p.ooo_ranges):
            cover = (
                (v.sacked[:, :, 0] >= 0)
                & (v.sacked[:, :, 0] <= hole_rx[:, None])
                & (v.sacked[:, :, 1] > hole_rx[:, None])
            )
            reach = jnp.max(
                jnp.where(cover, v.sacked[:, :, 1], jnp.int64(-1)), axis=1
            )
            hole_rx = jnp.maximum(hole_rx, reach)
        # march one hole per dupack while in recovery when the scoreboard
        # has information — but each hole only once per episode (the
        # managed tier's _last_rexmit marks; hostk/tcp.py parity)
        sack_any = jnp.any(v.sacked[:, :, 0] >= 0, axis=1)
        march = (
            dup & v.in_rec & sack_any
            & (hole_rx > v.rtx_mark)
            & (hole_rx < v.snd_max)
        )
        rtx_hole = rtx_hole | dup3 | march
        v = v.replace(
            rtx_mark=jnp.where(
                full_ack, 0, jnp.where(rtx_hole, hole_rx, v.rtx_mark)
            )
        )
    else:
        rtx_hole = rtx_hole | dup3

    # our FIN acked? (snd_limit = snd_end + 1 once the FIN is out)
    fin_acked = m_ackp & v.fin_sent & (v.snd_una >= v.snd_end + 1)
    v = v.replace(
        st=jnp.where(
            fin_acked & (v.st == FINWAIT1),
            FINWAIT2,
            jnp.where(
                fin_acked & (v.st == CLOSING),
                TIMEWAIT,
                jnp.where(fin_acked & (v.st == LASTACK), CLOSED, v.st),
            ),
        ),
    )
    sig_closed = sig_closed | (fin_acked & (v.st == CLOSED))
    enter_tw_ack = fin_acked & (v.st == TIMEWAIT)

    # ---- in-window data (tcp.c:2197-2235) ----
    seg_has_data = plen > 0
    m_seg = m_data_st & seg_has_data
    seg_s, seg_e = abs_seq, abs_seq + plen.astype(jnp.int64)
    acceptable = m_seg & (seg_e > v.rcv_nxt) & (seg_s <= v.rcv_nxt + p.rcv_wnd)
    in_order = acceptable & (seg_s <= v.rcv_nxt)
    ooo_seg = acceptable & ~in_order

    old_rcv = v.rcv_nxt
    rcv1 = jnp.where(in_order, seg_e, v.rcv_nxt)
    rcv1, ooo1 = _ooo_absorb(rcv1, v.ooo, in_order)
    ooo1 = _ooo_insert(ooo1, ooo_seg, seg_s, seg_e)
    v = v.replace(
        rcv_nxt=rcv1,
        ooo=ooo1,
        delivered=v.delivered + jnp.where(m_seg, rcv1 - old_rcv, 0),
    )
    need_ack = need_ack | m_seg  # data (incl. dup/ooo) always draws an ACK

    # ---- peer FIN (tcp.c FIN processing in _tcp_processPacket) ----
    m_finp = m_data_st & f_fin
    fin_off = seg_e  # FIN sits after this segment's data (or at abs_seq)
    v = v.replace(rcv_fin=jnp.where(m_finp & (v.rcv_fin < 0), fin_off, v.rcv_fin))
    fin_now = m_data_st & (v.rcv_fin >= 0) & (v.rcv_nxt == v.rcv_fin)
    v = v.replace(rcv_nxt=jnp.where(fin_now, v.rcv_nxt + 1, v.rcv_nxt))
    st_after_fin = jnp.where(
        fin_now & (v.st == ESTABLISHED),
        CLOSEWAIT,
        jnp.where(
            fin_now & (v.st == FINWAIT2),
            TIMEWAIT,
            jnp.where(fin_now & (v.st == FINWAIT1), CLOSING, v.st),
        ),
    )
    enter_tw_fin = fin_now & (st_after_fin == TIMEWAIT) & (v.st != TIMEWAIT)
    v = v.replace(st=st_after_fin)
    sig_fin = sig_fin | fin_now
    need_ack = need_ack | m_finp

    # TIMEWAIT timer (60 s, tcp.c:771); reuses rto_expire — no retransmits
    # are pending once both FINs are through.
    enter_tw = enter_tw_ack | enter_tw_fin
    v = v.replace(rto_expire=jnp.where(enter_tw, now + p.timewait_ns, v.rto_expire))

    # --- RST for unmatched segments (tcp.c sends RST to strays) ----------
    m_stray = m_rx & ~rx_match & ~f_rst
    rst_data = _mk_seg(
        dport,
        sport,
        unwrap32(jnp.int64(0), ev.data[:, LANE_ACK]),
        abs_seq + plen.astype(jnp.int64) + f_syn + f_fin,
        jnp.full((h,), FLAG_RST | FLAG_ACK, jnp.int32),
        jnp.zeros((h,), jnp.int32),
        jnp.zeros((h,), jnp.int64),
    )

    # ---------------- TIMER events (focus == t_slot when m_tmr) ----------
    v = v.replace(tev_time=jnp.where(m_tmr & (now >= v.tev_time), TIME_MAX, v.tev_time))
    fired = m_tmr & (now >= v.rto_expire) & (v.rto_expire < TIME_MAX)

    # TIMEWAIT expiry -> CLOSED
    tw_done = fired & (v.st == TIMEWAIT)
    v = v.replace(
        st=jnp.where(tw_done, CLOSED, v.st),
        rto_expire=jnp.where(tw_done, TIME_MAX, v.rto_expire),
    )
    sig_closed = sig_closed | tw_done

    # RTO (tcp.c:1445-1504): collapse to slow start, rewind, back off
    rto_fire = fired & ~tw_done & (v.snd_una < v.snd_max)
    flight_w = v.snd_max - v.snd_una
    v = v.replace(
        ssthresh=jnp.where(rto_fire, jnp.maximum(flight_w >> 1, 2 * mss), v.ssthresh),
        cwnd=jnp.where(rto_fire, mss, v.cwnd),
        snd_nxt=jnp.where(rto_fire, v.snd_una, v.snd_nxt),
        in_rec=jnp.where(rto_fire, False, v.in_rec),
        dupacks=jnp.where(rto_fire, 0, v.dupacks),
        rto=jnp.where(rto_fire, jnp.minimum(v.rto * 2, p.rto_max_ns), v.rto),
        backoff=jnp.where(rto_fire, v.backoff + 1, v.backoff),
        rtt_pending=jnp.where(rto_fire, False, v.rtt_pending),  # Karn
        rto_expire=jnp.where(rto_fire, TIME_MAX, v.rto_expire),
        # a timeout invalidates the scoreboard (reneging safety, RFC 2018)
        sacked=jnp.where(rto_fire[:, None, None], jnp.int64(-1), v.sacked),
        rtx_mark=jnp.where(rto_fire, 0, v.rtx_mark),
        # retransmits counted once, per segment, in the output pass
    )

    # ---------------- OUTPUT (the send engine, tcp.c:1265-1444) ----------
    out_slot = focus
    out_mask = m_act | m_tmr | m_flush | app.mask
    rtx_hole = rtx_hole & m_act  # belongs to the rx slot

    o = v

    # SYN / SYN|ACK when nothing has been sent yet (or after RTO rewind)
    m_syn_out = out_mask & ((o.st == SYNSENT) | (o.st == SYNRECEIVED)) & (o.snd_nxt == 0)
    syn_flags = jnp.where(
        o.st == SYNRECEIVED, FLAG_SYN | FLAG_ACK, FLAG_SYN
    ).astype(jnp.int32)
    syn_is_rtx = m_syn_out & (o.snd_max > 0)

    # sender-active states
    can_send = out_mask & (
        (o.st == ESTABLISHED) | (o.st == CLOSEWAIT) | (o.st == FINWAIT1)
        | (o.st == CLOSING) | (o.st == LASTACK)
    )
    wnd_lim = o.snd_una + jnp.minimum(o.cwnd, o.peer_wnd)
    fin_lim = o.snd_end + o.fin_pending.astype(jnp.int64)

    pv, pdst, pdata, psz = (
        emits.p_valid, emits.p_dst, emits.p_data, emits.p_size,
    )

    # forced hole retransmit (fast retransmit / NewReno partial ack): one
    # segment at the first *unsacked* hole (snd_una when the scoreboard is
    # empty), charged as a retransmission
    hole = o.snd_una
    if p.use_sack:
        for _ in range(p.ooo_ranges):
            cover = (
                (o.sacked[:, :, 0] >= 0)
                & (o.sacked[:, :, 0] <= hole[:, None])
                & (o.sacked[:, :, 1] > hole[:, None])
            )
            reach = jnp.max(jnp.where(cover, o.sacked[:, :, 1], jnp.int64(-1)), axis=1)
            hole = jnp.maximum(hole, reach)
    cursor = jnp.where(rtx_hole & can_send, hole, o.snd_nxt)
    is_first_rtx = rtx_hole & can_send

    # Karn: retransmitting invalidates any in-flight RTT sample
    new_rtt_pending = o.rtt_pending & ~is_first_rtx
    new_rtt_seq = o.rtt_seq
    new_rtt_ts = o.rtt_ts
    sent_any = jnp.zeros((h,), bool)
    nseg = p.segs_per_flush
    fin_goes = jnp.zeros((h,), bool)
    rtx_count = jnp.zeros((h,), jnp.int64)

    for i in range(nseg):
        room = jnp.minimum(jnp.minimum(o.snd_end, wnd_lim), cursor + mss)
        dlen = jnp.maximum(room - cursor, 0)
        send_data = can_send & (dlen > 0)
        # FIN rides its own zero-length segment once all data is out
        send_fin = (
            can_send
            & ~send_data
            & o.fin_pending
            & (cursor == o.snd_end)
            & (cursor + 1 <= wnd_lim)
            & ~fin_goes
        )
        lane_used = send_data | send_fin
        seq_w = cursor
        lflags = jnp.where(
            send_fin,
            FLAG_FIN | FLAG_ACK,
            jnp.where(send_data, FLAG_ACK, 0),
        ).astype(jnp.int32)
        if i == 0:
            # lane 0 doubles as the SYN / SYN|ACK lane
            lane_used = lane_used | m_syn_out
            seq_w = jnp.where(m_syn_out, jnp.int64(0), cursor)
            lflags = jnp.where(m_syn_out, syn_flags, lflags)
        lplen = jnp.where(send_data, dlen, 0).astype(jnp.int32)
        seg = _mk_seg(
            o.lport,
            o.rport,
            seq_w,
            o.rcv_nxt,
            lflags,
            lplen,
            jnp.full((h,), p.rcv_wnd, jnp.int64),
        )
        pv = pv.at[:, i].set(lane_used)
        pdst = pdst.at[:, i].set(o.rhost)
        pdata = pdata.at[:, i, :].set(seg)
        psz = psz.at[:, i].set(lplen + p.header_bytes)

        is_rtx = send_data & (cursor < o.snd_max)
        if i == 0:
            is_rtx = is_rtx | is_first_rtx | syn_is_rtx
        rtx_count = rtx_count + is_rtx
        # RTT timing starts on a fresh (non-retransmitted) segment (Karn)
        fresh = send_data & (cursor >= o.snd_max) & ~is_rtx
        start_rtt = fresh & ~new_rtt_pending
        new_rtt_pending = new_rtt_pending | start_rtt
        new_rtt_seq = jnp.where(start_rtt, cursor + dlen, new_rtt_seq)
        new_rtt_ts = jnp.where(start_rtt, now, new_rtt_ts)

        cursor = cursor + jnp.where(send_data, dlen, 0) + send_fin
        if i == 0:
            # fast retransmit / NewReno hole repair resends ONLY the hole
            # (one segment per RTT, tcp_cong_reno.c); subsequent lanes jump
            # back to the new-data frontier
            cursor = jnp.where(is_first_rtx, jnp.maximum(cursor, o.snd_nxt), cursor)
        fin_goes = fin_goes | send_fin
        sent_any = sent_any | lane_used

    # advance the send machine
    syn_adv = m_syn_out
    new_nxt = jnp.where(can_send, jnp.maximum(o.snd_nxt, cursor), o.snd_nxt)
    new_nxt = jnp.where(syn_adv, jnp.int64(1), new_nxt)
    new_max = jnp.maximum(o.snd_max, new_nxt)
    # FIN transmitted: ESTABLISHED->FINWAIT1, CLOSEWAIT->LASTACK (tcp.c:1751)
    st1 = jnp.where(
        fin_goes & (o.st == ESTABLISHED),
        FINWAIT1,
        jnp.where(fin_goes & (o.st == CLOSEWAIT), LASTACK, o.st),
    )
    # SYN starts the RTT sample too
    syn_rtt = syn_adv & ~new_rtt_pending & ~syn_is_rtx
    new_rtt_pending = new_rtt_pending | syn_rtt
    new_rtt_seq = jnp.where(syn_rtt, jnp.int64(1), new_rtt_seq)
    new_rtt_ts = jnp.where(syn_rtt, now, new_rtt_ts)

    # arm the RTO when data/SYN/FIN is outstanding and no timer is set
    outstanding_o = (o.snd_una < new_max) | m_syn_out
    arm = out_mask & outstanding_o & (o.rto_expire >= TIME_MAX) & (sent_any | m_syn_out)
    new_expire = jnp.where(arm, now + o.rto, o.rto_expire)

    # continuation: more sendable data than lanes this call
    more = can_send & (jnp.minimum(fin_lim, wnd_lim) > cursor)

    # timer maintenance: ensure a timer event exists at/before rto_expire
    need_tev = out_mask & (new_expire < o.tev_time)
    new_tev = jnp.where(need_tev, new_expire, o.tev_time)

    o = o.replace(
        snd_nxt=new_nxt,
        snd_max=new_max,
        st=st1,
        fin_sent=o.fin_sent | fin_goes,
        rtt_pending=new_rtt_pending,
        rtt_seq=new_rtt_seq,
        rtt_ts=new_rtt_ts,
        rto_expire=new_expire,
        tev_time=new_tev,
        retransmits=o.retransmits + rtx_count,
        segs_out=o.segs_out + jnp.sum(pv[:, :nseg], axis=1),
    )
    v = o  # the fused view, post-output

    # ---------------- control lane: ACK / RST ----------------------------
    # (after output so the ACK carries the freshest rcv_nxt/window;
    # focus == the rx slot whenever need_ack can be set)
    va = v
    if p.use_sack:
        # advertise the lowest buffered out-of-order range (the first-hole
        # information the sender's scoreboard needs most)
        starts = va.ooo[:, :, 0]
        present = starts >= 0
        min_start = jnp.min(
            jnp.where(present, starts, jnp.int64(1) << 62), axis=1
        )
        at_min = present & (starts == min_start[:, None])
        blk_e = jnp.max(jnp.where(at_min, va.ooo[:, :, 1], jnp.int64(-1)), axis=1)
        has_blk = jnp.any(present, axis=1)
        sack_s = jnp.where(has_blk, min_start, jnp.int64(0))
        sack_e = jnp.where(has_blk, blk_e, jnp.int64(0))
    else:
        sack_s = sack_e = jnp.zeros((h,), jnp.int64)
    ack_data = _mk_seg(
        va.lport,
        va.rport,
        va.snd_nxt,
        va.rcv_nxt,
        jnp.full((h,), FLAG_ACK, jnp.int32),
        jnp.zeros((h,), jnp.int32),
        jnp.full((h,), p.rcv_wnd, jnp.int64),
        sack_s=sack_s,
        sack_e=sack_e,
    )
    ctrl = p.segs_per_flush
    ctrl_valid = (need_ack & m_act) | m_stray
    emits = emits.replace(
        p_valid=pv.at[:, ctrl].set(ctrl_valid),
        p_dst=pdst.at[:, ctrl].set(jnp.where(m_stray, src, va.rhost)),
        p_data=pdata.at[:, ctrl, :].set(jnp.where(m_stray[:, None], rst_data, ack_data)),
        p_size=psz.at[:, ctrl].set(p.header_bytes),
    )

    # ---------------- local lanes: continuation + timer event ------------
    l_valid = emits.l_valid.at[:, 0].set(more)
    l_time = emits.l_time.at[:, 0].set(now)
    l_kind = emits.l_kind.at[:, 0].set(KIND_TCP_FLUSH)
    l_data = emits.l_data.at[:, 0, 0].set(out_slot)
    l_valid = l_valid.at[:, 1].set(need_tev)
    l_time = l_time.at[:, 1].set(jnp.where(need_tev, new_expire, now))
    l_kind = l_kind.at[:, 1].set(KIND_TCP_TIMER)
    l_data = l_data.at[:, 1, 0].set(out_slot)
    emits = emits.replace(l_valid=l_valid, l_time=l_time, l_kind=l_kind, l_data=l_data)

    sig = TcpSignals(
        slot=jnp.where(out_mask, out_slot, -1).astype(jnp.int32),
        established=sig_est,
        fin_seen=sig_fin,
        closed=sig_closed,
        reset=sig_rst,
    )
    return focus, out_mask, v, emits, sig, delivered_open
