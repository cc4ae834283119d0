"""Vectorized per-host event queues as fixed-slot HBM tensors.

Replaces the reference's per-host `BinaryHeap<Reverse<Event>>`
(reference: src/main/core/work/event_queue.rs:10-49) with a
struct-of-arrays layout: H hosts x Q slots. A row's pending events sit in
*arbitrary* slots; a free slot is a tombstone (time == TIME_MAX, tie ==
_I64_MAX) that keeps its stale kind / aux / data, and pushes fill free
slots by rank.

"Pop" is ONE reduction over the row and ONE gather (peek_min): the
reduction is a masked argmin over the total-order key (time, tie) from
events.py that also returns what it found — the tie is its minimum, and
the slot's kind and aux ride beside its index — and the gather reads the
slot's eight payload words, one index a host. On the chip a gather costs
by the index (10-22 ns each), not by the byte, so the pop issues H
indices once where picking tie, kind, aux and data each by the slot
issued them five times (the i64 tie is two 32-bit gathers there). The
consume half (clear_slot) rewrites the two key arrays and nothing else.

Hosts are axis 0 of every leaf: the sharded runner splits each leaf there
(engine/sharded.py), and the ensemble and mesh planes vmap over a replica
axis in front of it. kind and aux are [H, Q] leaves of their own and the
payload is [H, Q, 8]: eight words are a sublane tile of the chip, which
lays the payload out with the words on the sublanes whatever Q is; kind,
aux and the payload as ONE array of ten words a slot pad to sixteen there
and double the chunk program's temporaries at 524,288 hosts (PERF.md
section 6, PR 35).

All operations are branch-free, fixed-shape, and vectorized over hosts so
they trace into a single XLA computation (no per-host Python loops).

The reference panics when the queue would pop out of order
(event_queue.rs:26-31); here ordering is intrinsic (argmin), and the
analogous failure mode is slot exhaustion, which we track per host in
`overflow` rather than silently dropping.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from shadow_tpu import scopes
from shadow_tpu.events import KIND_INVALID, pack_tie, tie_src_host
from shadow_tpu.simtime import TIME_MAX

# Number of i32 payload lanes carried by every event. Models/packets pack
# their data into these (see engine/state.py for layouts). Transport packets
# use lanes as headers: ports, seq, ack, flags|len, wnd, app, and one SACK
# block (transport/header.py); the reference's C packet headers are
# packet.h:20-40 with SACK blocks in tcp_retransmit_tally.cc.
PAYLOAD_LANES = 8

_I64_MAX = jnp.iinfo(jnp.int64).max


@flax.struct.dataclass
class EventQueue:
    """H x Q event slots + per-host fill counts. `time` and `tie` are the
    key, which every pop scans and rewrites; `kind`, `aux` and `data` are
    what a slot carries, which a pop reads (kind and aux in the key's own
    reduction, data by one gather) and only pushes write."""

    time: jax.Array  # [H, Q] i64 ns; TIME_MAX in empty slots
    tie: jax.Array  # [H, Q] i64 packed (variant, src_host, seq); _I64_MAX when empty
    kind: jax.Array  # [H, Q] i32 dispatch code; KIND_INVALID when empty
    data: jax.Array  # [H, Q, PAYLOAD_LANES] i32
    aux: jax.Array  # [H, Q] i32 engine channel (packet size | shaped flag)
    count: jax.Array  # [H] i32 number of valid slots
    overflow: jax.Array  # [H] i32 number of events dropped for lack of slots
    # Cached exact per-host minimum of `time` (TIME_MAX when empty). Every
    # mutator maintains it (pushes: running min; pops: row rescan), so the
    # round loop's eligibility/window math is O(H) instead of an O(H*Q)
    # scan per check — load-bearing for per-iteration cost at 10k hosts.
    head_time: jax.Array  # [H] i64

    @property
    def num_hosts(self) -> int:
        return self.time.shape[0]

    @property
    def capacity(self) -> int:
        return self.time.shape[1]


def create(num_hosts: int, capacity: int) -> EventQueue:
    h, q = num_hosts, capacity
    return EventQueue(
        time=jnp.full((h, q), TIME_MAX, dtype=jnp.int64),
        tie=jnp.full((h, q), _I64_MAX, dtype=jnp.int64),
        kind=jnp.full((h, q), KIND_INVALID, dtype=jnp.int32),
        data=jnp.zeros((h, q, PAYLOAD_LANES), dtype=jnp.int32),
        aux=jnp.zeros((h, q), dtype=jnp.int32),
        count=jnp.zeros((h,), dtype=jnp.int32),
        overflow=jnp.zeros((h,), dtype=jnp.int32),
        head_time=jnp.full((h,), TIME_MAX, dtype=jnp.int64),
    )


def next_time(q: EventQueue) -> jax.Array:
    """[H] i64: each host's earliest pending event time (TIME_MAX if none)."""
    return q.head_time


@flax.struct.dataclass
class Popped:
    """One popped event per host (valid marks hosts that actually popped)."""

    valid: jax.Array  # [H] bool
    time: jax.Array  # [H] i64
    tie: jax.Array  # [H] i64
    kind: jax.Array  # [H] i32
    data: jax.Array  # [H, PAYLOAD_LANES] i32
    aux: jax.Array  # [H] i32

    @property
    def src_host(self) -> jax.Array:
        return tie_src_host(self.tie).astype(jnp.int32)


def _first_min(key: jax.Array, *rides: jax.Array) -> "tuple[jax.Array, ...]":
    """(the minimum of each row of `key` [H, Q], the first slot that holds
    it as [H] i32, and every one of `rides` [H, Q] read at that slot).
    jnp.argmin's own reduction over (value, index), with the value kept and
    the rides carried beside the index: one pass over the row where jnp.min
    beside jnp.argmin makes two, and no gather of the slot afterwards."""
    index = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)

    def first(a, b):
        take_a = (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return tuple(jnp.where(take_a, x, y) for x, y in zip(a, b))

    operands = (key, index, *rides)
    # what loses to every slot: the largest key at an index past the row
    top = (jnp.iinfo(key.dtype).max, jnp.iinfo(jnp.int32).max) + (0,) * len(rides)
    init = tuple(jnp.array(t, x.dtype) for t, x in zip(top, operands))
    return jax.lax.reduce(operands, init, first, (1,))


@scopes.scoped(scopes.POP)
def peek_min(q: EventQueue, want: jax.Array) -> tuple[Popped, jax.Array]:
    """Read each host's minimum event where `want[h]` and the host is
    non-empty, WITHOUT removing it. Returns (event, slot); pass the slot
    to clear_slot to consume. Ordering follows the reference's total
    order: min by time, ties broken by the packed (variant, src_host,
    seq) key (event.rs:104-155)."""
    tmin = q.head_time  # [H]
    at_min = q.time == tmin[:, None]
    tie_masked = jnp.where(at_min, q.tie, _I64_MAX)
    # ONE reduction over the row gives the slot, and with it what the slot
    # holds in the [H, Q] arrays: the tie is the reduction's own minimum
    # (on an empty row _I64_MAX, which create and clear_slot keep in every
    # free slot, so what a gather of slot 0 would read), kind and aux ride
    # beside the index. A gather costs per INDEX on the chip, 10-22 ns each
    # (PERF.md), where the two more planes the pass reads cost their bytes.
    tie, slot, kind, aux = _first_min(tie_masked, q.kind, q.aux)
    valid = want & (q.count > 0)

    # The payload is the pop's ONE gather of H indices (eight words each:
    # a sublane tile; ten words in one array pad to sixteen, PERF.md).
    data = jnp.take_along_axis(
        q.data, slot[:, None, None], axis=1, mode="promise_in_bounds"
    )[:, 0]

    ev = Popped(
        valid=valid,
        time=tmin,  # the selected slot's time IS the cached row minimum
        tie=tie,
        kind=kind,
        data=data,
        aux=aux,
    )
    return ev, slot


@scopes.scoped(scopes.POP)
def clear_slot(q: EventQueue, slot: jax.Array, mask: jax.Array) -> EventQueue:
    """Tombstone q[h, slot[h]] where mask[h] (the consume half of a
    peek_min/clear_slot pop; see pop_min). Only the two key arrays are
    rewritten; kind/data/aux stay as stale slot contents."""
    slot_idx = jnp.arange(q.capacity)[None, :]
    clear = (slot_idx == slot[:, None]) & mask[:, None]
    new_time = jnp.where(clear, TIME_MAX, q.time)
    return q.replace(
        time=new_time,
        tie=jnp.where(clear, _I64_MAX, q.tie),
        count=q.count - mask.astype(jnp.int32),
        head_time=jnp.min(new_time, axis=1),
    )


def pop_min(q: EventQueue, want: jax.Array) -> tuple[Popped, EventQueue]:
    """Pop each host's minimum event where `want[h]` and the host is
    non-empty (peek_min + clear_slot fused). The freed slot becomes a
    tombstone (time=TIME_MAX): rows are NOT kept compact — pushes fill
    free slots by rank over the free mask — so a pop only rewrites the
    two key arrays instead of back-filling all five (data alone is
    [H, Q, 8] i32, the single biggest traffic term at bench scale)."""
    ev, slot = peek_min(q, want)
    return ev, clear_slot(q, slot, ev.valid)


def push_self(
    q: EventQueue,
    valid: jax.Array,  # [H] bool
    time: jax.Array,  # [H] i64
    tie: jax.Array,  # [H] i64
    kind: jax.Array,  # [H] i32
    data: jax.Array,  # [H, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [H] i32
) -> EventQueue:
    """Each host pushes at most one event into its *own* queue (conflict-free).

    One-hot where writes (fusable on TPU), not scatters; see pop_min.
    Targets the first free (tombstoned) slot of each row.

    Invariant (load-bearing): time == TIME_MAX marks a FREE slot, so no
    live event may be pushed at TIME_MAX. Such a push would increment
    count while the slot still reads free, silently desyncing occupancy —
    it is instead rejected and counted into overflow (loud via
    check_capacity). A "never" sentinel event is semantically an event
    that does not exist; schedule real events strictly below TIME_MAX.
    """
    if aux is None:
        aux = jnp.zeros_like(kind)
    sentinel = valid & (time >= TIME_MAX)
    valid = valid & ~sentinel
    free = q.time == TIME_MAX  # [H, Q]
    has_room = q.count < q.capacity
    write = valid & has_room
    fr = jnp.cumsum(free, axis=1) - free  # rank among free slots
    at = free & (fr == 0) & write[:, None]
    return q.replace(
        time=jnp.where(at, time[:, None], q.time),
        tie=jnp.where(at, tie[:, None], q.tie),
        kind=jnp.where(at, kind[:, None], q.kind),
        data=jnp.where(at[:, :, None], data[:, None, :], q.data),
        aux=jnp.where(at, aux[:, None], q.aux),
        count=q.count + write.astype(jnp.int32),
        overflow=q.overflow
        + (valid & ~has_room).astype(jnp.int32)
        + sentinel.astype(jnp.int32),
        head_time=jnp.minimum(q.head_time, jnp.where(write, time, TIME_MAX)),
    )


def push_self_lanes(
    q: EventQueue,
    valid: jax.Array,  # [H, L] bool
    time: jax.Array,  # [H, L] i64
    tie: jax.Array,  # [H, L] i64
    kind: jax.Array,  # [H, L] i32
    data: jax.Array,  # [H, L, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [H, L] i32
) -> EventQueue:
    """Each host pushes up to L events into its *own* queue, in lane order —
    semantically identical to L sequential push_self calls, but the slot
    writes collapse into one fused where-chain per array (one pass on TPU
    instead of L). Lane l lands in the row's l-th free (tombstoned) slot.

    Same TIME_MAX invariant as push_self: a push at TIME_MAX (the
    free-slot marker) is rejected and counted into overflow, never
    silently admitted."""
    if valid.shape[1] == 0:
        return q  # no lanes: the sequential-push contract is a no-op
    if aux is None:
        aux = jnp.zeros_like(kind)
    sentinel = valid & (time >= TIME_MAX)
    valid = valid & ~sentinel
    free = q.time == TIME_MAX  # [H, Q]
    fr = jnp.cumsum(free, axis=1) - free  # rank among free slots
    ranks = jnp.cumsum(valid.astype(jnp.int32), axis=1) - valid.astype(jnp.int32)
    room = q.capacity - q.count  # [H] free-slot count
    write = valid & (ranks < room[:, None])

    new_time, new_tie = q.time, q.tie
    new_kind, new_data, new_aux = q.kind, q.data, q.aux
    for l in range(valid.shape[1]):
        at = free & (fr == ranks[:, l][:, None]) & write[:, l][:, None]
        new_time = jnp.where(at, time[:, l][:, None], new_time)
        new_tie = jnp.where(at, tie[:, l][:, None], new_tie)
        new_kind = jnp.where(at, kind[:, l][:, None], new_kind)
        new_data = jnp.where(at[:, :, None], data[:, l, None, :], new_data)
        new_aux = jnp.where(at, aux[:, l][:, None], new_aux)
    head_new = jnp.min(jnp.where(write, time, TIME_MAX), axis=1)
    return q.replace(
        time=new_time,
        tie=new_tie,
        kind=new_kind,
        data=new_data,
        aux=new_aux,
        # explicit int32: jnp.sum promotes int under x64 (see _lane_seqs)
        count=q.count + jnp.sum(write, axis=1).astype(jnp.int32),
        overflow=q.overflow
        + jnp.sum((valid & ~write) | sentinel, axis=1).astype(jnp.int32),
        head_time=jnp.minimum(q.head_time, head_new),
    )


# Arrival lanes of every destination that one pass of the landing pulls
# (land_sorted). One constant for every caller: what adapts is the number
# of passes, to the busiest destination of the batch. Chosen by a sweep on
# the chip (PERF.md section 7.7).
LAND_LANES = 4


def land_passes(max_land):
    """Passes land_sorted's loop makes when the busiest destination lands
    `max_land` arrivals: ceil(max_land / LAND_LANES)."""
    return (max_land + (LAND_LANES - 1)) // LAND_LANES


def push_many(
    q: EventQueue,
    dst: jax.Array,  # [M] i32 destination host ids
    valid: jax.Array,  # [M] bool
    time: jax.Array,  # [M] i64
    tie: jax.Array,  # [M] i64
    kind: jax.Array,  # [M] i32
    data: jax.Array,  # [M, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [M] i32
) -> EventQueue:
    """Batched push of M events to arbitrary destination hosts.

    This is the round-boundary exchange step (the analogue of
    Worker::push_packet_to_host, reference src/main/core/worker.rs:619-629,
    minus the mutex). push_many_sorted with no per-destination bound but
    the queue's own capacity (exact: only a full row rejects); it costs by
    the batch and by the busiest destination's arrivals (land_sorted)."""
    return push_many_sorted(
        q, dst, valid, time, tie, kind, data, aux,
        deliver_lanes=q.capacity,
    )


def run_bounds(key: jax.Array, h: int) -> "tuple[jax.Array, jax.Array]":
    """(cnt, begin), each [h] i32: cnt[x] the number of entries of `key`
    ([M] i32, in any order) equal to x, begin its exclusive cumulative sum
    (where x's run starts once the keys are sorted). A key outside [0, h)
    is counted nowhere.

    cnt[a * 128 + b] = sum_i [key_i // 128 == a][key_i % 128 == b], one
    product of two one-hot matrices (exact in int32; on the chip the
    MXU's, 0.1 ms where H+1 binary searches over the sorted keys took
    1.2-1.4 ms a round: PERF.md, PR 27). Key h, the invalids', matches no
    column or one past the last host's."""
    with jax.named_scope(scopes.COUNT):
        blocks = -(-h // 128)
        hot_a = (key >> 7)[:, None] == jnp.arange(blocks, dtype=jnp.int32)
        hot_b = (key & 127)[:, None] == jnp.arange(128, dtype=jnp.int32)
        cnt = jnp.dot(
            hot_a.T.astype(jnp.int8), hot_b.astype(jnp.int8),
            preferred_element_type=jnp.int32,
        ).reshape(-1)[:h]
        begin = jnp.cumsum(cnt, dtype=jnp.int32) - cnt  # [H] start of h's run
    return cnt, begin


def push_many_sorted(
    q: EventQueue,
    dst: jax.Array,  # [M] i32 destination host ids
    valid: jax.Array,  # [M] bool
    time: jax.Array,  # [M] i64
    tie: jax.Array,  # [M] i64
    kind: jax.Array,  # [M] i32
    data: jax.Array,  # [M, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [M] i32
    deliver_lanes: int = 48,
) -> EventQueue:
    """land_sorted's queue alone, for callers with no use for the count of
    arrivals the busiest destination landed."""
    return land_sorted(q, dst, valid, time, tie, kind, data, aux, deliver_lanes)[0]


def land_sorted(
    q: EventQueue,
    dst: jax.Array,  # [M] i32 destination host ids
    valid: jax.Array,  # [M] bool
    time: jax.Array,  # [M] i64
    tie: jax.Array,  # [M] i64
    kind: jax.Array,  # [M] i32
    data: jax.Array,  # [M, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [M] i32
    deliver_lanes: int = 48,
) -> "tuple[EventQueue, jax.Array]":
    """push_many as a PULL by arrival lane: every destination pulls its own
    arrivals, LAND_LANES of them a pass, for as many passes as the busiest
    destination needs. Returns (queue, max_land): max_land (scalar i32) is
    the most arrivals one destination landed, the loop's bound.

      S   one stable sort of (destination, position) — two words per
          entry, invalids last — puts each destination's arrivals in one
          run, in arrival order; cnt[h] of the runs is a histogram of the
          keys (one product of two one-hot matrices), begin[h] its
          exclusive cumulative sum;
      G   the payload is packed as 32-bit words (14 an entry) where it
          lies; nothing is copied into sorted order: a pass reads entry
          order[begin[h] + r] for arrival rank r, through the sort's
          permutation;
      P   row h lands land[h] = min(cnt[h], D, room[h]) arrivals; pass p
          handles ranks [p K, (p + 1) K), K = LAND_LANES: two gathers of
          [K, H] indices (the permutation, then the 14 words) and one
          select chain over the five queue arrays that puts rank r into
          the row's r-th free (tombstoned) slot (`free & (fr == r)`, as
          push_self_lanes spells it). A lax.while_loop makes
          ceil(max_h land[h] / K) passes: none where nothing landed.

    S and G are land_sort, P is land_pull: this is the two on ONE batch.
    The flush (engine/round.py flush_outbox) hands land_sort the outbox a
    block of slot columns at a time, the staged ones only, and land_pull
    the blocks it filled.

    Cost follows the batch (S) and the busiest destination (P), not the
    queue's H x Q slots and not D = deliver_lanes: there is no [H, D]
    delivery grid, no scatter, no gather of H x Q or of M indices. D keeps
    its meaning: a destination takes at most its first D arrivals of a
    call (rank < D fits); those beyond D are counted on overflow row 0,
    those beyond the row's room on the row's own overflow — both loud via
    check_capacity. The r-th arrival lands in the row's r-th free slot
    (arrival order of the stable sort); pop order is key-driven anyway.
    Under vmap the loop runs to the largest replica's pass count and a
    replica that is done keeps its carry: each replica's leaves are its
    own.

    Why the payload does not ride the sort: XLA:TPU's sort costs the
    chip's compiler ~14 s per 32-bit operand word once the array no
    longer sorts in one tile (> 16k entries); and searchsorted's
    method="sort" is one more such sort, 43 s of compile at this size
    (tools/compile_for_chip.py, CHANGES.md PR 22).

    Same TIME_MAX invariant as push_self: a push at TIME_MAX (the
    free-slot marker) is rejected and counted into overflow — on row 0;
    always fatal via check_capacity.
    """
    if dst.shape[0] == 0:
        return q, jnp.zeros((), jnp.int32)
    n_pushed, cnt, begin, order, words = land_sort(
        q.num_hosts, dst, valid, time, tie, kind, data, aux
    )
    return land_pull(q, n_pushed, cnt[None], begin[None], order, words, deliver_lanes)


def land_sort(
    h: int,
    dst: jax.Array,  # [M] i32 destination host ids
    valid: jax.Array,  # [M] bool
    time: jax.Array,  # [M] i64
    tie: jax.Array,  # [M] i64
    kind: jax.Array,  # [M] i32
    data: jax.Array,  # [M, PAYLOAD_LANES] i32
    aux: "jax.Array | None" = None,  # [M] i32
) -> tuple:
    """Steps S and G of land_sorted on one batch of M entries bound for a
    queue of `h` rows: (n_pushed, cnt, begin, order, words). n_pushed
    (scalar i32) counts the valid entries as handed in; cnt[x] and
    begin[x] ([h] i32) are destination x's run in the sorted order, order
    ([M] i32) the sort's permutation, words ([14, M] i32) the payload
    packed where it lies: time and tie as (low, high), kind, aux, the
    data lanes."""
    m = dst.shape[0]
    if aux is None:
        aux = jnp.zeros_like(kind)
    n_pushed = jnp.sum(valid, dtype=jnp.int32)
    valid = valid & (time < TIME_MAX) & (dst >= 0) & (dst < h)

    # S: group by destination (stable; invalids sort last)
    with jax.named_scope(scopes.SORT):
        key1 = jnp.where(valid, dst, h).astype(jnp.int32)
        pos = jnp.arange(m, dtype=jnp.int32)
        _, order = jax.lax.sort((key1, pos), num_keys=1, is_stable=True)
    cnt, begin = run_bounds(key1, h)

    # G: word-major [W, M], because the chip tiles the two minor
    # dimensions: a [.., W] minor of 14 pads to 128 lanes and every field
    # sliced out of it is a strided pass over that.
    with jax.named_scope(scopes.PACK):
        words = jnp.concatenate(
            [jnp.stack([_lo(time), _hi(time), _lo(tie), _hi(tie), kind, aux]), data.T]
        )
    return n_pushed, cnt, begin, order, words


def _lo(x):  # i64 -> its low 32 bits as i32
    return x.astype(jnp.int32)


def _hi(x):
    return (x >> 32).astype(jnp.int32)


def _long(low, high):  # the i64 back, bit-exact
    low = jax.lax.bitcast_convert_type(low, jnp.uint32)
    return (high.astype(jnp.int64) << 32) | low.astype(jnp.int64)


def land_pull(
    q: EventQueue,
    n_pushed: jax.Array,  # scalar i32: valid entries handed to land_sort
    cnt: jax.Array,  # [B, H] i32: each block's arrivals by destination
    begin: jax.Array,  # [B, H] i32: where the run starts in its block
    order: jax.Array,  # [B * N] i32: block b's permutation, each + b * N
    words: jax.Array,  # [14, B * N] i32
    deliver_lanes: int,
) -> "tuple[EventQueue, jax.Array]":
    """Step P of land_sorted over B blocks of N entries each that
    land_sort grouped one by one: a destination's arrivals are block 0's
    run, then block 1's, and so on, and its r-th arrival lands in its r-th
    free slot, exactly as if the blocks had been sorted as one batch in
    that order. A block nobody filled has cnt 0 and is never read. With
    one block this is land_sorted's own loop."""
    nb = cnt.shape[0]
    n = order.shape[0] // nb
    cap = q.capacity
    total = jnp.sum(cnt, axis=0, dtype=jnp.int32)  # [H]
    if nb > 1:
        upto = jnp.cumsum(cnt, axis=0, dtype=jnp.int32)  # [B, H] inclusive
        # rank r of row h is entry start[b, h] + r of the blocks' segments,
        # b the first block whose inclusive count passes r
        start = begin - (upto - cnt) + (jnp.arange(nb, dtype=jnp.int32) * n)[:, None]

    # P: each row's free slots by rank, and how many arrivals it lands
    free = q.time == TIME_MAX  # [H, Q]
    fr = (jnp.cumsum(free, axis=1) - free).astype(jnp.int32)  # rank among free slots
    fit = jnp.minimum(total, deliver_lanes)
    land = jnp.minimum(fit, cap - q.count)  # [H]
    take = free & (fr < land[:, None])
    max_land = jnp.max(land)
    passes = land_passes(max_land)
    lane = jnp.arange(LAND_LANES, dtype=jnp.int32)

    def one_pass(carry):
        p, q = carry
        rank = p * LAND_LANES + lane  # [K] arrival ranks of this pass
        # lanes major, hosts minor: a minor axis of K would pad to 128 lanes
        # a lane with no arrival left to pull (most lanes, in most rounds)
        # reads on in block 0, past its row's run, where its neighbours'
        # lanes read too. What idle lanes read is part of the gather's
        # price on the chip: all ONE entry cost it a third more at 524,288
        # rows, each an entry of its own a tenth more (PERF.md, PR 37)
        off = begin[0]
        if nb > 1:
            more = rank[:, None] < total
            for b in range(1, nb):
                off = jnp.where(more & (rank[:, None] >= upto[b - 1]), start[b], off)
        # [K, H]; used where rank < land
        src = jnp.minimum(off + rank[:, None], order.shape[0] - 1)
        g = words[:, order[src]]  # [W, K, H]
        g_time, g_tie = _long(g[0], g[1]), _long(g[2], g[3])
        q_time, q_tie, q_kind, q_data, q_aux = q.time, q.tie, q.kind, q.data, q.aux
        for k in range(LAND_LANES):
            at = take & (fr == rank[k])  # the row's rank-th free slot
            q_time = jnp.where(at, g_time[k, :, None], q_time)
            q_tie = jnp.where(at, g_tie[k, :, None], q_tie)
            q_kind = jnp.where(at, g[4, k, :, None], q_kind)
            q_aux = jnp.where(at, g[5, k, :, None], q_aux)
            q_data = jnp.where(at[:, :, None], g[6:, k].T[:, None, :], q_data)
        landed = jnp.where(rank[:, None] < land, g_time, TIME_MAX)
        return p + 1, q.replace(
            time=q_time, tie=q_tie, kind=q_kind, data=q_data, aux=q_aux,
            head_time=jnp.minimum(q.head_time, jnp.min(landed, axis=0)),
        )

    with jax.named_scope(scopes.PULL):
        _, q = jax.lax.while_loop(
            lambda carry: carry[0] < passes, one_pass, (jnp.zeros((), jnp.int32), q)
        )
    q = q.replace(
        count=q.count + land,
        # beyond the row's room: on the row; beyond deliver_lanes, at
        # TIME_MAX or to no host of this queue: globally on row 0
        overflow=(q.overflow + (fit - land))
        .at[0]
        .add(n_pushed - jnp.sum(fit, dtype=jnp.int32)),
    )
    return q, max_land


def debug_sorted_events(q: EventQueue, host: int):
    """Host-side helper: the given host's events in pop order (for tests)."""
    time = jax.device_get(q.time[host])
    tie = jax.device_get(q.tie[host])
    kind = jax.device_get(q.kind[host])
    data = jax.device_get(q.data[host])
    n = int(q.count[host])
    # live slots are those without a tombstone (stale kind/data may remain
    # in popped slots; time is the occupancy marker)
    items = sorted(
        ((int(time[i]), int(tie[i]), int(kind[i]), tuple(int(x) for x in data[i])) for i in range(q.capacity) if time[i] != TIME_MAX),
    )
    assert len(items) == n, (len(items), n)
    return items
