"""Vectorized per-host network device: token-bucket relays + CoDel AQM.

The reference models bandwidth with per-host `Relay` forwarders that charge
a `TokenBucket` and re-schedule themselves as closures when out of tokens
(reference: src/main/network/relay/mod.rs:50-318,
src/main/network/relay/token_bucket.rs:6-120), and models the upstream
router's queue with a CoDel AQM checked at dequeue time
(src/main/network/router/mod.rs:16-115, router/codel_queue.rs:23-540).

The TPU-native reformulation avoids self-rescheduling state machines
entirely: because the token bucket refills a fixed amount on a fixed
interval (1 ms, relay/mod.rs:277-318), the departure time of a packet of
size S presented at time T is *closed-form integer arithmetic* over the
bucket state — so egress shaping happens inline at emit time, ingress
shaping becomes a single deferred re-enqueue of the arrival event at its
computed dequeue time, and CoDel is a per-host scalar state machine
advanced once per dequeue. All of it is branch-free and batched over the
host axis; no extra events are ever created for the relay itself.

Determinism: all bucket math is int64; CoDel's `interval / sqrt(count)`
uses a precomputed int64 table so CPU-reference and TPU timelines agree
bit-for-bit. The buckets' division by the per-host refill goes through
intmath.divmod_nonneg (exact, and cheap for the chip's compiler where an
int64 `//` by a variable is not); divisions by a constant stay `//`.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.intmath import divmod_nonneg
from shadow_tpu.simtime import NS_PER_MS

# Reference constants: refill every 1 ms (relay/mod.rs:286), CoDel TARGET
# 10 ms / INTERVAL 100 ms (codel_queue.rs:23-34), MTU burst allowance
# (relay/mod.rs:277-284).
REFILL_INTERVAL_NS = 1 * NS_PER_MS
CODEL_TARGET_NS = 10 * NS_PER_MS
CODEL_INTERVAL_NS = 100 * NS_PER_MS
MTU_BYTES = 1500

# Event-aux packing: low 24 bits = packet size in bytes, bit 24 = "shaped"
# (already passed ingress shaping; deliver as-is).
AUX_SIZE_MASK = (1 << 24) - 1
AUX_SHAPED_BIT = 1 << 24

# interval / sqrt(count) as an int64 table (index clamped to the last entry;
# by count=1024 the divisor has decayed to ~3 ms and further decay is
# negligible for simulation fidelity).
_CODEL_TABLE_LEN = 1024
_codel_div_np = np.array(
    [CODEL_INTERVAL_NS]
    + [int(CODEL_INTERVAL_NS / float(np.sqrt(np.float64(c)))) for c in range(1, _CODEL_TABLE_LEN + 1)],
    dtype=np.int64,
)


def codel_control_law(count):
    """interval / sqrt(count) in ns, table-driven (works on ints or arrays)."""
    if hasattr(count, "astype"):
        idx = jnp.clip(count, 1, _CODEL_TABLE_LEN)
        return jnp.asarray(_codel_div_np)[idx]
    return int(_codel_div_np[min(max(int(count), 1), _CODEL_TABLE_LEN)])


@flax.struct.dataclass
class NetDevState:
    """Per-host network-device state (all leaves lead with the host axis).

    A refill of 0 bytes/interval means "unlimited" (the loopback relay,
    relay/mod.rs exempts local packets; hosts without configured bandwidth
    are unshaped, matching hosts on an unrestricted graph node).
    """

    # egress (inet-out relay, up-bandwidth)
    tx_refill: jax.Array  # [H] i64 bytes per refill interval (0 = unlimited)
    tx_tokens: jax.Array  # [H] i64 bytes currently available
    tx_last: jax.Array  # [H] i64 ns of last refill boundary
    # ingress (inet-in relay, down-bandwidth)
    rx_refill: jax.Array  # [H] i64
    rx_tokens: jax.Array  # [H] i64
    rx_last: jax.Array  # [H] i64
    # CoDel AQM on the ingress (upstream-router) queue
    codel_first_above: jax.Array  # [H] i64 ns; -1 = none
    codel_drop_next: jax.Array  # [H] i64 ns
    codel_count: jax.Array  # [H] i32 drops in current dropping episode
    codel_dropping: jax.Array  # [H] bool
    rx_backlog_bytes: jax.Array  # [H] i64 bytes queued awaiting ingress tokens
    # stats (tracker feed, reference src/main/host/tracker.c:407-450)
    codel_dropped: jax.Array  # [H] i64
    bytes_sent: jax.Array  # [H] i64
    bytes_recv: jax.Array  # [H] i64


def create(
    num_hosts: int,
    tx_bytes_per_interval=None,
    rx_bytes_per_interval=None,
) -> NetDevState:
    h = num_hosts

    def _bw(v):
        if v is None:
            return jnp.zeros((h,), jnp.int64)
        arr = jnp.asarray(v, jnp.int64)
        if arr.ndim == 0:
            arr = jnp.full((h,), arr, jnp.int64)
        return arr

    tx = _bw(tx_bytes_per_interval)
    rx = _bw(rx_bytes_per_interval)
    return NetDevState(
        tx_refill=tx,
        # buckets start full: capacity = refill + MTU (relay/mod.rs:277-284)
        tx_tokens=tx + MTU_BYTES,
        tx_last=jnp.zeros((h,), jnp.int64),
        rx_refill=rx,
        rx_tokens=rx + MTU_BYTES,
        rx_last=jnp.zeros((h,), jnp.int64),
        codel_first_above=jnp.full((h,), -1, jnp.int64),
        codel_drop_next=jnp.zeros((h,), jnp.int64),
        codel_count=jnp.zeros((h,), jnp.int32),
        codel_dropping=jnp.zeros((h,), bool),
        rx_backlog_bytes=jnp.zeros((h,), jnp.int64),
        codel_dropped=jnp.zeros((h,), jnp.int64),
        bytes_sent=jnp.zeros((h,), jnp.int64),
        bytes_recv=jnp.zeros((h,), jnp.int64),
    )


def bw_bits_per_sec_to_refill(bits_per_sec) -> jax.Array:
    """Convert a bandwidth in bits/s to bucket refill bytes per interval.

    A configured-but-tiny bandwidth clamps to 1 byte/interval rather than
    flooring to 0, because refill 0 means *unlimited* here.
    """
    bps = jnp.asarray(bits_per_sec, jnp.int64)
    refill = (bps // 8) * REFILL_INTERVAL_NS // 1_000_000_000
    return jnp.where(bps > 0, jnp.maximum(refill, 1), 0)


def tb_depart(tokens, last, refill, now, size, charge):
    """Closed-form conforming-remove (token_bucket.rs:69-120, vectorized).

    Returns (depart_time, tokens', last') — the earliest time >= now the
    bucket can serve `size` bytes, with the post-charge state. Where
    `charge` is False or refill == 0 the packet departs at `now` and state
    is unchanged. Buckets refill `refill` bytes at fixed interval
    boundaries anchored at `last`, capped at refill + MTU while idle.
    """
    tokens = jnp.asarray(tokens, jnp.int64)
    now = jnp.asarray(now, jnp.int64)
    size = jnp.asarray(size, jnp.int64)
    limited = charge & (refill > 0)
    safe_refill = jnp.maximum(refill, 1)
    cap = refill + MTU_BYTES

    # lazy refill up to `now`
    intervals = jnp.maximum(now - last, 0) // REFILL_INTERVAL_NS
    cur = jnp.minimum(cap, tokens + intervals * safe_refill)
    cur_last = last + intervals * REFILL_INTERVAL_NS

    # wait k more intervals until the deficit is covered (k = 0 if none)
    deficit = jnp.maximum(size - cur, 0)
    k, _ = divmod_nonneg(deficit + safe_refill - 1, safe_refill)
    depart = jnp.where(deficit > 0, cur_last + k * REFILL_INTERVAL_NS, now)
    tokens_out = cur + k * safe_refill - size
    last_out = jnp.where(deficit > 0, cur_last + k * REFILL_INTERVAL_NS, cur_last)

    depart = jnp.where(limited, depart, now)
    tokens_out = jnp.where(limited, tokens_out, tokens)
    last_out = jnp.where(limited, last_out, last)
    return depart, tokens_out, last_out


def tb_depart_lanes(tokens, last, refill, now, sizes, charge):
    """Closed-form multi-lane conforming-remove: serve L packets at the
    same instant `now` in lane order. EXACTLY equals L sequential
    tb_depart calls (the nested ceil telescopes: the k-th lane's total
    extra intervals is ceil((prefix_k - cur)/refill)), in one prefix-sum
    pass instead of L dependent chains.

    sizes/charge are [H, L]; returns (departs [H, L], tokens', last').
    Rows with refill == 0 or all-False charge are unchanged and depart
    at `now` (the unlimited/exempt path, as tb_depart).
    """
    tokens = jnp.asarray(tokens, jnp.int64)
    now = jnp.asarray(now, jnp.int64)
    sizes = jnp.asarray(sizes, jnp.int64)
    limited = charge & (refill > 0)[:, None]
    safe_refill = jnp.maximum(refill, 1)
    cap = refill + MTU_BYTES

    intervals = jnp.maximum(now - last, 0) // REFILL_INTERVAL_NS
    cur = jnp.minimum(cap, tokens + intervals * safe_refill)
    cur_last = last + intervals * REFILL_INTERVAL_NS

    pref = jnp.cumsum(jnp.where(limited, sizes, 0), axis=1)
    deficit = jnp.maximum(pref - cur[:, None], 0)
    k, _ = divmod_nonneg(deficit + (safe_refill - 1)[:, None], safe_refill[:, None])
    # "departs at now" follows the SEQUENTIAL deficit — tokens left over
    # from an earlier lane's interval refill can cover a later lane
    # immediately (tb_depart returns `now` whenever the running balance
    # suffices), even though the raw prefix deficit is positive
    k_prev = jnp.concatenate([jnp.zeros_like(k[:, :1]), k[:, :-1]], axis=1)
    seq_deficit = pref - cur[:, None] - k_prev * safe_refill[:, None]
    departs = jnp.where(
        limited & (seq_deficit > 0),
        cur_last[:, None] + k * REFILL_INTERVAL_NS,
        now[:, None] if jnp.ndim(now) else jnp.broadcast_to(now, sizes.shape),
    )
    any_charged = jnp.any(limited, axis=1)
    k_last = jnp.max(jnp.where(limited, k, 0), axis=1)
    p_last = jnp.max(jnp.where(limited, pref, 0), axis=1)
    tokens_out = jnp.where(any_charged, cur + k_last * safe_refill - p_last, tokens)
    last_out = jnp.where(
        any_charged,
        jnp.where(k_last > 0, cur_last + k_last * REFILL_INTERVAL_NS, cur_last),
        last,
    )
    return departs, tokens_out, last_out


def codel_dequeue(net: NetDevState, now, sojourn, active):
    """One CoDel dequeue step per host (codel_queue.rs:23-540, RFC 8289).

    `now` is the dequeue time, `sojourn` the packet's queue delay, `active`
    the hosts actually dequeuing this step. Returns (drop, net').
    Divergence from the reference noted: the reference may drop several
    packets in one dequeue call (drain loop); here dequeues are per-packet
    events so the episode advances one packet at a time — the drop *rate*
    (control law) is identical.
    """
    now = jnp.asarray(now, jnp.int64)
    below = (sojourn < CODEL_TARGET_NS) | (net.rx_backlog_bytes < MTU_BYTES)

    first_above = net.codel_first_above
    unset = first_above < 0
    new_first = jnp.where(
        below, jnp.int64(-1), jnp.where(unset, now + CODEL_INTERVAL_NS, first_above)
    )
    ok_to_drop = ~below & ~unset & (now >= first_above)

    dropping = net.codel_dropping
    count = net.codel_count
    drop_next = net.codel_drop_next

    # in a dropping episode: leave it if below target, else drop on schedule
    leave = dropping & ~ok_to_drop
    drop_in_episode = dropping & ok_to_drop & (now >= drop_next)
    count_in = count + drop_in_episode.astype(jnp.int32)
    next_in = jnp.where(
        drop_in_episode,
        drop_next + codel_control_law(count_in),
        drop_next,
    )

    # entering a new episode (codel_queue.rs: resume with count-2 if the
    # last episode ended recently, else restart at 1)
    enter = ~dropping & ok_to_drop
    recent = (now - drop_next) < CODEL_INTERVAL_NS
    count_enter = jnp.where(recent & (count > 2), count - 2, 1).astype(jnp.int32)
    next_enter = now + codel_control_law(count_enter)

    drop = active & (drop_in_episode | enter)
    new_dropping = jnp.where(active, (dropping & ~leave) | enter, dropping)
    new_count = jnp.where(active & enter, count_enter, jnp.where(active, count_in, count))
    new_next = jnp.where(active & enter, next_enter, jnp.where(active, next_in, drop_next))
    new_first = jnp.where(active, new_first, first_above)

    return drop, net.replace(
        codel_first_above=new_first,
        codel_dropping=new_dropping,
        codel_count=new_count,
        codel_drop_next=new_next,
    )


# The scalar shaping twins live elsewhere by design: the managed kernel's
# product copy is shadow_tpu/hostk/shaping.py; the conformance oracle's
# independent re-derivation is shadow_tpu/cpu_ref/netstack_ref.py.
