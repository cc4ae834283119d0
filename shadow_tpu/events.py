"""Event identity and total ordering.

The reference's determinism contract is a total order over events:
(time, variant with Packet < Local, src_host_id, per-src-host event counter)
— reference: src/main/core/work/event.rs:104-184. We encode the three
tie-break fields into one i64 ("tie") so an event is totally ordered by the
lexicographic pair (time_i64, tie_i64). Two-stage masked argmin over that
pair replaces the reference's per-host BinaryHeap
(src/main/core/work/event_queue.rs:10-49).

tie layout (MSB..LSB):  [bit 62: variant][30 bits src_host][32 bits seq]
(bit 63 stays clear so the packed value is a valid non-negative i64).
variant: 0 = Packet, 1 = Local (Packet sorts first, as in the reference).

Event *kinds* are engine/model dispatch codes stored separately; only
"is it a packet" (kind == KIND_PACKET) feeds the ordering.
"""

from __future__ import annotations

import jax.numpy as jnp

# Engine-level kinds. Models may define their own kinds >= KIND_MODEL_BASE.
KIND_INVALID = -1
KIND_PACKET = 0  # a packet arriving at a host's upstream router
KIND_MODEL_BASE = 1  # local (task/timer) kinds start here

# Tracker-plane kind classes (reference: tracker.c splits heartbeat
# counters by event class): kind == KIND_PACKET is a packet event; a
# model that embeds a protocol machine declares its protocol-internal
# kind range as a static `TCP_KIND_RANGE = (lo, hi)` attribute (the TCP
# models export [KIND_TCP_TIMER, TCP_KIND_USER_BASE), transport/tcp.py
# — kind values are only unique WITHIN a model, e.g. phold's KIND_SEND
# shares the integer with KIND_TCP_TIMER, so the range must be
# model-owned); every other handled kind is a model-local task. The
# classification depends only on (model, kind), so per-kind counters
# are identical across plain/pump by construction.

_SEQ_BITS = 32
_SRC_BITS = 30
SEQ_MASK = (1 << _SEQ_BITS) - 1
SRC_MASK = (1 << _SRC_BITS) - 1
MAX_HOSTS = 1 << _SRC_BITS


def pack_tie(kind, src_host, seq):
    """Pack ordering tie-break fields into one i64. Works on ints or arrays.

    seq wraps at 2^32: ordering between two *pending* events of one src is
    only affected if their seq numbers straddle a wrap (>= 2^32 events apart),
    which cannot happen with bounded queues. src_host must be < MAX_HOSTS
    (2^30); engine construction validates this.
    """
    if hasattr(kind, "astype"):
        variant = (kind != KIND_PACKET).astype(jnp.int64)
        return (
            (variant << (_SRC_BITS + _SEQ_BITS))
            | ((src_host.astype(jnp.int64) & SRC_MASK) << _SEQ_BITS)
            | (seq.astype(jnp.int64) & SEQ_MASK)
        )
    if not (0 <= int(src_host) < MAX_HOSTS):
        raise ValueError(f"src_host {src_host} out of range [0, {MAX_HOSTS})")
    return (int(kind != KIND_PACKET) << (_SRC_BITS + _SEQ_BITS)) | (int(src_host) << _SEQ_BITS) | (int(seq) & SEQ_MASK)


def tie_src_host(tie):
    return (tie >> _SEQ_BITS) & SRC_MASK


def tie_seq(tie):
    return tie & SEQ_MASK


def tie_is_local(tie):
    return (tie >> (_SRC_BITS + _SEQ_BITS)) & 1
