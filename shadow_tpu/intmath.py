"""Exact 64-bit integer division that the TPU's compiler can afford.

The chip has no 64-bit integer divide; XLA:TPU emulates `lax.div`/`lax.rem`
on int64 with generated code that costs tens of seconds of compile time
*per division* (asked of the compiler for a described v5e: four chained
`x // d` on int64[4096] took 35 s, the same chain in int32 a fraction of a
second — tools/compile_for_chip.py, CHANGES.md PR 22). Every time and byte
count in the engine is int64, and the token buckets and the RTT estimator
divide them on every handled event, so those few operators were most of
the handler's compile wall.

The rule: a divisor that is data (the per-host refill of the token
buckets) goes through `divmod_nonneg`; a divisor the trace knows as a
constant stays `//` or `%` — XLA reduces that itself (about a second of
compile, no divide in the program) — and a power of two is a shift.

`divmod_nonneg` gets the same quotient and remainder from float32
estimates that are *verified and corrected in exact int64 arithmetic*: the
loop ends only when 0 <= r < d holds in every lane, so the result is exact
by construction and the float unit's accuracy decides only how many rounds
it takes (one or two for quotients below 2^12, at most ~7 for 2^62).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def divmod_nonneg(x, d) -> "tuple[jax.Array, jax.Array]":
    """(x // d, x % d) for int64 x >= 0 and d >= 1, exactly, without an
    int64 divide. Invariant x == q * d + r holds in int64 throughout; each
    round takes a float32 estimate of r / d, biased low so r stays >= 0,
    and a lane whose estimate truncates to 0 steps by one. Shapes
    broadcast; works under jit, vmap and shard_map."""
    x, d = jnp.broadcast_arrays(jnp.asarray(x, jnp.int64), jnp.asarray(d, jnp.int64))
    df = d.astype(jnp.float32)

    def unfinished(c):
        _, r = c
        return jnp.any((r < 0) | (r >= d))

    def refine(c):
        q, r = c
        est = (r.astype(jnp.float32) / df) * jnp.float32(1 - 2.0**-12)
        step = est.astype(jnp.int64)  # truncates toward zero
        one = jnp.where(r < 0, -1, (r >= d).astype(jnp.int64))
        step = jnp.where(step == 0, one, step)
        return q + step, r - step * d

    return jax.lax.while_loop(unfinished, refine, (jnp.zeros_like(x), x))

