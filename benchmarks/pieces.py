"""Blocked-call timings of two pieces of a round on the cell's warm state:
`handle_one_iteration` (per drain iteration) and `flush_outbox` on the
outbox those iterations filled. A copy of the "world" part of
tools/profile_landing.py, started from the warm state and not from t=0.

These are separately compiled pieces, timed from the host with
`block_until_ready`: what a piece costs alone, not what it costs fused into
the chunk program. They stand until the program names its layers in the
chunk's own trace (`jax.named_scope`, see PERF.md).
"""

from __future__ import annotations

import statistics
import time

ITERS = 24
REPS = 9


def _timed(fn, *a, per=1):
    import jax

    out = jax.block_until_ready(fn(*a))  # compiles, or loads from the cache
    ms = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        ms.append((time.perf_counter() - t0) * 1e3 / per)
    return out, statistics.median(ms)


def measure(s_warm, world, warm_probe) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from shadow_tpu.engine import round as rnd

    import roofline

    cfg = world.ecfg
    # the window the next round would drain: from the earliest pending event
    window_end = jnp.asarray(int(warm_probe.next_time) + cfg.runahead_ns, jnp.int64)

    def fill(s, tables):
        def body(s, _):
            return rnd.handle_one_iteration(s, window_end, world.model, tables, cfg), None

        return jax.lax.scan(body, s, None, length=ITERS)[0]

    filled, iter_ms = _timed(jax.jit(fill), s_warm, world.tables, per=ITERS)
    staged = int(np.asarray(filled.outbox.valid).sum())
    _out, flush_ms = _timed(jax.jit(lambda s: rnd.flush_outbox(s, None, cfg)), filled)
    return {
        "iter_ms": iter_ms,
        "flush_ms": flush_ms,
        "staged_packets": staged,
        "flush_min_bytes": roofline.flush_min_bytes(filled.outbox, filled.queue),
    }
