"""De-noised stage timings: 64 inner reps per call so the per-call
dispatch floor cannot swamp per-stage deltas. Fresh inputs per call.

  python tools/profile_truth3.py [hosts]
"""

import json
import sys
import time

sys.path.insert(0, ".")


def main():
    hosts = int(sys.argv[1]) if len(sys.argv) > 1 else 10240
    reps = 3
    N = 64

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _build
    from shadow_tpu.engine.round import (
        _next_window_end,
        flush_outbox,
        handle_one_iteration,
        handle_one_iteration_compact,
        run_round,
    )

    cfg, model, tables, st0 = _build(hosts)
    we_far = jnp.asarray(10**18, jnp.int64)

    warm = jax.jit(
        lambda s: run_round(
            s, _next_window_end(s, we_far, cfg, None), model, tables, cfg
        )
    )
    st = st0
    for _ in range(3):
        st = warm(st)
    jax.block_until_ready(st.events_handled)
    results = {"backend": jax.default_backend(), "hosts": hosts, "n_inner": N}

    def timed(name, fn, n_inner=N):
        f = jax.jit(fn)
        out = f(st, jnp.uint32(999))
        jax.block_until_ready(out)
        ts = []
        for r in range(reps):
            t0 = time.perf_counter()
            out = f(st, jnp.uint32(r))
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        best = min(ts)
        results[name] = {
            "total_ms": round(best * 1e3, 1),
            "per_ms": round(best * 1e3 / n_inner, 3),
        }
        print(name, results[name], flush=True)

    # floor reference
    timed("floor", lambda s, r: s.events_handled.sum() + r, n_inner=1)

    # true all-in per-round cost: N real rounds in one call
    def rounds_n(s, r):
        s = s.replace(seq=s.seq + r * 0)

        def one(s, _):
            we = _next_window_end(s, we_far, cfg, None)
            return run_round(s, we, model, tables, cfg), None

        s, _ = jax.lax.scan(one, s, None, length=N)
        return s.events_handled.sum() + r

    timed("round_allin", rounds_n)

    # flush at various deliver_lanes
    def mk_flush(lanes):
        c2 = dataclasses.replace(cfg, deliver_lanes=lanes)

        def f(s, r):
            s = s.replace(seq=s.seq + r * 0)

            def step(q, _):
                s2 = flush_outbox(s.replace(queue=q), None, c2)
                return s2.queue, None

            q, _ = jax.lax.scan(step, s.queue, None, length=N)
            return q.count.sum() + q.tie.sum() + r

        return f

    for lanes in (64, 32):
        timed(f"flush_d{lanes}", mk_flush(lanes))

    # bodies
    we = jnp.asarray(int(np.asarray(st.now)) + 10**15, jnp.int64)

    def mk_body(fn):
        def f(s, r):
            s = s.replace(seq=s.seq + r * 0)

            def inner(s, _):
                return fn(s), None

            s, _ = jax.lax.scan(inner, s, None, length=N)
            return s.events_handled.sum() + r

        return f

    timed("body_full", mk_body(lambda s: handle_one_iteration(s, we, model, tables, cfg)))
    for lanes in (256, 1024):
        timed(
            f"body_compact{lanes}",
            mk_body(
                lambda s, L=lanes: handle_one_iteration_compact(
                    s, we, model, tables, cfg, L
                )
            ),
        )

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
