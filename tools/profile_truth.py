"""Ground-truth timings on the live backend with FRESH inputs per call.

profile_while/profile_scan re-invoke the same jitted fn with the SAME
input buffers; if any layer dedupes identical executions, their numbers
collapse to the dispatch floor and lie (round-4's 59us-scan reading). Every timed call here perturbs the input state (a
different rng_counter bump), so no layer can serve a cached result.

Measures, at bench shapes:
  call_floor        jit identity on the state (dispatch floor)
  while_trivial     while_loop of N counter bumps (no body work)
  scan_body[N]      scan of N handle_one_iteration bodies, fresh input
  while_body[N]     while-loop-driven N bodies (cond: iters < N), fresh
  round_while       the real run_round (8 real rounds, fresh input)
  flush             one flush_outbox per call, fresh input

  python tools/profile_truth.py [hosts] [reps]
"""

import json
import sys
import time

sys.path.insert(0, ".")


def main():
    hosts = int(sys.argv[1]) if len(sys.argv) > 1 else 10240
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 5

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _build
    from shadow_tpu.engine.round import (
        _next_window_end,
        flush_outbox,
        handle_one_iteration,
        run_round,
    )

    cfg, model, tables, st0 = _build(hosts)
    we_far = jnp.asarray(10**18, jnp.int64)

    # a realistic mid-sim state: run a few rounds first
    warm = jax.jit(
        lambda s: run_round(
            s, _next_window_end(s, we_far, cfg, None), model, tables, cfg
        )
    )
    st = st0
    for _ in range(3):
        st = warm(st)
    jax.block_until_ready(st.events_handled)

    results = {"backend": jax.default_backend(), "hosts": hosts}

    def timed(name, fn, n_inner=1):
        f = jax.jit(fn)
        out = f(st, jnp.uint32(999))  # compile
        jax.block_until_ready(out)
        ts = []
        for r in range(reps):
            s_in = st
            t0 = time.perf_counter()
            out = f(s_in, jnp.uint32(r))  # fresh scalar => fresh execution
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        best = min(ts)
        results[name] = {
            "ms": round(best * 1e3, 3),
            "ms_per_inner": round(best * 1e3 / n_inner, 4),
        }
        print(name, results[name], flush=True)

    # dispatch floor: return a scalar derived from the state
    timed("call_floor", lambda s, r: s.events_handled.sum() + r)

    # while_loop overhead with a trivial body (r keeps inputs fresh
    # without changing the 64-iteration trip count)
    def while_trivial(s, r):
        def cond(c):
            return c[0] < 64
        def body(c):
            return (c[0] + 1, c[1] + c[0])
        i, acc = jax.lax.while_loop(cond, body, (r * 0, jnp.uint32(0)))
        return acc + s.events_handled[0] + r
    timed("while_trivial_64", while_trivial, n_inner=64)

    we = jnp.asarray(int(np.asarray(st.now)) + 10**15, jnp.int64)

    def mk_scan(n):
        def f(s, r):
            s = s.replace(rng_counter=s.rng_counter + r * 0)
            s = s.replace(seq=s.seq + r * 0)

            def inner(s, _):
                return handle_one_iteration(s, we, model, tables, cfg), None

            s, _ = jax.lax.scan(inner, s, None, length=n)
            return s.events_handled.sum() + r
        return f

    def mk_while(n):
        def f(s, r):
            def cond(c):
                return c[1] < n

            def body(c):
                s, i = c
                return handle_one_iteration(s, we, model, tables, cfg), i + 1

            s, _ = jax.lax.while_loop(cond, body, (s, r * 0))
            return s.events_handled.sum() + r
        return f

    timed("scan_body_16", mk_scan(16), n_inner=16)
    timed("while_body_16", mk_while(16), n_inner=16)
    timed("scan_body_64", mk_scan(64), n_inner=64)

    def one_flush(s, r):
        s = s.replace(rng_counter=s.rng_counter + r * 0)
        s = flush_outbox(s, None, cfg)
        return s.queue.count.sum() + r
    timed("flush", one_flush)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
