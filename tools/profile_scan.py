"""On-device per-phase cost: time big compiled scans (one dispatch each)
so per-call dispatch overhead cannot contaminate the numbers
(tools/profile_iter.py's standalone timings all sit on that floor). Phases: N pop-iterations (no flush), N outbox flushes, N full
rounds, and N iterations with the model handler replaced by an identity
(isolates the 15k-op tgen/TCP handler from queue mechanics).

  python tools/profile_scan.py [hosts] [N]
"""

import json
import sys
import time

sys.path.insert(0, ".")


def timed(fn, *args):
    import jax

    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def main():
    hosts = int(sys.argv[1]) if len(sys.argv) > 1 else 10240
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 64

    import jax
    import jax.numpy as jnp

    from bench import _build
    from shadow_tpu.engine.round import (
        flush_outbox,
        handle_one_iteration,
        run_round,
    )

    cfg, model, tables, st0 = _build(hosts)
    we = jnp.asarray(40_000_000, jnp.int64)

    print("warming one round...", flush=True)
    warm = jax.jit(lambda s: run_round(s, we, model, tables, cfg))
    st = warm(st0)
    jax.block_until_ready(st.events_handled)

    results = {"backend": jax.default_backend(), "hosts": hosts, "n": n}

    def scan_iters(s):
        def body(s, _):
            return handle_one_iteration(s, we, model, tables, cfg), None
        s, _ = jax.lax.scan(body, s, None, length=n)
        return s

    def scan_flush(s):
        def body(s, _):
            return flush_outbox(s, None, cfg), None
        s, _ = jax.lax.scan(body, s, None, length=n)
        return s

    def scan_rounds(s):
        def body(s, _):
            return run_round(s, we, model, tables, cfg), None
        s, _ = jax.lax.scan(body, s, None, length=n)
        return s

    class _IdModel:
        """Identity handler with tgen's emit shapes: isolates queue
        mechanics + netstack from the TCP handler's op count."""
        LOCAL_EMITS = model.LOCAL_EMITS
        PACKET_EMITS = model.PACKET_EMITS
        DRAWS_PER_EVENT = 0
        BOOTSTRAP_DRAWS = 0
        LOSS_COUNTER_LANE = None

        def __hash__(self):
            return 1

        def __eq__(self, other):
            return isinstance(other, _IdModel)

        def handle(self, mstate, ev, draw, cfg_, host_id):
            from shadow_tpu.engine.state import (
                empty_local_emits,
                empty_packet_emits,
            )
            h = host_id.shape[0]
            return mstate, empty_local_emits(h, self.LOCAL_EMITS), \
                empty_packet_emits(h, self.PACKET_EMITS)

    idm = _IdModel()

    def scan_iters_noop(s):
        def body(s, _):
            return handle_one_iteration(s, we, idm, tables, cfg), None
        s, _ = jax.lax.scan(body, s, None, length=n)
        return s

    for name, fn in (
        ("iters", scan_iters),
        ("iters_noop_handler", scan_iters_noop),
        ("flush", scan_flush),
        ("rounds", scan_rounds),
    ):
        print(f"compiling {name}...", flush=True)
        f = jax.jit(fn)
        t = timed(f, st)
        results[f"{name}_ms_per"] = round(t / n * 1e3, 3)
        print(name, results[f"{name}_ms_per"], flush=True)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
