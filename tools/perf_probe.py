"""Quick engine-throughput probe at bench scale (bounded horizon).
Usage:

  python tools/perf_probe.py [hosts] [sim_ms] [active_lanes] [rpc]

Prints one JSON line with wall time, events, and events/s for the tgen
bench workload (same builder as bench.py)."""

import json
import sys
import time

sys.path.insert(0, ".")


def main():
    hosts = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    sim_ms = int(sys.argv[2]) if len(sys.argv) > 2 else 100
    lanes = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    rpc = int(sys.argv[4]) if len(sys.argv) > 4 else 16

    import dataclasses
    import os

    import jax
    import numpy as np

    from bench import _build
    from shadow_tpu.engine.round import run_until

    cfg, model, tables, st0 = _build(hosts)
    if lanes:
        cfg = dataclasses.replace(cfg, active_lanes=lanes)
    # experiment knobs (bottleneck isolation)
    overrides = {}
    if os.environ.get("SHADOW_PROBE_QCAP"):
        overrides["queue_capacity"] = int(os.environ["SHADOW_PROBE_QCAP"])
    if os.environ.get("SHADOW_PROBE_NETSTACK") == "0":
        overrides["use_netstack"] = False
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
        from shadow_tpu.engine.round import bootstrap
        from shadow_tpu.engine.state import init_state
        from shadow_tpu.netstack import bw_bits_per_sec_to_refill

        bw = bw_bits_per_sec_to_refill(100_000_000) if cfg.use_netstack else None
        st0 = bootstrap(
            init_state(cfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw),
            model,
            cfg,
        )
    end = sim_ms * 1_000_000

    t0 = time.perf_counter()
    run_until(st0, 2_000_000, model, tables, cfg, rounds_per_chunk=rpc)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    st = run_until(st0, end, model, tables, cfg, rounds_per_chunk=rpc, max_chunks=1_000_000)
    jax.block_until_ready(st.events_handled)
    wall = time.perf_counter() - t0
    ev = int(np.asarray(st.events_handled).sum())
    iters = int(np.asarray(st.iters_done).sum())
    print(
        json.dumps(
            {
                "backend": jax.default_backend(),
                "hosts": hosts,
                "sim_ms": sim_ms,
                "active_lanes": lanes,
                "rpc": rpc,
                "compile_s": round(compile_s, 1),
                "wall_s": round(wall, 2),
                "events": ev,
                "events_per_s": int(ev / wall) if wall > 0 else None,
                "sim_per_wall": round(sim_ms / 1000.0 / wall, 4),
                "iters": iters,
                "events_per_iter": round(ev / iters, 2) if iters else None,
                "us_per_iter": round(wall / iters * 1e6, 1) if iters else None,
                "streams_done": int(np.asarray(st.model.streams_done).sum()),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
