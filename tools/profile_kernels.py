"""Kernel-count vs width, measured per-iteration engine costs, and
chunk-driver dispatch accounting.

Part 1 (width scan): compile the plain iteration body at several host
widths on the live backend, print optimized-HLO fusion/kernel counts and
fresh-input timings. If time is ~flat in width while kernel count is
constant, the body is launch-bound and the lever is fewer kernels.

Part 2 (engine comparison, round-6 verdict Next #3): measure the
per-iteration cost of all three round engines — plain (one-event-per-host
handler), pump (XLA microscan, engine/pump.py) and megakernel (fused
Pallas launch, engine/megakernel.py) — on the bench workload's burst
phase. All three are bit-identical, so the comparison starts every
engine from the same mid-burst state and divides wall time by the
drain-loop iterations actually executed (SimState.iters_done). The
resulting table is the one published in docs/megakernel.md.

Part 3 (dispatch pipeline, round-7 tentpole): on the same burst phase,
measure the dispatch gap — wall time between a chunk completing on
device and the next chunk's launch — for the synchronous driver shape
(block on the probe, run the old _peek_next_time decision, then launch)
vs the depth-2 pipelined driver (launch N+1 BEFORE fetching N's probe:
the gap collapses to zero because the next chunk is already queued when
completion is even observable). Also reports per-chunk HBM copy bytes
from the compiled chunk's memory analysis with and without state
donation: donated runs alias the whole SimState in place
(aliased_bytes ~= state size, copied_bytes ~= the probe).

Part 4 (checkpoint, robustness round): save/restore wall + bytes.

Part 5 (ensemble round): amortized per-replica launch cost vs replica
count R — wall-clock per replica at R=1/8/32 through the vmapped
ensemble driver (docs/ensemble.md).

Part 6 (sweep-scheduler round, docs/service.md): cold-compile vs
cache-hit dispatch wall for the fingerprint-keyed compile cache — the
AOT compile a world's FIRST batch pays, the ~free executable lookup
every later same-shape batch pays, and one cached-chunk dispatch — plus
amortized per-job wall vs sweep size (1/2/4/8 jobs through the
production SweepService).

Part 7 (adaptive-window round, docs/architecture.md "Lookahead &
compaction"): on a sparse-in-time scenario (hosts whose true lookahead
is 20x the graph's minimum latency), the drain-iteration reduction and
window-widening the adaptive LBTS bound buys vs fixed-width rounds —
per-run window-width distribution (log10 histogram of per-chunk mean
live widths), live-lane occupancy per drain iteration (the quantity
live-host compaction exploits), and the same run again under
active-lane compaction. The iteration-reduction factor printed here is
the published acceptance number for the adaptive-window round.

Part 9 (event-exchange v2 round, docs/parallelism.md "Segment
exchange"): per-phase cost of the round-boundary exchange — pool sort /
collective exchange / queue landing / capacity check — dense lane grid
vs sort-based segment exchange on the same busy staged outbox, plus
sharded per-round collective deltas and analytic bytes/host (dense
heuristic buckets vs the segment ring at measured exch_hwm capacity).

  python tools/profile_kernels.py [reps] [engine_hosts]

Env knobs: SHADOW_TPU_PROFILE_WIDTHS (comma list, part 1),
SHADOW_TPU_PROFILE_BURST_MS (start,end sim-ms for parts 2-3, default 20,60).
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, ".")


def _fusion_count(compiled_text: str) -> int:
    return len(re.findall(r"^\s*(fusion|%fusion)", compiled_text, re.M))


def profile_widths(reps: int):
    import jax
    import jax.numpy as jnp

    from bench import _build
    from shadow_tpu.engine.round import handle_one_iteration

    default_widths = (
        "1280,10240" if jax.default_backend() == "tpu" else "640,1280"
    )
    widths_env = os.environ.get("SHADOW_TPU_PROFILE_WIDTHS", default_widths)
    widths = [int(x) for x in widths_env.split(",") if x.strip()]
    we = jnp.asarray(10**15, jnp.int64)
    out = {}
    for hosts in widths:
        cfg, model, tables, st0 = _build(hosts)
        f = jax.jit(lambda s: handle_one_iteration(s, we, model, tables, cfg))
        compiled = f.lower(st0).compile()
        txt = compiled.as_text()
        # fresh-input timing
        st = f(st0)
        jax.block_until_ready(st.events_handled)
        ts = []
        for r in range(reps):
            s_in = st0.replace(rng_counter=st0.rng_counter + r + 1)
            jax.block_until_ready(s_in.rng_counter)
            t0 = time.perf_counter()
            o = f(s_in)
            jax.block_until_ready(o.events_handled)
            ts.append(time.perf_counter() - t0)
        out[hosts] = {
            "fusions": _fusion_count(txt),
            "hlo_lines": txt.count("\n"),
            "best_ms": round(min(ts) * 1e3, 2),
        }
        print(hosts, out[hosts], flush=True)
    return out


def profile_engines(reps: int, hosts: int):
    """Per-iteration cost of plain vs pump vs megakernel on the burst
    phase: identical start state (the engines are bit-identical, so any
    engine may produce it), wall divided by drain-loop iterations."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _build
    from shadow_tpu.engine.round import run_round, run_until

    burst_env = os.environ.get("SHADOW_TPU_PROFILE_BURST_MS", "20,60")
    b0_ms, b1_ms = [int(x) for x in burst_env.split(",")]
    b0, b1 = b0_ms * 1_000_000, b1_ms * 1_000_000

    cfg0, model, tables, st0 = _build(hosts)
    st_burst = run_until(st0, b0, model, tables, cfg0, rounds_per_chunk=32)
    jax.block_until_ready(st_burst.events_handled)
    iters0 = int(np.asarray(st_burst.iters_done).sum())
    ev0 = int(np.asarray(st_burst.events_handled).sum())

    variants = {
        "plain": dataclasses.replace(cfg0, engine="plain", pump_k=0),
        "pump": dataclasses.replace(cfg0, engine="pump", pump_k=8),
        "megakernel": dataclasses.replace(
            cfg0, engine="megakernel", pump_k=8
        ),
    }
    out = {}
    for name, cfg in variants.items():
        row = {}
        try:
            we = jnp.asarray(b0 + cfg.runahead_ns, jnp.int64)
            body = jax.jit(
                lambda s, c=cfg: run_round(s, we, model, tables, c)
            )
            row["fusions"] = _fusion_count(
                body.lower(st_burst).compile().as_text()
            )
            # warm the chunked executable, then time the burst window
            s = run_until(
                st_burst, b1, model, tables, cfg, rounds_per_chunk=32
            )
            jax.block_until_ready(s.events_handled)
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                s = run_until(
                    st_burst, b1, model, tables, cfg, rounds_per_chunk=32
                )
                jax.block_until_ready(s.events_handled)
                walls.append(time.perf_counter() - t0)
            wall = min(walls)
            iters = int(np.asarray(s.iters_done).sum()) - iters0
            events = int(np.asarray(s.events_handled).sum()) - ev0
            row.update(
                wall_s=round(wall, 3),
                iters=iters,
                events=events,
                us_per_iter=round(wall / max(iters, 1) * 1e6, 1),
                ns_per_event=round(wall / max(events, 1) * 1e9, 1),
            )
        except Exception as e:  # noqa: BLE001 — a backend that cannot
            # lower one engine must not kill the comparison of the others
            row["error"] = str(e)[:300]
        out[name] = row
        print(json.dumps({"engine": name, **row}), flush=True)
    if "us_per_iter" in out.get("plain", {}):
        for name in ("pump", "megakernel"):
            if "us_per_iter" in out.get(name, {}):
                out[name]["iter_cost_vs_plain"] = round(
                    out[name]["us_per_iter"] / out["plain"]["us_per_iter"], 3
                )
    return out


def profile_dispatch(hosts: int, chunks: int = 6):
    """Dispatch accounting on the burst phase, read from the tracker
    plane's spans (round-8 tentpole): the REAL run_until driver runs
    with a utils/tracker.py Tracker attached — the same spans
    `--trace-file` writes — and the sync decision gap / pipelined
    launch-ahead margin / per-launch call wall are computed from the
    recorded (ts, dur) intervals instead of an ad-hoc reimplementation
    of the drive loop. Also reports per-chunk HBM copy bytes (donated
    vs undonated chunk executable)."""
    import jax
    import jax.numpy as jnp

    from bench import _build
    from shadow_tpu.engine.round import _run_chunk, _run_chunk_jit, run_until
    from shadow_tpu.utils.tracker import Tracker

    burst_env = os.environ.get("SHADOW_TPU_PROFILE_BURST_MS", "20,60")
    b0_ms = int(burst_env.split(",")[0])
    b0 = b0_ms * 1_000_000

    cfg, model, tables, st0 = _build(hosts)
    st_burst = run_until(st0, b0, model, tables, cfg, rounds_per_chunk=32)
    jax.block_until_ready(st_burst.events_handled)
    far = 10**15  # far horizon: chunks never quiesce
    end = jnp.asarray(far, jnp.int64)
    rpc = 8
    out = {"hosts": hosts, "rounds_per_chunk": rpc, "chunks": chunks}

    # --- per-chunk HBM copy bytes, before/after donation -----------------
    def _nbytes(leaf):
        try:
            return leaf.nbytes
        except Exception:  # typed PRNG key arrays: measure the raw words
            return jax.random.key_data(leaf).nbytes

    out["state_bytes"] = int(sum(_nbytes(l) for l in jax.tree.leaves(st_burst)))
    try:
        plain = jax.jit(_run_chunk, static_argnums=(2, 3, 5))
        rows = {}
        for name, fn in (("no_donate", plain), ("donate", _run_chunk_jit)):
            ma = (
                fn.lower(st_burst, end, rpc, model, tables, cfg)
                .compile()
                .memory_analysis()
            )
            rows[name] = {
                "output_bytes": int(ma.output_size_in_bytes),
                "aliased_bytes": int(ma.alias_size_in_bytes),
                "copied_bytes": int(
                    ma.output_size_in_bytes - ma.alias_size_in_bytes
                ),
            }
        out["per_chunk_copy"] = rows
    except Exception as e:  # noqa: BLE001 — memory analysis is best-effort
        out["per_chunk_copy"] = {"error": str(e)[:200]}

    # --- dispatch gap from tracker spans ---------------------------------
    def drive(pipeline):
        """Run exactly `chunks` launches through the production driver
        with a Tracker attached; the bounded max_chunks stop raises
        RuntimeError by design (the horizon is unreachable). Only THAT
        stop is absorbed — a CapacityError or any other runtime failure
        must surface, not publish gap numbers from a dead run."""
        tr = Tracker()
        try:
            run_until(
                st_burst, far, model, tables, cfg, rounds_per_chunk=rpc,
                max_chunks=chunks, pipeline=pipeline, tracker=tr,
            )
        except RuntimeError as e:
            if "did not reach end_time" not in str(e):
                raise  # capacity/donation/backend errors are real
        launches = {
            e.get("args", {}).get("chunk"): e
            for e in tr.spans("compile+launch") + tr.spans("chunk_launch")
        }
        fetches = {
            e.get("args", {}).get("chunk"): e for e in tr.spans("probe_fetch")
        }
        return tr, launches, fetches

    def _span_end(e):
        return e["ts"] + e["dur"]

    drive(True)  # warm the chunk executable (its spans are discarded)
    _tr, launches, fetches = drive(False)
    # synchronous driver: the device idles from probe-fetch end (chunk N
    # observed done, decision made) to the next launch call — plus the
    # launch call itself (reported separately: XLA:CPU executes inline
    # during dispatch, which would otherwise masquerade as decision time)
    gaps = [
        (launches[i + 1]["ts"] - _span_end(fetches[i])) / 1e3
        for i in range(chunks - 1)
        if i + 1 in launches and i in fetches
    ]
    dwalls = [launches[i]["dur"] / 1e3 for i in launches if i > 0]
    out["dispatch_gap_sync_ms"] = {
        "mean": round(sum(gaps) / max(len(gaps), 1), 3),
        "max": round(max(gaps), 3),
        "launch_call_mean_ms": round(sum(dwalls) / max(len(dwalls), 1), 3),
    }
    _tr, launches, fetches = drive(True)
    # pipelined: chunk N+1's launch span ENDS before chunk N's probe
    # fetch does — the gap is 0 by construction; the measured quantity is
    # the launch-ahead margin (how long before chunk N's completion was
    # even observable the next chunk was already dispatched)
    ahead = [
        (_span_end(fetches[i]) - _span_end(launches[i + 1])) / 1e3
        for i in range(chunks - 1)
        if i + 1 in launches and i in fetches
    ]
    dwalls = [launches[i]["dur"] / 1e3 for i in launches if i > 0]
    out["dispatch_gap_pipelined_ms"] = {
        "by_construction": 0.0,
        "launch_ahead_mean_ms": round(sum(ahead) / max(len(ahead), 1), 3),
        "launch_call_mean_ms": round(sum(dwalls) / max(len(dwalls), 1), 3),
    }
    print(json.dumps({"dispatch": out}), flush=True)
    return out


def profile_checkpoint(hosts: int, reps: int = 3):
    """Part 4 (robustness round): wall time and bytes of a checkpoint
    save (state_to_host bulk fetch + atomic npz write) and restore (npz
    read + state_from_host upload), on a mid-burst state — the cost a
    --checkpoint-interval cadence actually pays per checkpoint, and the
    transfer the rollback-and-regrow retainer pays per snapshot. Also
    verifies the restore is leaf-exact."""
    import tempfile

    import jax
    import numpy as np

    from bench import _build
    from shadow_tpu.engine.round import run_until
    from shadow_tpu.engine.state import (
        _is_key_leaf,
        state_from_host,
        state_to_host,
    )
    from shadow_tpu.runtime.checkpoint import load_checkpoint, save_checkpoint

    burst_env = os.environ.get("SHADOW_TPU_PROFILE_BURST_MS", "20,60")
    b0 = int(burst_env.split(",")[0]) * 1_000_000

    cfg, model, tables, st0 = _build(hosts)
    st = run_until(st0, b0, model, tables, cfg, rounds_per_chunk=32)
    jax.block_until_ready(st.events_handled)

    def _nbytes(leaf):
        try:
            return leaf.nbytes
        except Exception:
            return jax.random.key_data(leaf).nbytes

    out = {
        "hosts": hosts,
        "state_bytes": int(sum(_nbytes(l) for l in jax.tree.leaves(st))),
        "leaves": len(jax.tree.leaves(st)),
    }
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        fetch_ms, save_ms, load_ms = [], [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            host = state_to_host(st)
            fetch_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            save_checkpoint(path, host, {"fingerprint": "profile"})
            save_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            restored, _meta = load_checkpoint(path, st)
            jax.block_until_ready(restored.events_handled)
            load_ms.append((time.perf_counter() - t0) * 1e3)
        out["file_bytes"] = int(os.path.getsize(path))
        out["fetch_ms"] = round(min(fetch_ms), 2)
        out["save_ms"] = round(min(save_ms), 2)
        out["restore_ms"] = round(min(load_ms), 2)
        host = state_to_host(st)
        rt = state_from_host(host, st)
        out["roundtrip_exact"] = bool(
            all(
                np.array_equal(
                    np.asarray(jax.random.key_data(a) if _is_key_leaf(a) else a),
                    np.asarray(jax.random.key_data(b) if _is_key_leaf(b) else b),
                )
                for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(rt))
            )
        )
    print(json.dumps({"checkpoint": out}), flush=True)
    return out


def profile_ensemble(reps: int = 3, hosts: int = 0, replica_counts=(1, 8, 32)):
    """Part 5 (ensemble round): amortized per-replica cost vs R. The
    ensemble plane's claim is that stacking R replicas under one vmap
    amortizes the per-chunk dispatch/launch overhead (flat in R) across
    R worlds — so wall-clock PER REPLICA falls as R grows until compute
    saturates the backend. Measured on a small phold world (dispatch-
    bound by construction), with the production run_ensemble_until
    driver and a Tracker attached: per-R rows report total wall, wall
    per replica, the chunk-launch span total, and launch wall per
    replica (the directly-amortized component)."""
    import time

    import jax
    import jax.numpy as jnp  # noqa: F401 — backend init ordering
    import numpy as np

    from shadow_tpu.engine import EngineConfig
    from shadow_tpu.engine.ensemble import init_ensemble_state, run_ensemble_until
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models import PholdModel
    from shadow_tpu.simtime import NS_PER_MS
    from shadow_tpu.utils.tracker import Tracker

    h = hosts or (1024 if jax.default_backend() == "tpu" else 128)
    n_nodes = 8
    lines = ["graph [", "  directed 0"]
    for i in range(n_nodes):
        lines.append(f"  node [ id {i} ]")
        lines.append(f'  edge [ source {i} target {i} latency "1 ms" ]')
        lines.append(
            f'  edge [ source {i} target {(i + 1) % n_nodes} latency "3 ms" ]'
        )
    lines.append("]")
    graph = NetworkGraph.from_gml("\n".join(lines))
    tables = compute_routing(graph).with_hosts([i % n_nodes for i in range(h)])
    cfg = EngineConfig(
        num_hosts=h, runahead_ns=graph.min_latency_ns(), seed=7
    )
    model = PholdModel(
        num_hosts=h, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    end = 100 * NS_PER_MS
    out = {"hosts": h, "sim_ms": 100, "rows": {}}
    base_per_replica = None
    for r_count in replica_counts:
        row = {}
        try:
            ens0 = init_ensemble_state(cfg, model, r_count)
            # compile (fresh executable per R: the batch shape changed)
            t0 = time.perf_counter()
            s = run_ensemble_until(ens0, end, model, tables, cfg, rounds_per_chunk=16)
            jax.block_until_ready(s.events_handled)
            row["compile_plus_run_s"] = round(time.perf_counter() - t0, 3)
            walls = []
            tr = Tracker()
            for _ in range(reps):
                t0 = time.perf_counter()
                s = run_ensemble_until(
                    ens0, end, model, tables, cfg,
                    rounds_per_chunk=16, tracker=tr,
                )
                jax.block_until_ready(s.events_handled)
                walls.append(time.perf_counter() - t0)
            wall = min(walls)
            launch_s = tr.phase_totals().get("chunk_launch", 0.0) / reps
            row.update(
                wall_s=round(wall, 4),
                wall_per_replica_ms=round(wall / r_count * 1e3, 2),
                launch_wall_s=round(launch_s, 4),
                launch_per_replica_ms=round(launch_s / r_count * 1e3, 3),
                events=int(np.asarray(s.events_handled).sum()),
            )
            if base_per_replica is None:
                base_per_replica = wall / r_count
            else:
                row["speedup_per_replica_vs_r1"] = round(
                    base_per_replica / (wall / r_count), 2
                )
        except Exception as e:  # noqa: BLE001 — one R failing (e.g. OOM at
            # 32 on a small backend) must not kill the smaller rows
            row["error"] = str(e)[:300]
        out["rows"][r_count] = row
        print(json.dumps({"ensemble_r": r_count, **row}), flush=True)
    return out


def profile_sweep(hosts: int = 0, capacity: int = 4):
    """Part 6 (sweep-scheduler round): what the compile cache buys.

    Cold vs hit: the first batch of a distinct world pays one AOT
    compile (lower_ensemble_chunk + .compile(), a CompileCache miss);
    every later same-shape batch acquires the executable from the cache
    (a dict lookup) — measured against one cached-chunk dispatch wall so
    the saving is in context. Then sweeps of 1/2/4/8 jobs run through
    the production SweepService and report wall per job: the amortized
    per-job overhead the service's packing + caching exist to shrink."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from shadow_tpu.config.sweep import load_sweep_spec
    from shadow_tpu.engine import EngineConfig
    from shadow_tpu.engine.ensemble import (
        ensemble_engine_cfg,
        init_ensemble_state,
        lower_ensemble_chunk,
    )
    from shadow_tpu.engine.state import trace_static_cfg
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models import PholdModel
    from shadow_tpu.runtime.compile_cache import CompileCache
    from shadow_tpu.runtime.sweep import SweepService
    from shadow_tpu.simtime import NS_PER_MS

    h = hosts or (1024 if jax.default_backend() == "tpu" else 128)
    graph = NetworkGraph.from_gml(
        "graph [\n  directed 0\n"
        + "".join(
            f"  node [ id {i} ]\n"
            f'  edge [ source {i} target {i} latency "1 ms" ]\n'
            f'  edge [ source {i} target {(i + 1) % 8} latency "3 ms" ]\n'
            for i in range(8)
        )
        + "]"
    )
    tables = compute_routing(graph).with_hosts([i % 8 for i in range(h)])
    cfg = EngineConfig(num_hosts=h, runahead_ns=graph.min_latency_ns(), seed=7)
    model = PholdModel(
        num_hosts=h, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    end, rpc = 100 * NS_PER_MS, 16
    out = {"hosts": h, "capacity": capacity}

    # --- cold compile vs cache hit ---------------------------------------
    cache = CompileCache()
    ens0 = init_ensemble_state(cfg, model, capacity)
    static = trace_static_cfg(ensemble_engine_cfg(cfg))

    def build():
        return lower_ensemble_chunk(ens0, end, rpc, model, tables, cfg).compile()

    t0 = time.perf_counter()
    exe = cache.get("world", ens0, static, build)
    out["cold_compile_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    exe = cache.get("world", ens0, static, build)
    out["cache_hit_lookup_s"] = round(time.perf_counter() - t0, 6)
    st = ens0.donatable()
    end_arr = jnp.asarray(end, jnp.int64)
    st, probe = exe(st, end_arr, tables)  # warm dispatch (donates st)
    jax.block_until_ready(probe)
    st2 = ens0.donatable()
    t0 = time.perf_counter()
    st2, probe = exe(st2, end_arr, tables)
    jax.block_until_ready(probe)
    out["cached_chunk_dispatch_s"] = round(time.perf_counter() - t0, 4)
    assert cache.misses == 1 and cache.hits == 1

    # --- amortized per-job overhead vs sweep size ------------------------
    base = {
        "general": {"stop_time": "100 ms", "heartbeat_interval": None},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "experimental": {"rounds_per_chunk": rpc},
        "hosts": {
            "peer": {
                "network_node_id": 0,
                "quantity": h,
                "processes": [
                    {
                        "path": "phold",
                        "args": {"min_delay": "1 ms", "max_delay": "8 ms"},
                    }
                ],
            }
        },
    }
    rows = []
    for jobs in (1, 2, 4, 8):
        with tempfile.TemporaryDirectory() as d:
            spec = load_sweep_spec(
                {
                    "sweep": {
                        "config": base,
                        "output_dir": os.path.join(d, "out"),
                        "capacity": capacity,
                        "jobs": [{"name": "ph", "seed_range": [0, jobs]}],
                    }
                }
            )
            svc = SweepService(spec)
            t0 = time.perf_counter()
            manifest = svc.run()
            wall = time.perf_counter() - t0
        rows.append(
            {
                "jobs": jobs,
                "wall_s": round(wall, 3),
                "wall_per_job_s": round(wall / jobs, 3),
                "compiles": manifest["compile_cache"]["compiles"],
                "cache_hits": manifest["compile_cache"]["hits"],
            }
        )
        print(json.dumps({"sweep_size": rows[-1]}), flush=True)
    out["per_sweep_size"] = rows
    print(json.dumps({"sweep": out}), flush=True)
    return out


def profile_adaptivity(hosts: int = 0):
    """Part 7 (adaptive-window round): what the LBTS window + compaction
    buy on a sparse-in-time world.

    Topology: hosts sit on nodes with 20 ms links, while a pair of
    host-less nodes carries the graph's 1 ms minimum-latency edge — so
    the FIXED conservative width is 1 ms although every host's true
    lookahead is 20 ms. phold with delays up to 50 ms makes event times
    sparse. The three runs are leaf-identical
    (tests/test_adaptive_window.py); only the round structure differs:

      fixed            adaptive_window=False — 1 ms windows, most empty
      adaptive         window_end = min(next_event + lookahead)
      adaptive_compact adaptive + active-lane compaction (gathered
                       [H/8]-row iterations)

    Reported per run: drain iterations, live/idle round split, mean live
    window width + its log10 per-chunk histogram, live-lane occupancy
    per iteration, wall. `iter_reduction` (fixed/adaptive iterations) is
    the published acceptance number."""
    import dataclasses

    import jax
    import numpy as np

    from bench import WidthCapture
    from shadow_tpu.engine import EngineConfig, init_state
    from shadow_tpu.engine.round import (
        ChunkProbe,
        bootstrap,
        run_until,
        state_probe,
    )
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models import PholdModel
    from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC

    h = hosts or (2560 if jax.default_backend() == "tpu" else 256)
    graph = NetworkGraph.from_gml(
        "\n".join(
            [
                "graph [",
                "  directed 0",
                *[f"  node [ id {i} ]" for i in range(4)],
                '  edge [ source 0 target 0 latency "20 ms" ]',
                '  edge [ source 1 target 1 latency "20 ms" ]',
                '  edge [ source 0 target 1 latency "20 ms" ]',
                '  edge [ source 2 target 3 latency "1 ms" ]',
                '  edge [ source 2 target 2 latency "1 ms" ]',
                '  edge [ source 3 target 3 latency "1 ms" ]',
                "]",
            ]
        )
    )
    tables = compute_routing(graph).with_hosts([i % 2 for i in range(h)])
    cfg0 = EngineConfig(
        num_hosts=h,
        queue_capacity=32,
        runahead_ns=graph.min_latency_ns(),
        seed=9,
        tracker=True,
    )
    model = PholdModel(
        num_hosts=h, min_delay_ns=1 * NS_PER_MS, max_delay_ns=50 * NS_PER_MS
    )
    st0 = bootstrap(init_state(cfg0, model.init()), model, cfg0)
    end = int(0.4 * NS_PER_SEC)

    def run_one(cfg):
        widths = WidthCapture()

        t0 = time.perf_counter()
        st = run_until(
            st0, end, model, tables, cfg, rounds_per_chunk=8,
            on_chunk=widths.update,
        )
        wall = time.perf_counter() - t0
        p = ChunkProbe.from_array(np.asarray(jax.jit(state_probe)(st)))
        return p, {
            "iters": p.iters,
            "rounds": {"live": p.rounds_live, "idle": p.rounds_idle},
            "window_ns_mean": round(p.window_ns_mean, 1),
            "window_ns_hist": widths.hist(),
            "occupancy": round(p.occupancy(h), 4),
            "events": p.events_handled,
            "wall_s": round(wall, 3),
        }

    out = {"hosts": h, "sim_s": end / NS_PER_SEC}
    pf, out["fixed"] = run_one(
        dataclasses.replace(cfg0, adaptive_window=False)
    )
    pa, out["adaptive"] = run_one(cfg0)
    _, out["adaptive_compact"] = run_one(
        dataclasses.replace(cfg0, active_lanes=max(h // 8, 8))
    )
    assert pa.events_handled == pf.events_handled  # leaf-identical runs
    out["iter_reduction"] = round(pf.iters / max(pa.iters, 1), 2)
    print(json.dumps({"adaptivity": out}), flush=True)
    return out


def profile_mesh_collectives(hosts: int = 0, sim_s: float = 0.1):
    """Part 8 (2-D mesh round, docs/parallelism.md "2-D mesh"): the
    per-round cost of the host-axis collectives vs shard count.

    The same single-replica phold world runs through the mesh chunk
    path (engine/mesh.py, 1xS grids) at every shard count that divides
    the visible devices; S=1 has no collectives at all, so the
    per-live-round wall delta vs the S=1 row IS the window-pmin +
    exchange-all_gather cost at that shard count (plus shard_map
    overheads — exactly the bundle a round pays). Trajectories are
    leaf-identical across S (tests/test_mesh.py), so rounds_live is the
    shared denominator. Also prints each grid's compile wall — the
    quantity the --autotune mesh-shape probe now projects (a
    single-device probe would report the S=1 column for every grid)."""
    import jax
    import numpy as np

    from shadow_tpu.engine import EngineConfig, init_state
    from shadow_tpu.engine.mesh import MeshPlan, init_mesh_state, run_mesh_until
    from shadow_tpu.engine.round import bootstrap
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models import PholdModel
    from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC

    ndev = jax.device_count()
    h = hosts or (10240 if jax.default_backend() == "tpu" else 512)
    h -= h % ndev  # every shard count below must divide evenly
    graph = NetworkGraph.from_gml(
        "\n".join(
            [
                "graph [",
                "  directed 0",
                *[f"  node [ id {i} ]" for i in range(4)],
                *[
                    f'  edge [ source {i} target {i} latency "1 ms" ]'
                    for i in range(4)
                ],
                *[
                    f'  edge [ source {i} target {j} latency "3 ms" ]'
                    for i in range(4)
                    for j in range(i + 1, 4)
                ],
                "]",
            ]
        )
    )
    tables = compute_routing(graph).with_hosts([i % 4 for i in range(h)])
    cfg = EngineConfig(
        num_hosts=h,
        runahead_ns=graph.min_latency_ns(),
        seed=13,
        tracker=True,
    )
    model = PholdModel(
        num_hosts=h, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    end = int(sim_s * NS_PER_SEC)
    shard_counts = [s for s in (1, 2, 4, 8, 16) if s <= ndev and ndev % s == 0]
    out = {"hosts": h, "sim_s": sim_s, "devices": ndev, "rows": []}
    base_per_round = None
    for s_count in shard_counts:
        plan = MeshPlan(replicas=1, shards=s_count, rows=1)
        row = {"shards": s_count}
        try:
            st0 = init_mesh_state(cfg, model, plan)
            t0 = time.perf_counter()
            st = run_mesh_until(
                st0, end, model, tables, cfg, plan, rounds_per_chunk=16
            )
            jax.block_until_ready(st.events_handled)
            row["compile_plus_run_s"] = round(time.perf_counter() - t0, 3)
            st0 = init_mesh_state(cfg, model, plan)
            t0 = time.perf_counter()
            st = run_mesh_until(
                st0, end, model, tables, cfg, plan, rounds_per_chunk=16
            )
            jax.block_until_ready(st.events_handled)
            wall = time.perf_counter() - t0
            rounds_live = int(np.asarray(st.tracker.rounds_live).max())
            per_round_ms = wall / max(rounds_live, 1) * 1e3
            row.update(
                wall_s=round(wall, 4),
                rounds_live=rounds_live,
                per_round_ms=round(per_round_ms, 3),
                compile_s=round(row["compile_plus_run_s"] - wall, 3),
            )
            if s_count == 1:
                # the baseline is the collective-FREE row specifically —
                # an errored S=1 must not silently shift it to S=2
                base_per_round = per_round_ms
            elif base_per_round is not None:
                row["collective_ms_per_round"] = round(
                    per_round_ms - base_per_round, 3
                )
        except Exception as e:  # noqa: BLE001 — publish the rows that ran
            row["error"] = str(e)[:300]
        out["rows"].append(row)
        print(json.dumps({"mesh_collectives_row": row}), flush=True)
    return out


def profile_exchange(hosts: int = 0, reps: int = 10):
    """Part 9 (event-exchange v2 round, docs/parallelism.md "Segment
    exchange"): per-phase cost of the round-boundary exchange — pool
    sort / collective exchange / queue landing / capacity check — for
    the dense lane grid vs the sort-based segment exchange.

    Single-device, the phases are timed as separately-jitted stages on
    the SAME busy staged outbox (a few handler iterations with the
    flush withheld):

      * sort — the segment pool compaction (one stable (dst, time, tie)
        multi-operand sort over the flattened outbox). The dense path
        has no standalone pre-sort: its three [H, lanes]-grid sorts live
        inside the landing, which is exactly the cost the segment
        layout removes.
      * landing — equeue.push_many_sorted (dense grid) vs
        equeue.push_many_segment (ragged segments) on the staged pool.
      * capacity-check — the driver's per-chunk _peek_capacity fetch
        ([5] scalars; mode-independent — segment just feeds the
        exchange-hwm lane the pool occupancy the per-round check uses).
      * full — the whole _flush_outbox_traffic per mode, the number the
        bench exchange trial publishes.

    Sharded (every visible device), the collective phase is isolated
    mesh-collectives-style: the per-live-round wall of the sharded run
    minus the single-device run of the same mode ≈ collective +
    shard_map overhead per round (trajectories are leaf-identical, so
    rounds_live is a shared denominator). Bytes/host per round are
    analytic from the static bucket shapes (dense heuristic buckets vs
    the segment ring at the measured exch_hwm capacity)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _event_slot_bytes
    from shadow_tpu import equeue
    from shadow_tpu.engine import EngineConfig, ShardedRunner, init_state
    from shadow_tpu.engine.round import (
        _flush_outbox_traffic,
        _payload_words,
        _peek_capacity,
        bootstrap,
        handle_one_iteration,
    )
    from shadow_tpu.engine.sharded import AXIS, auto_a2a_capacity
    from shadow_tpu.events import KIND_PACKET
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.models import PholdModel
    from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC

    ndev = jax.device_count()
    h = hosts or (10240 if jax.default_backend() == "tpu" else 512)
    h -= h % max(ndev, 1)
    graph = NetworkGraph.from_gml(
        "\n".join(
            [
                "graph [",
                "  directed 0",
                *[f"  node [ id {i} ]" for i in range(4)],
                *[
                    f'  edge [ source {i} target {i} latency "1 ms" ]'
                    for i in range(4)
                ],
                *[
                    f'  edge [ source {i} target {j} latency "3 ms" ]'
                    for i in range(4)
                    for j in range(i + 1, 4)
                ],
                "]",
            ]
        )
    )
    tables = compute_routing(graph).with_hosts([i % 4 for i in range(h)])
    cfg = EngineConfig(
        num_hosts=h, runahead_ns=graph.min_latency_ns(), seed=13, tracker=True
    )
    model = PholdModel(
        num_hosts=h, min_delay_ns=1 * NS_PER_MS, max_delay_ns=8 * NS_PER_MS
    )
    st0 = bootstrap(init_state(cfg, model.init()), model, cfg)
    we = jnp.asarray(10**15, jnp.int64)

    @jax.jit
    def _stage(st):
        def body(s, _):
            return handle_one_iteration(s, we, model, tables, cfg), None

        return jax.lax.scan(body, st, None, length=4)[0]

    busy = _stage(st0)
    jax.block_until_ready(busy.events_handled)
    staged = int(np.asarray(busy.outbox.fill).sum())

    def _timed(f, *args):
        jax.block_until_ready(jax.tree.leaves(f(*args))[0])  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            o = f(*args)
            jax.block_until_ready(jax.tree.leaves(o)[0])
            ts.append(time.perf_counter() - t0)
        return round(min(ts) * 1e3, 3)

    # --- single-device phase stages -----------------------------------
    ob = busy.outbox
    h_local, o_cap = ob.valid.shape
    m = h_local * o_cap

    @jax.jit
    def _pool_sort(ob):
        def flat(x):
            return x.reshape(m)

        valid, dst = flat(ob.valid), flat(ob.dst)
        t, tie, aux = flat(ob.time), flat(ob.tie), flat(ob.aux)
        key = jnp.where(valid, dst, jnp.int32(1 << 30))
        return jax.lax.sort(
            (key, t, tie, aux, valid, dst) + tuple(_payload_words(ob)),
            num_keys=3,
            is_stable=True,
        )

    pooled = _pool_sort(ob)
    _, time_p, tie_p, aux_p, valid_p, dst_p, *data_cols = pooled
    data_p = jnp.stack(data_cols, axis=-1)

    @jax.jit
    def _land_segment(q, dst, valid, t, tie, data, aux):
        return equeue.push_many_segment(
            q=q, dst=dst, valid=valid, time=t, tie=tie,
            kind=jnp.full(valid.shape, KIND_PACKET, jnp.int32),
            data=data, aux=aux,
        )

    @jax.jit
    def _land_dense(q, ob):
        def flat(x):
            return x.reshape(m)

        lanes = cfg.deliver_lanes if cfg.deliver_lanes > 0 else q.capacity
        return equeue.push_many_sorted(
            deliver_lanes=lanes, q=q, dst=flat(ob.dst), valid=flat(ob.valid),
            time=flat(ob.time), tie=flat(ob.tie),
            kind=jnp.full((m,), KIND_PACKET, jnp.int32),
            data=_payload_words(ob).T, aux=flat(ob.aux),
        )

    peek = jax.jit(_peek_capacity)

    def _check(st):
        return np.asarray(peek(st))

    phases = {
        "capacity_check_ms": _timed(_check, busy),
        "segment": {
            "sort_ms": _timed(_pool_sort, ob),
            "landing_ms": _timed(
                _land_segment, busy.queue, dst_p, valid_p, time_p, tie_p,
                data_p, aux_p,
            ),
            "full_flush_ms": _timed(
                jax.jit(
                    lambda s: _flush_outbox_traffic(
                        s, None, dataclasses.replace(cfg, exchange="segment")
                    )
                ),
                busy,
            ),
        },
        "dense": {
            # the dense grid's three sorts are inside the landing — the
            # per-phase split the segment layout makes possible is the
            # point of the comparison
            "sort_ms": None,
            "landing_ms": _timed(_land_dense, busy.queue, ob),
            "full_flush_ms": _timed(
                jax.jit(
                    lambda s: _flush_outbox_traffic(
                        s, None, dataclasses.replace(cfg, exchange="dense")
                    )
                ),
                busy,
            ),
        },
    }
    out = {
        "hosts": h,
        "staged_events": staged,
        "slot_bytes": _event_slot_bytes(ob),
        "phases": phases,
    }
    print(json.dumps({"exchange_phases": phases}), flush=True)

    # --- sharded: collective phase by per-round delta vs single -------
    if ndev > 1 and h % ndev == 0:
        from jax.sharding import Mesh

        from shadow_tpu.engine.round import run_until

        end = int(0.05 * NS_PER_SEC)
        slot_bytes = out["slot_bytes"]
        rows = []
        measured_hwm = None
        for mode in ("dense", "segment"):
            row = {"mode": mode, "devices": ndev}
            try:
                mcfg = dataclasses.replace(cfg, exchange=mode)
                single = run_until(
                    st0, end, model, tables, mcfg, rounds_per_chunk=16
                )
                t0 = time.perf_counter()
                single = run_until(
                    st0, end, model, tables, mcfg, rounds_per_chunk=16
                )
                jax.block_until_ready(single.events_handled)
                single_wall = time.perf_counter() - t0
                runner = ShardedRunner(
                    Mesh(np.array(jax.devices()), (AXIS,)), model, tables,
                    mcfg, rounds_per_chunk=16,
                    measured_exchange_hwm=measured_hwm,
                )
                s = runner.run_until(st0, end)
                jax.block_until_ready(s.events_handled)
                t0 = time.perf_counter()
                s = runner.run_until(st0, end)
                jax.block_until_ready(s.events_handled)
                wall = time.perf_counter() - t0
                rl = int(np.asarray(s.tracker.rounds_live).max())
                hwm = int(np.asarray(s.tracker.exch_hwm).max())
                cap = auto_a2a_capacity(mcfg, ndev, measured_hwm=measured_hwm)
                row.update(
                    per_round_ms=round(wall / max(rl, 1) * 1e3, 3),
                    exchange_ms_per_round=round(
                        (wall - single_wall) / max(rl, 1) * 1e3, 3
                    ),
                    exch_hwm=hwm,
                    bucket_capacity=cap,
                    bytes_per_host_per_round=round(
                        (ndev - 1) * cap * slot_bytes / (h // ndev), 1
                    ),
                )
                if mode == "dense":
                    measured_hwm = hwm
            except Exception as e:  # noqa: BLE001 — publish the rows that ran
                row["error"] = str(e)[:300]
            rows.append(row)
            print(json.dumps({"exchange_sharded_row": row}), flush=True)
        out["sharded"] = {"devices": ndev, "rows": rows}
    return out


def profile_memory(sizes=(256, 1024, 4096)):
    """Part 10 (memory observatory round): the three memory layers side
    by side per world size — the STATIC priced state (runtime/memtrack.py,
    exact leaf bytes), the COMPILED peak XLA reports for one chunk
    executable (arguments + outputs + temps − donation aliases), and the
    MEASURED device bytes_in_use where the backend exposes memory_stats
    (TPU/GPU; CPU reports none and says so). Also publishes the
    per-subsystem breakdown and checks the dominant grid is the queue's
    [H, C] event rows — the scaling story docs/observability.md tells."""
    import jax
    import jax.numpy as jnp

    from bench import _build
    from shadow_tpu.engine.round import _run_chunk
    from shadow_tpu.runtime import memtrack

    rows = []
    for hosts in sizes:
        cfg, model, tables, st0 = _build(hosts)
        report = memtrack.price_state(st0, cfg)
        row = {
            "hosts": hosts,
            "static_bytes": report["total_bytes"],
            "bytes_per_host": report["bytes_per_host"],
            "groups": {
                name: g["bytes"] for name, g in report["groups"].items()
            },
            "dominant": report["dominant"]["name"],
            "dominant_is_queue": report["dominant"]["name"].startswith(
                "queue."
            ),
        }
        try:
            exe = (
                jax.jit(_run_chunk, static_argnums=(2, 3, 5))
                .lower(
                    st0, jnp.asarray(10**15, jnp.int64), 8, model, tables,
                    cfg,
                )
                .compile()
            )
            cm = memtrack.compiled_memory(exe)
            if cm:
                row["compiled"] = cm
        except Exception as e:  # noqa: BLE001 — memory analysis is best-effort
            row["compiled"] = {"error": str(e)[:200]}
        dm = memtrack.device_memory()
        row["device"] = dm if dm else "backend reports no memory_stats"
        rows.append(row)
        print(json.dumps({"memory_row": row}), flush=True)
    return {"rows": rows}


def main():
    import jax

    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    eng_hosts = (
        int(sys.argv[2])
        if len(sys.argv) > 2
        else (10240 if jax.default_backend() == "tpu" else 640)
    )
    out = {"backend": jax.default_backend()}
    out["widths"] = profile_widths(reps)
    out["engines"] = profile_engines(reps, eng_hosts)
    out["dispatch"] = profile_dispatch(eng_hosts)
    out["checkpoint"] = profile_checkpoint(eng_hosts)
    out["ensemble"] = profile_ensemble(min(reps, 3))
    out["sweep"] = profile_sweep()
    out["adaptivity"] = profile_adaptivity()
    out["mesh_collectives"] = profile_mesh_collectives()
    out["exchange"] = profile_exchange()
    out["memory"] = profile_memory()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
