"""Compile-budget autotuner: pick `rounds_per_chunk` (and a pump_k cap)
BEFORE paying a full-scale XLA compile.

One rounds_per_chunk=128 compile at 10240 hosts once took a whole
1100 s attempt before any fallback ran (BENCH_r05 published null). This
module is the pre-probe that prevents it, a service every driver can run
under:

  * scan-chunk compile cost is ~linear in the scan length
    (rounds_per_chunk), so compiling a TINY chunk (probe_rpc rounds)
    projects the full-rpc compile wall with one cheap measurement;
  * given an explicit wall budget, the planner walks a candidate ladder
    (requested → 128 → 64 → 32 → 16) and picks the LARGEST
    rounds_per_chunk whose projected compile (times the number of engine
    compiles about to happen) fits — a too-small chunk costs some
    dispatch overhead, a too-large one costs the whole run;
  * probe walls are persisted to a small JSON cache keyed by the
    canonicalized static EngineConfig (engine/state.py trace_static_cfg —
    the same seed-canonicalized key the compile cache uses, so worlds
    differing only in seed share one probe) plus the backend, so repeat
    runs of the same world skip the probe entirely.

The choice is trajectory-neutral: rounds_per_chunk only groups rounds
into device dispatches (quiescent tails take the idle branch), so two
runs differing only in the autotuned value are leaf-identical — which is
why the knobs are excluded from the config fingerprint
(config/fingerprint.py) and an autotuned resume stays bit-exact.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

DEFAULT_CANDIDATES = (128, 64, 32, 16)
# the smallest chunk the planner will ever choose; also the threshold
# below which probing is pointless (a 16-round compile cannot meaningfully
# outcost its own probe)
RPC_FLOOR = 16
PROBE_RPC = 4
PROBE_END_NS = 10_000_000


@dataclasses.dataclass(frozen=True)
class AutotunePlan:
    """One rounds_per_chunk decision, with the evidence it was made on.
    `source`: "probe" (fresh tiny-chunk measurement), "cache" (persisted
    probe wall reused), "floor" (requested already at/below the floor),
    or "disabled" (no budget given)."""

    rounds_per_chunk: int
    requested: int
    budget_s: float
    n_compiles: float
    probe_rpc: int
    probe_wall_s: "float | None"
    projected_compile_s: "float | None"
    pump_k: "int | None"  # None = keep the caller's value
    source: str
    backend: str = ""
    # XLA-reported peak HBM of the probe chunk's executable (memory
    # observatory): best-effort — None where the backend doesn't report
    # a memory analysis or the probe was skipped
    peak_hbm_bytes: "int | None" = None

    def as_dict(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items() if v is not None}


def _cache_key(cfg, probe_rpc: int, backend: str, shape_key: str = "") -> str:
    from shadow_tpu.engine.state import trace_static_cfg

    blob = f"{trace_static_cfg(cfg)!r}|rpc={probe_rpc}|{backend}"
    if shape_key:
        # the dispatch shape (ensemble [R] batch, RxS mesh) scales the
        # compile wall independently of the static cfg: a single-device
        # probe wall must never answer for a mesh-shaped run
        blob += f"|{shape_key}"
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _load_cache(path: "str | None") -> dict:
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_cache(path: "str | None", data: dict) -> None:
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except OSError:
        pass  # the cache is an optimization, never a failure


def candidate_ladder(requested: int, floor: int = RPC_FLOOR) -> "list[int]":
    cands = [requested] + [c for c in DEFAULT_CANDIDATES if c < requested]
    if cands[-1] > floor:
        cands.append(floor)
    return cands


def plan_rounds_per_chunk(
    st0,
    model,
    tables,
    cfg,
    *,
    requested: int,
    budget_s: float,
    n_compiles: float = 1.0,
    probe_rpc: int = PROBE_RPC,
    probe_end_ns: int = PROBE_END_NS,
    floor: int = RPC_FLOOR,
    cache_path: "str | None" = None,
    tracker=None,
    probe_runner=None,
    shape_key: str = "",
) -> AutotunePlan:
    """Measure (or recall) the tiny-chunk compile wall and choose the
    largest rounds_per_chunk whose projected compile cost fits
    `budget_s`. `n_compiles` scales the projection by how many engine
    compiles the caller is about to pay times any engine-variance
    headroom.

    The probe runs a real `run_until` of `probe_end_ns` sim-ns at
    `probe_rpc` rounds per chunk on the caller's initial state (the
    state is copied by the driver, never consumed), with the plain
    engine pinned — the cheapest compile that still scales ~linearly
    with the scan length. `st0` may be a zero-arg callable building
    that state lazily: cache hits, the rpc floor, and a zero budget
    all return before the probe, and a lazy state means those paths
    never pay a full-width init_state/bootstrap at all.

    `probe_runner(st, end_ns, rpc, cfg, tracker)` overrides the probe's
    driver so the probe compiles the shape the run will ACTUALLY trace:
    a `--replicas` run passes the vmapped ensemble driver, a `--mesh`
    run the 2-D shard_map driver — a single-device probe under-projects
    both (the batched/collective program costs more to compile), and
    the budget walk would pick a too-large rounds_per_chunk. `shape_key`
    names that dispatch shape in the probe cache key so shapes never
    answer for each other.
    """
    import jax

    backend = jax.default_backend()
    if budget_s <= 0:
        return AutotunePlan(
            rounds_per_chunk=requested, requested=requested, budget_s=budget_s,
            n_compiles=n_compiles, probe_rpc=probe_rpc, probe_wall_s=None,
            projected_compile_s=None, pump_k=None, source="disabled",
            backend=backend,
        )
    if requested <= floor:
        return AutotunePlan(
            rounds_per_chunk=requested, requested=requested, budget_s=budget_s,
            n_compiles=n_compiles, probe_rpc=probe_rpc, probe_wall_s=None,
            projected_compile_s=None, pump_k=None, source="floor",
            backend=backend,
        )

    key = _cache_key(cfg, probe_rpc, backend, shape_key)
    cache = _load_cache(cache_path)
    probe_wall = cache.get(key, {}).get("probe_wall_s")
    peak_hbm = cache.get(key, {}).get("peak_hbm_bytes")
    source = "cache" if probe_wall is not None else "probe"
    if probe_wall is None:
        import contextlib

        from shadow_tpu.engine.round import run_until
        from shadow_tpu.runtime import flightrec

        probe_cfg = dataclasses.replace(cfg, engine="plain", pump_k=0)
        probe_st = st0() if callable(st0) else st0  # build outside the wall
        # the probe's cost is real wall the run pays: record it as a
        # first-class tracker span (`autotune_probe`) so traces and the
        # phase percentiles show it, not just sim-stats' autotune block
        span = (
            tracker.span("autotune_probe", rpc=probe_rpc)
            if tracker is not None
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with span, flightrec.suspended():
            # suspended: the probe drives a THROWAWAY state through the
            # real driver — its per-chunk probes must not pollute the
            # run's metrics stream/ring (the decision event below is the
            # probe's footprint there)
            if probe_runner is not None:
                probe_runner(
                    probe_st, probe_end_ns, probe_rpc, probe_cfg, tracker
                )
            else:
                run_until(
                    probe_st, probe_end_ns, model, tables, probe_cfg,
                    rounds_per_chunk=probe_rpc, tracker=tracker,
                )
        probe_wall = time.perf_counter() - t0
        if probe_runner is None:
            # memory observatory: the plain probe chunk is already
            # compiled in the process jit cache, so AOT-lowering it again
            # is a cheap second tiny compile that gives us the one thing
            # run_until can't: the executable handle whose
            # memory_analysis() projects peak HBM. Best-effort — the
            # autotuner's budget walk never depends on it.
            try:
                import jax.numpy as jnp

                from shadow_tpu.engine.round import _run_chunk
                from shadow_tpu.runtime import memtrack

                exe = (
                    jax.jit(_run_chunk, static_argnums=(2, 3, 5))
                    .lower(
                        probe_st, jnp.asarray(probe_end_ns, jnp.int64),
                        probe_rpc, model, tables, probe_cfg,
                    )
                    .compile()
                )
                mem = memtrack.compiled_memory(exe)
                if mem and mem.get("peak_bytes"):
                    peak_hbm = int(mem["peak_bytes"])
            except Exception:  # noqa: BLE001 — telemetry, never a failure
                peak_hbm = None
        flightrec.record_event(
            "autotune_probe", wall_s=round(probe_wall, 4), rpc=probe_rpc,
            backend=backend, **({"shape": shape_key} if shape_key else {}),
            **({"peak_hbm_bytes": peak_hbm} if peak_hbm else {}),
        )
        cache[key] = {
            "probe_wall_s": round(probe_wall, 4),
            "probe_rpc": probe_rpc,
            "backend": backend,
            "saved_at": int(time.time()),
        }
        if peak_hbm:
            cache[key]["peak_hbm_bytes"] = peak_hbm
        _save_cache(cache_path, cache)

    chosen, projected = requested, None
    for cand in candidate_ladder(requested, floor):
        chosen = cand
        projected = probe_wall * (cand / probe_rpc) * n_compiles
        if projected <= budget_s:
            break
    return AutotunePlan(
        rounds_per_chunk=chosen, requested=requested, budget_s=budget_s,
        n_compiles=n_compiles, probe_rpc=probe_rpc,
        probe_wall_s=round(probe_wall, 4),
        projected_compile_s=round(projected, 4) if projected is not None else None,
        pump_k=None, source=source, backend=backend,
        peak_hbm_bytes=int(peak_hbm) if peak_hbm else None,
    )


def plan_pump_k(
    plan: AutotunePlan, cfg, *, candidates=(16, 8, 4), budget_share: float = 0.25
) -> AutotunePlan:
    """Cap pump_k under the same compile budget: one pump microstep's
    trace is a few hundred ops repeated pump_k times per iteration, so
    the pump compile grows ~linearly in pump_k the same way
    the scan grows in rounds_per_chunk. Project from the measured probe
    wall (plain engine ≈ one microstep-equivalent per iteration) and pick
    the largest candidate whose extra compile cost fits `budget_share`
    of the budget. Returns a plan whose `pump_k` is None (keep) when the
    probe never ran or the caller pinned the engine to plain."""
    if plan.probe_wall_s is None or cfg.engine == "plain":
        return plan
    # the plain probe is ~one microstep-equivalent per iteration, so a
    # pump_k=cand trace projects to cand times the plain full-rpc compile
    per_k = plan.probe_wall_s * (plan.rounds_per_chunk / plan.probe_rpc)
    limit = plan.budget_s * budget_share
    chosen = candidates[-1]
    for cand in candidates:
        chosen = cand
        if per_k * cand <= limit:
            break
    current = cfg.pump_k if cfg.pump_k > 0 else 8
    if chosen >= current:
        return plan  # never raise pump_k above the caller's choice
    return dataclasses.replace(plan, pump_k=chosen)
