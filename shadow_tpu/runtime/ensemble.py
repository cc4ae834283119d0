"""EnsembleRunner: the runtime face of the ensemble plane
(engine/ensemble.py; docs/ensemble.md).

Drop-in for TpuScheduler on scripted-model runs with
`general.replicas > 1` (`--replicas N` / `--replica-seed-stride K`):
same run() surface — start_state / checkpoints / guard / recovery — so
the Manager's fault-tolerant run loop (runtime/checkpoint.py StateTap
two-phase commit, runtime/recovery.py rollback-and-regrow) composes
unchanged. The differences live where the replica axis does:

  * the state is the [R, ...] init_ensemble_state stack and checkpoints
    serialize it whole — the replica count is folded into the config
    fingerprint, so resuming with a different `--replicas` fails with a
    clear CheckpointError, never a shape mismatch;
  * recovery regrows the WHOLE batch via grow_ensemble_state (one
    replica's CapacityError — which names the replica — rolls every
    replica back to the shared retained snapshot and replays on the one
    regrown compiled shape);
  * ensemble_stats folds the final state into sim-stats.json: one
    per-replica section per world plus an aggregate block
    (mean/stddev/min/max and normal-approximation 95% CI across
    replicas) fed from the tracker plane's per-host tensors.

Ensembles run on a single device (replica batching via vmap); sharding
the host axis under an ensemble is future work.
"""

from __future__ import annotations

import math

import numpy as np

from shadow_tpu.engine.ensemble import (
    ensemble_engine_cfg,
    grow_ensemble_state,
    init_ensemble_state,
    num_replicas,
    replica_seeds,
    run_ensemble_until,
)
from shadow_tpu.engine.round import host_stats
from shadow_tpu.engine.state import EngineConfig


class EnsembleRunner:
    name = "tpu-ensemble"

    def __init__(
        self,
        model,
        tables,
        cfg: EngineConfig,
        num_replicas: int,
        seed_stride: int = 1,
        rounds_per_chunk: int = 256,
        tx_bytes_per_interval=None,
        rx_bytes_per_interval=None,
        compile_cache=None,
        cache_key=None,
        on_rows=None,
        watchdog_s: float = 0.0,
    ):
        if num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        # the done-mask is armed once here so initial_state, the chunk
        # jit cache key, and every recovery recompile agree on the cfg
        self.cfg = ensemble_engine_cfg(cfg)
        self.model = model
        self.tables = tables
        self.num_replicas = num_replicas
        self.seed_stride = seed_stride
        self.rounds_per_chunk = rounds_per_chunk
        self.tx_bytes_per_interval = tx_bytes_per_interval
        self.rx_bytes_per_interval = rx_bytes_per_interval
        # Sweep-scheduler seams (runtime/sweep.py): an AOT compile cache
        # (runtime/compile_cache.py) keyed under `cache_key` (the config
        # fingerprint modulo seed) so same-shape batches share one
        # executable, and a per-replica probe-row stream for sync-free
        # per-job progress.
        self.compile_cache = compile_cache
        self.cache_key = cache_key
        self.on_rows = on_rows
        self.watchdog_s = watchdog_s

    @property
    def seeds(self) -> "list[int]":
        return replica_seeds(self.cfg, self.num_replicas, self.seed_stride)

    def initial_state(self, cfg: "EngineConfig | None" = None):
        """The bootstrapped [R, ...] t=0 stack — also the template a
        resume loads a checkpoint into (same config -> same shapes)."""
        cfg = cfg or self.cfg
        return init_ensemble_state(
            cfg,
            self.model,
            self.num_replicas,
            self.seed_stride,
            tx_bytes_per_interval=self.tx_bytes_per_interval,
            rx_bytes_per_interval=self.rx_bytes_per_interval,
        )

    def _launch_for(self, st, end_time_ns: int, cfg):
        """The compile-cache lookup: an AOT-compiled chunk executable for
        this (fingerprint-modulo-seed key, state shapes, static cfg), or
        None to use the process-wide jit cache. Recovery regrows change
        the state shapes, so a regrown replay keys (and compiles) its own
        entry instead of aliasing the old executable."""
        if self.compile_cache is None:
            return None
        from shadow_tpu.engine.ensemble import lower_ensemble_chunk
        from shadow_tpu.engine.round import effective_engine
        from shadow_tpu.engine.state import trace_static_cfg
        from shadow_tpu.runtime import chaos

        static_cfg = trace_static_cfg(ensemble_engine_cfg(cfg))
        eng = effective_engine(static_cfg)
        # the AOT twin of _drive's chunk-0 wrap: a compile/trace failure
        # here must reach the same fallback ladder
        with chaos.compile_seam(eng):
            return self.compile_cache.get(
                (self.cache_key, self.rounds_per_chunk),
                st,
                static_cfg,
                lambda: lower_ensemble_chunk(
                    st, end_time_ns, self.rounds_per_chunk, self.model,
                    self.tables, cfg,
                ).compile(),
            )

    def _runner_factory(self, end_time_ns: int, on_chunk, max_chunks, tracker):
        def factory(cfg):
            def run(st, on_state=None):
                return run_ensemble_until(
                    st, end_time_ns, self.model, self.tables, cfg,
                    rounds_per_chunk=self.rounds_per_chunk,
                    max_chunks=max_chunks, on_chunk=on_chunk,
                    tracker=tracker, on_state=on_state,
                    on_rows=self.on_rows,
                    launch=self._launch_for(st, end_time_ns, cfg),
                    watchdog_s=self.watchdog_s,
                )

            return run

        return factory

    def run(self, end_time_ns: int, on_chunk=None, max_chunks: int = 100_000,
            tracker=None, start_state=None, checkpoints=None, guard=None,
            recovery=None):
        """Run the whole batch to end_time_ns (the driver stops when the
        SLOWEST replica quiesces; finished replicas idle as identity
        no-ops). Mirrors TpuScheduler.run — including the engine
        fallback ladder (already at pump under vmap, so the only rung
        left is pump → plain; bit-identical either way), walked only
        when a `recovery` policy is given: `recovery=None` is fail-fast,
        the first EngineCompileError propagates — with the regrow step
        vmapped over the replica axis."""
        from shadow_tpu.runtime.chaos import run_with_engine_ladder
        from shadow_tpu.runtime.recovery import (
            RecoveryPolicy,
            run_until_recovering,
        )

        st = start_state if start_state is not None else self.initial_state()
        self.recovery_report = []
        factory = self._runner_factory(end_time_ns, on_chunk, max_chunks, tracker)

        def attempt(cfg):
            if recovery is None and checkpoints is None and guard is None:
                return factory(cfg)(st), []
            return run_until_recovering(
                st,
                end_time_ns,
                cfg=cfg,
                tracker=tracker,
                policy=recovery or RecoveryPolicy(max_recoveries=0),
                checkpoints=checkpoints,
                guard=guard,
                runner_factory=factory,
                grow_fn=grow_ensemble_state,
            )

        self.engine_fallbacks: "list[dict]" = []
        try:
            (final, report), _ = run_with_engine_ladder(
                self.cfg, attempt,
                on_fallback=self.engine_fallbacks.append,
                fail_fast=recovery is None,
            )
        except Exception as err:
            # keep the partial degradation record on failure: recoveries
            # ride the terminal exception (runtime/recovery.py), fallbacks
            # accumulated live via on_fallback above
            self.recovery_report = list(getattr(err, "recoveries", []))
            raise
        self.recovery_report = report
        return final


def _agg(values) -> dict:
    """mean/stddev/min/max and a normal-approximation 95% CI over one
    per-replica metric (sample stddev; CI half-width 1.96 * sd / sqrt(R),
    degenerate to the point value at R=1)."""
    a = np.asarray(values, dtype=np.float64)
    mean = float(a.mean())
    sd = float(a.std(ddof=1)) if a.size > 1 else 0.0
    half = 1.96 * sd / math.sqrt(a.size) if a.size > 1 else 0.0
    return {
        "mean": round(mean, 4),
        "stddev": round(sd, 4),
        "min": float(a.min()),
        "max": float(a.max()),
        "ci95": [round(mean - half, 4), round(mean + half, 4)],
    }


def ensemble_stats(
    final,
    seeds: "list[int]",
    wall_seconds: float,
    sim_seconds: float,
    seed_stride: int = 1,
    host_tensors: "dict | None" = None,
) -> dict:
    """The `ensemble` section of sim-stats.json: one per-replica block
    per world (events/packets/drops/bytes/rounds, summed over that
    replica's hosts from the tracker plane's bulk host_stats fetch) plus
    the aggregate statistics across replicas — mean/stddev/min/max/95% CI
    of events, packets, bytes, and events-per-wall-second, and the
    amortization scalars (wall per replica, sim-sec per wall-sec per
    replica) the ensemble exists to improve."""
    hs = host_tensors if host_tensors is not None else host_stats(final)
    r = num_replicas(final)
    if len(seeds) != r:
        raise ValueError(f"{len(seeds)} seeds for {r} replicas")
    wall_per_replica = wall_seconds / r if r else float("nan")
    per = []
    for i in range(r):
        per.append(
            {
                "replica": i,
                "seed": int(seeds[i]),
                "events_handled": int(np.sum(hs["events_handled"][i])),
                "packets_sent": int(np.sum(hs["packets_sent"][i])),
                "packets_dropped": int(np.sum(hs["packets_dropped"][i])),
                "packets_unroutable": int(np.sum(hs["packets_unroutable"][i])),
                "bytes_sent": int(np.sum(hs["bytes_sent"][i])),
                "bytes_ctrl": int(np.sum(hs["bytes_ctrl"][i])),
                "bytes_data": int(np.sum(hs["bytes_data"][i])),
                "rounds_live": int(hs["rounds_live"][i]),
                "rounds_idle": int(hs["rounds_idle"][i]),
            }
        )
    events = [p["events_handled"] for p in per]
    return {
        "replicas": r,
        "seed_stride": int(seed_stride),
        "wall_seconds": round(wall_seconds, 4),
        "wall_seconds_per_replica": round(wall_per_replica, 4),
        "sim_sec_per_wall_sec_per_replica": round(
            sim_seconds / wall_per_replica, 4
        )
        if wall_per_replica > 0
        else None,
        "per_replica": per,
        "aggregate": {
            "events_handled": _agg(events),
            "packets_sent": _agg([p["packets_sent"] for p in per]),
            "bytes_sent": _agg([p["bytes_sent"] for p in per]),
            "bytes_data": _agg([p["bytes_data"] for p in per]),
            "events_per_wall_second": _agg(
                [e / wall_seconds for e in events]
            )
            if wall_seconds > 0
            else None,
        },
    }


def flatten_host_stats(hs: dict) -> dict:
    """Collapse the [R, H] per-host tensors of an ensemble host_stats
    fetch into the flat shape the host-side tracker fold expects
    (utils/tracker.py sums/maxes over one axis): per-host arrays flatten
    to [R*H]; the per-replica round scalars reduce to their max (exact
    per-replica rounds live in the `ensemble` stats block instead). The
    window-width pair is the exception: mean_ns = win_ns_sum /
    rounds_live must take BOTH from the same population, so the fold
    gets the across-replica totals (win_rounds_live carries the summed
    denominator; maxing each independently would divide numbers from
    different replicas and report a mean no replica actually had)."""
    out = {}
    for k, v in hs.items():
        a = np.asarray(v)
        if k == "win_ns_sum":
            out[k] = int(a.sum())
        elif k in ("rounds_live", "rounds_idle"):
            out[k] = int(a.max())
        else:
            out[k] = a.reshape(-1)
    out["win_rounds_live"] = int(np.asarray(hs["rounds_live"]).sum())
    return out
