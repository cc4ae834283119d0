"""The Scheduler seam: which engine steps the simulation.

Mirrors the reference's `Scheduler` facade over interchangeable parallel
engines (reference: src/main/core/scheduler/mod.rs:19-151, with
ThreadPerCore/ThreadPerHost variants). Here the variants are:

  * TpuScheduler — the jitted device engine; single device, or hosts
    block-sharded over all visible devices via ShardedRunner.
  * CpuRefScheduler — the pure-Python conformance oracle (slow; exists so
    device results can be diffed against independently-written semantics,
    like the reference's determinism double-runs).
"""

from __future__ import annotations

import jax
import numpy as np

from shadow_tpu.cpu_ref import CpuRefPhold
from shadow_tpu.engine import EngineConfig
from shadow_tpu.engine.round import (
    bootstrap,
    effective_engine,
    model_pump_capable,
    run_until,
)
from shadow_tpu.engine.sharded import AXIS, ShardedRunner
from shadow_tpu.engine.state import init_state
from shadow_tpu.graph.routing import RoutingTables
from shadow_tpu.models.phold import PholdModel


class TpuScheduler:
    name = "tpu"

    def __init__(self, model, tables: RoutingTables, cfg: EngineConfig, *, parallelism: int = 0, rounds_per_chunk: int = 256,
                 tx_bytes_per_interval=None, rx_bytes_per_interval=None,
                 watchdog_s: float = 0.0):
        self.model = model
        self.tables = tables
        self.cfg = cfg
        self.rounds_per_chunk = rounds_per_chunk
        self.tx_bytes_per_interval = tx_bytes_per_interval
        self.rx_bytes_per_interval = rx_bytes_per_interval
        self.watchdog_s = watchdog_s
        devices = jax.devices()
        n = parallelism if parallelism > 0 else len(devices)
        n = min(n, len(devices))
        # shard only when it divides evenly; otherwise fall back to 1 device
        while n > 1 and cfg.num_hosts % n != 0:
            n -= 1
        self.num_devices = n
        # the engine run_round actually executes for THIS model ("auto"
        # is pump when pump_k > 0, else plain — engine/round.py
        # effective_engine), mirroring run_round's own substitution so
        # the start log never advertises a faster engine than runs:
        # models the pump can't honor take the plain handler
        self.engine = effective_engine(cfg)
        if not model_pump_capable(model):
            self.engine = "plain"
        # how the handler finds a host's node in THIS world, a fact of
        # set-up like the engine: "runs" (compares against the host groups'
        # bounds, that many) or "gather" (graph/routing.py node_of)
        self.route_path, self.route_runs = tables.route_path, tables.route_runs
        if n > 1:
            from jax.sharding import Mesh

            mesh = Mesh(np.array(devices[:n]), (AXIS,))
            self._runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk)
        else:
            self._runner = None

    def initial_state(self, cfg: "EngineConfig | None" = None):
        """The bootstrapped t=0 state — also the template resume loads a
        checkpoint into (same config → same shapes/dtypes)."""
        cfg = cfg or self.cfg
        return bootstrap(
            init_state(
                cfg,
                self.model.init(),
                tx_bytes_per_interval=self.tx_bytes_per_interval,
                rx_bytes_per_interval=self.rx_bytes_per_interval,
            ),
            self.model,
            cfg,
        )

    def _runner_factory(self, end_time_ns: int, on_chunk, max_chunks, tracker):
        """run(st, on_state=...) builders per engine config — the seam
        rollback-and-regrow recompiles through (a regrown capacity is a
        new static shape). The original config reuses the already-built
        sharded runner; grown configs get a fresh one."""

        def factory(cfg):
            if self.num_devices > 1:
                runner = (
                    self._runner
                    if cfg == self.cfg
                    else ShardedRunner(
                        self._runner.mesh, self.model, self.tables, cfg,
                        self.rounds_per_chunk,
                    )
                )

                def run(st, on_state=None):
                    return runner.run_until(
                        st, end_time_ns, max_chunks=max_chunks,
                        on_chunk=on_chunk, tracker=tracker, on_state=on_state,
                        watchdog_s=self.watchdog_s,
                    )

            else:

                def run(st, on_state=None):
                    return run_until(
                        st, end_time_ns, self.model, self.tables, cfg,
                        rounds_per_chunk=self.rounds_per_chunk,
                        max_chunks=max_chunks, on_chunk=on_chunk,
                        tracker=tracker, on_state=on_state,
                        watchdog_s=self.watchdog_s,
                    )

            return run

        return factory

    def run(self, end_time_ns: int, on_chunk=None, max_chunks: int = 100_000,
            tracker=None, start_state=None, checkpoints=None, guard=None,
            recovery=None):
        """Run to end_time_ns. `start_state` (a restored checkpoint)
        replaces the bootstrapped t=0 state; `checkpoints` /`guard` tap
        chunk-boundary states (runtime/checkpoint.py); `recovery` (a
        RecoveryPolicy) turns CapacityError into rollback-and-regrow, and
        with one a compile/trace failure of the selected engine walks the
        fallback ladder (pump → plain, bit-identical
        results) instead of failing the run. `recovery=None` — what
        --no-recover and every programmatic caller that passes no policy
        get — is fail-fast for both: the first
        CapacityError or EngineCompileError propagates and no rung is
        walked. The fallback records of the last run are left on
        self.engine_fallbacks and the recovery report on
        self.recovery_report."""
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
                # the plain path: no taps, no recovery wrapper
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
            )

        self.engine_fallbacks: "list[dict]" = []
        try:
            (final, report), _ = run_with_engine_ladder(
                self.cfg, attempt,
                on_fallback=self.engine_fallbacks.append,
                fail_fast=recovery is None,
            )
        except Exception as err:
            # keep the partial degradation record on failure (mirrors
            # EnsembleRunner.run): recoveries ride the terminal exception
            self.recovery_report = list(getattr(err, "recoveries", []))
            raise
        self.recovery_report = report
        return final


class CpuRefScheduler:
    name = "cpu-ref"

    def __init__(self, model, tables: RoutingTables, cfg: EngineConfig, host_node,
                 tx_bytes_per_interval=None, rx_bytes_per_interval=None, **_):
        from shadow_tpu.cpu_ref.bulk_ref import CpuRefBulk
        from shadow_tpu.cpu_ref.tgen_ref import CpuRefTgen
        from shadow_tpu.models.bulk import BulkTcpModel
        from shadow_tpu.models.tgen import TgenModel

        if isinstance(model, PholdModel):
            ref_cls = CpuRefPhold
        elif isinstance(model, BulkTcpModel):
            ref_cls = CpuRefBulk
        elif isinstance(model, TgenModel):
            ref_cls = CpuRefTgen
        else:
            raise ValueError(
                "cpu-ref scheduler supports the phold, bulk-tcp, and tgen models"
            )
        self.ref = ref_cls(cfg, model, tables, host_node,
                           tx_bytes_per_interval=tx_bytes_per_interval,
                           rx_bytes_per_interval=rx_bytes_per_interval)

    def run(self, end_time_ns: int, on_chunk=None, max_chunks: int = 100_000,
            tracker=None):
        # the oracle has no device dispatch pipeline: tracker spans and
        # device counters do not apply here
        self.ref.bootstrap()
        self.ref.run_until(end_time_ns)
        return self.ref


def make_scheduler(name: str, model, tables, cfg, host_node, parallelism=0, rounds_per_chunk=256,
                   tx_bytes_per_interval=None, rx_bytes_per_interval=None,
                   watchdog_s=0.0):
    if name == "tpu":
        return TpuScheduler(model, tables, cfg, parallelism=parallelism, rounds_per_chunk=rounds_per_chunk,
                            tx_bytes_per_interval=tx_bytes_per_interval,
                            rx_bytes_per_interval=rx_bytes_per_interval,
                            watchdog_s=watchdog_s)
    if name == "cpu-ref":
        return CpuRefScheduler(model, tables, cfg, host_node,
                               tx_bytes_per_interval=tx_bytes_per_interval,
                               rx_bytes_per_interval=rx_bytes_per_interval)
    raise ValueError(f"unknown scheduler {name!r}")
