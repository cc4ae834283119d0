"""Manager/Controller: build the simulated world from config and run it.

The reference splits this between Controller (owns end time / windows,
reference src/main/core/controller.rs:39-111) and Manager (builds hosts,
picks the scheduler, runs the round loop, reference manager.rs:227-549).
Window logic lives on-device here (engine/round.py), so this Manager's jobs
are: resolve the graph, expand host specs (quantity), assign IPs, map hosts
to graph nodes, build the model, run the chosen scheduler with heartbeats,
and write `sim-stats.json` + the processed config into the data directory
(reference manager.rs:187-198 re-serializes config the same way).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

import contextlib

from shadow_tpu.config import ConfigOptions
from shadow_tpu.engine import EngineConfig
from shadow_tpu.engine.round import RunInterrupted
from shadow_tpu.graph import IpAssignment, NetworkGraph, compute_routing
from shadow_tpu.graph.network_graph import ONE_GBIT_SWITCH_GML
from shadow_tpu.models.registry import build_model
from shadow_tpu.runtime.scheduler import CpuRefScheduler, make_scheduler
from shadow_tpu.simtime import NS_PER_SEC, fmt_time_ns
from shadow_tpu.utils.shadow_log import slog


# per-host counters both the device state and the scalar oracle keep;
# published as sim-stats.json's `per_host` block
PER_HOST_COUNTERS = ("events_handled", "packets_sent", "packets_dropped")


@dataclasses.dataclass
class HostInstance:
    """One expanded simulated host (reference: HostInfo, sim_config.rs:96)."""

    index: int
    name: str
    node_index: int
    ip: int
    model_name: str
    # resolved access-link bandwidth: per-host config override, else the
    # graph node's host_bandwidth_up/down, else -1 = unshaped
    # (reference: sim_config.rs Bandwidth resolution)
    bw_up_bits: int = -1
    bw_down_bits: int = -1
    cpu_freq_hz: int = 0  # 0 = native speed (no CPU delay scaling)
    spec: object = None  # the HostOptions this instance was expanded from


@dataclasses.dataclass
class ScriptedWorld:
    """Everything a scripted-model run needs besides a scheduler: the
    built model, routing tables, resolved EngineConfig, and the shaping
    refill vectors. Extracted from Manager.run so other drivers — the
    sweep scheduler service (runtime/sweep.py) foremost — build the
    exact world the CLI would, through the exact validation."""

    model: object
    tables: object
    ecfg: EngineConfig
    tx_refill: "object | None"
    rx_refill: "object | None"
    host_node: "list[int]"
    runahead_ns: int


@dataclasses.dataclass
class SimResults:
    hosts: "list[HostInstance]"
    events_handled: int
    packets_sent: int
    packets_dropped: int
    packets_unroutable: int
    wall_seconds: float
    sim_seconds: float
    scheduler: str
    # managed-process runs only: processes whose final state did not match
    # their expected_final_state (reference worker.rs:485-487)
    unexpected_final_states: "list[str]" = dataclasses.field(default_factory=list)
    extra_stats: dict = dataclasses.field(default_factory=dict)

    @property
    def sim_sec_per_wall_sec(self) -> float:
        return self.sim_seconds / self.wall_seconds if self.wall_seconds > 0 else float("inf")


class Manager:
    def __init__(self, config: ConfigOptions):
        self.config = config
        self.graph = self._load_graph()
        self.hosts = self._expand_hosts()
        self.managed_mode = self._validate_process_specs()
        self.mesh_plan = self._resolve_mesh()
        if config.general.replicas > 1 and self.mesh_plan is None:
            # ensemble plane (docs/ensemble.md): scripted models on the
            # device engine only — managed guests are live OS processes
            # and cannot be replicated on device, and the oracle/serial
            # schedulers have no replica axis
            if self.managed_mode:
                raise ValueError(
                    "general.replicas > 1 supports scripted-model runs "
                    "only; managed guests are live OS processes and cannot "
                    "be replicated on device (docs/ensemble.md)"
                )
            if config.experimental.scheduler != "tpu":
                raise ValueError(
                    "general.replicas > 1 requires experimental.scheduler: "
                    "tpu (the ensemble plane vmaps the device engine)"
                )
            if config.general.parallelism > 1:
                raise ValueError(
                    "general.replicas > 1 runs on a single device (the "
                    "replica axis is vmapped); it does not compose with "
                    "general.parallelism > 1 host sharding yet — drop one "
                    "of the two (docs/ensemble.md)"
                )
        self.ip = IpAssignment()
        for h in self.hosts:
            if h.ip >= 0:
                self.ip.assign_explicit(h.index, h.ip)
        for h in self.hosts:
            if h.ip < 0:
                h.ip = self.ip.assign_auto(h.index)

    def _resolve_mesh(self):
        """Validate general.mesh at construction (construction = world
        validation) and return the resolved MeshPlan, or None. The 2-D
        mesh plane (docs/parallelism.md "2-D mesh") composes the
        replica and host-shard axes: the run's replica count is
        general.replicas when > 1 (each of the R mesh rows vmaps
        replicas/R locally), else the grid's R."""
        g = self.config.general
        if not g.mesh:
            return None
        from shadow_tpu.config.options import parse_mesh
        from shadow_tpu.engine.mesh import MeshPlan

        rows, shards = parse_mesh(g.mesh)
        if self.managed_mode:
            raise ValueError(
                "general.mesh supports scripted-model runs only; managed "
                "guests are live OS processes and cannot be laid out on a "
                "device mesh (docs/parallelism.md)"
            )
        if self.config.experimental.scheduler != "tpu":
            raise ValueError(
                "general.mesh requires experimental.scheduler: tpu (the "
                "mesh plane dispatches the device engine)"
            )
        if g.parallelism > 1:
            raise ValueError(
                "general.mesh IS the sharding plane — drop "
                "general.parallelism > 1 (the mesh's S axis replaces it)"
            )
        replicas = g.replicas if g.replicas > 1 else rows
        if replicas % rows:
            raise ValueError(
                f"general.replicas={replicas} must be a multiple of the "
                f"mesh's replica rows ({g.mesh}): each row carries "
                "replicas/R vmapped replicas"
            )
        if len(self.hosts) % shards:
            raise ValueError(
                f"{len(self.hosts)} hosts must divide evenly over the "
                f"mesh's {shards} host-shard(s) ({g.mesh})"
            )
        plan = MeshPlan(replicas=replicas, shards=shards, rows=rows)
        import jax

        if plan.devices_needed > len(jax.devices()):
            # fail at construction like every other world error — left
            # to dispatch time this first surfaces as a misleading
            # "autotune probe failed" warning before the run dies
            raise ValueError(
                f"general.mesh {g.mesh} needs {plan.devices_needed} "
                f"devices, {len(jax.devices())} visible"
            )
        return plan

    def _validate_process_specs(self) -> bool:
        """Classify the run as scripted-model or managed-executable mode and
        validate the specs up front (construction = world validation)."""
        import pathlib

        from shadow_tpu.models.registry import _REGISTRY

        kinds = {p.path in _REGISTRY for h in self.hosts for p in h.spec.processes}
        if kinds == {True, False}:
            raise ValueError(
                "config mixes scripted models and executable paths across hosts; "
                "run them in separate simulations"
            )
        if kinds != {False}:
            for h in self.hosts:
                if len(h.spec.processes) != 1:
                    raise ValueError(
                        f"hosts.{h.name}: scripted-model hosts take exactly one process"
                    )
                if not isinstance(h.spec.processes[0].args, dict):
                    raise ValueError(
                        f"hosts.{h.name}: scripted model {h.model_name!r} takes args "
                        f"as a mapping, not a string or list"
                    )
            return False
        for h in self.hosts:
            for p in h.spec.processes:
                exe = pathlib.Path(p.path)
                if not (exe.is_file() and os.access(exe, os.X_OK)):
                    from shadow_tpu.models.registry import unknown_model_error

                    if os.sep not in p.path:
                        # a bare word is a (mistyped) model name, not a
                        # path: say what IS registered, with a hint
                        raise ValueError(
                            f"hosts.{h.name}: {unknown_model_error(p.path)}"
                        )
                    raise ValueError(
                        f"hosts.{h.name}: process path {p.path!r} is neither a "
                        f"registered model nor an executable file"
                    )
                if not isinstance(p.args, list) and p.args != {}:
                    raise ValueError(
                        f"hosts.{h.name}: executable processes take args as a string "
                        f"or list, not a mapping"
                    )
        return True

    def _load_graph(self) -> NetworkGraph:
        g = self.config.network.graph
        if g.kind == "1_gbit_switch":
            return NetworkGraph.from_gml(ONE_GBIT_SWITCH_GML)
        if g.inline is not None:
            return NetworkGraph.from_gml(g.inline)
        return NetworkGraph.from_file(g.path)  # handles .gz/.xz/.bz2 too

    def _expand_hosts(self) -> "list[HostInstance]":
        import ipaddress

        out = []
        for spec in self.config.hosts:
            if spec.network_node_id not in self.graph.id_to_index:
                raise ValueError(
                    f"hosts.{spec.name}: network_node_id {spec.network_node_id} not in graph"
                )
            if not spec.processes:
                raise ValueError(f"hosts.{spec.name}: at least one process is required")
            for i in range(spec.quantity):
                name = spec.name if spec.quantity == 1 else f"{spec.name}{i + 1}"
                ip = -1
                if spec.ip_addr is not None:
                    if spec.quantity != 1:
                        raise ValueError(f"hosts.{spec.name}: ip_addr with quantity > 1")
                    ip = int(ipaddress.IPv4Address(spec.ip_addr))
                node_index = self.graph.id_to_index[spec.network_node_id]
                bw_up = spec.bandwidth_up_bits
                if bw_up is None:
                    bw_up = int(self.graph.bw_up_bits[node_index])
                bw_down = spec.bandwidth_down_bits
                if bw_down is None:
                    bw_down = int(self.graph.bw_down_bits[node_index])
                out.append(
                    HostInstance(
                        index=len(out),
                        name=name,
                        node_index=node_index,
                        ip=ip,
                        model_name=spec.processes[0].path,
                        bw_up_bits=bw_up,
                        bw_down_bits=bw_down,
                        cpu_freq_hz=spec.cpu_frequency_hz or 0,
                        spec=spec,
                    )
                )
        return out

    def _resolve_runahead(self, tables) -> int:
        """The conservative round window: the configured value, else the
        minimum link/path latency (reference runahead.rs:43-56). One
        definition for the scripted and managed paths — the hybrid/serial
        clamp grid must match the engine's window exactly."""
        ra = self.config.experimental.runahead_ns
        if ra is None:
            ra = min(self.graph.min_latency_ns(), tables.min_path_latency_ns())
        return ra

    def build_world(self) -> ScriptedWorld:
        """Build the scripted-model world: validate the model specs,
        compute routing, resolve the runahead window and shaping
        refills, and assemble the EngineConfig. The seam the sweep
        scheduler (runtime/sweep.py) drives batches through."""
        cfgo = self.config
        num_hosts = len(self.hosts)
        if self.managed_mode:
            raise ValueError(
                "build_world() is for scripted-model runs; managed "
                "executables go through Manager.run()"
            )

        model_names = {h.model_name for h in self.hosts}
        if len(model_names) != 1:
            raise ValueError(
                f"all hosts must run the same model currently, got {sorted(model_names)}"
            )
        arg_sets = {json.dumps(spec.processes[0].args, sort_keys=True) for spec in cfgo.hosts}
        if len(arg_sets) != 1:
            raise ValueError(
                "all hosts must run the model with identical args currently, got "
                f"{sorted(arg_sets)}"
            )
        model = build_model(model_names.pop(), num_hosts, cfgo.hosts[0].processes[0].args)

        host_node = [h.node_index for h in self.hosts]
        tables = compute_routing(self.graph, use_shortest_path=cfgo.network.use_shortest_path)
        tables = tables.with_hosts(host_node)

        runahead = self._resolve_runahead(tables)

        # Any host with a resolved bandwidth turns the relays/AQM on; hosts
        # without one stay unshaped (refill 0).
        from shadow_tpu.netstack import bw_bits_per_sec_to_refill

        bw_up = np.array([max(h.bw_up_bits, 0) for h in self.hosts], dtype=np.int64)
        bw_down = np.array([max(h.bw_down_bits, 0) for h in self.hosts], dtype=np.int64)
        use_netstack = bool((bw_up > 0).any() or (bw_down > 0).any())
        tx_refill = np.asarray(bw_bits_per_sec_to_refill(bw_up)) if use_netstack else None
        rx_refill = np.asarray(bw_bits_per_sec_to_refill(bw_down)) if use_netstack else None

        ecfg = EngineConfig(
            num_hosts=num_hosts,
            queue_capacity=cfgo.experimental.queue_capacity,
            outbox_capacity=cfgo.experimental.outbox_capacity,
            runahead_ns=runahead,
            seed=cfgo.general.seed,
            max_iters_per_round=cfgo.experimental.max_iters_per_round,
            use_netstack=use_netstack,
            bootstrap_end_ns=cfgo.general.bootstrap_end_time_ns,
            use_dynamic_runahead=cfgo.experimental.use_dynamic_runahead,
            adaptive_window=cfgo.experimental.adaptive_window,
            active_lanes=cfgo.experimental.active_lanes,
            engine=cfgo.experimental.engine,
            pump_k=cfgo.experimental.pump_k,
            tracker=cfgo.general.tracker,
        )
        return ScriptedWorld(
            model=model,
            tables=tables,
            ecfg=ecfg,
            tx_refill=tx_refill,
            rx_refill=rx_refill,
            host_node=host_node,
            runahead_ns=runahead,
        )

    def run(self) -> SimResults:
        """Run the simulation, with the chaos plane installed when the
        config's `chaos:` section declares faults (docs/robustness.md
        "Chaos testing"). The plan is process-global for the duration of
        the run — every seam (drivers, checkpoint writer, hybrid
        supervision) consults it through runtime/chaos.py fire()."""
        from shadow_tpu.runtime import chaos, flightrec

        try:
            plan = chaos.plan_from_config(self.config.chaos)
            if plan is None:
                return self._run()
            with chaos.installed(plan):
                return self._run()
        finally:
            # belt-and-braces: the drivers' finally uninstalls the flight
            # recorder, but an exception between its install and the run
            # (a world-construction error) must never leak a recorder
            # into the next run of this process
            flightrec.uninstall()

    def _fold_chaos(self, results: SimResults) -> None:
        """Publish what the installed fault plan actually injected: a
        chaos run must be visibly a chaos run in sim-stats.json."""
        from shadow_tpu.runtime import chaos

        plan = chaos.active()
        if plan is not None:
            results.extra_stats["chaos"] = plan.report()

    def _run(self) -> SimResults:
        cfgo = self.config
        num_hosts = len(self.hosts)

        if self.managed_mode:
            return self._run_managed()

        world = self.build_world()
        model, tables = world.model, world.tables
        host_node, runahead = world.host_node, world.runahead_ns
        tx_refill, rx_refill = world.tx_refill, world.rx_refill
        ecfg, ckpt, guard, resume_path = self._setup_checkpointing(world.ecfg)

        from shadow_tpu.runtime import flightrec
        from shadow_tpu.utils.progress import ProgressLine

        # progress/tracker are built BEFORE the autotuner so its compile
        # probe records an `autotune_probe` span like any other phase
        progress = ProgressLine(cfgo.general.progress)
        tracker = self._build_tracker(progress)
        # the flight recorder (runtime/flightrec.py) is always on for
        # scripted runs: the bounded ring costs nothing per chunk (it
        # reads the already-fetched probe through the _drive seam), and
        # the black-box dump must exist on EVERY failure path, not only
        # when --metrics-file was passed
        recorder = self._build_recorder(tracker)
        flightrec.install(recorder)

        rounds_per_chunk = cfgo.experimental.rounds_per_chunk
        autotune_plan = None
        if (
            cfgo.experimental.autotune
            and cfgo.experimental.scheduler != "tpu"
        ):
            # never silently drop the flag: the user asked for compile-
            # budget protection the other schedulers don't dispatch through
            slog(
                "warning", 0, "autotune",
                f"experimental.autotune only applies to the tpu scheduler "
                f"(scheduler={cfgo.experimental.scheduler}); ignoring",
            )
        elif cfgo.experimental.autotune:
            # Compile-budget autotuner (runtime/autotune.py): a tiny-chunk
            # probe projects the full compile wall and walks
            # rounds_per_chunk down to fit the budget BEFORE the main
            # compile. Trajectory-neutral (chunking only groups rounds),
            # so resume/checkpoints are unaffected; probe walls persist
            # in the data directory keyed by the canonicalized config.
            # The probe runs at the shape the run will actually trace —
            # the [R, ...] ensemble batch or the RxS mesh layout, not a
            # single-device stand-in whose wall projection under-
            # estimates the batched/collective compile and lets the
            # budget walk pick a too-large rounds_per_chunk.
            import os as _os

            from shadow_tpu.engine.state import init_state as _init_state
            from shadow_tpu.runtime.autotune import plan_rounds_per_chunk

            from shadow_tpu.engine.round import bootstrap as _bootstrap

            probe_runner = None
            probe_shape_key = ""
            if self.mesh_plan is not None:
                from shadow_tpu.engine.mesh import (
                    init_mesh_state,
                    run_mesh_until,
                )

                plan_ = self.mesh_plan
                probe_shape_key = (
                    f"mesh{plan_.rows}x{plan_.shards}r{plan_.replicas}"
                )

                def _probe_state():
                    return init_mesh_state(
                        ecfg, model, plan_,
                        cfgo.general.replica_seed_stride,
                        tx_bytes_per_interval=tx_refill,
                        rx_bytes_per_interval=rx_refill,
                    )

                def probe_runner(st, end_ns, rpc, pcfg, ptracker):
                    run_mesh_until(
                        st, end_ns, model, tables, pcfg, plan_,
                        rounds_per_chunk=rpc, tracker=ptracker,
                    )

            elif cfgo.general.replicas > 1:
                from shadow_tpu.engine.ensemble import (
                    init_ensemble_state,
                    run_ensemble_until,
                )

                reps = cfgo.general.replicas
                probe_shape_key = f"r{reps}"

                def _probe_state():
                    return init_ensemble_state(
                        ecfg, model, reps,
                        cfgo.general.replica_seed_stride,
                        tx_bytes_per_interval=tx_refill,
                        rx_bytes_per_interval=rx_refill,
                    )

                def probe_runner(st, end_ns, rpc, pcfg, ptracker):
                    run_ensemble_until(
                        st, end_ns, model, tables, pcfg,
                        rounds_per_chunk=rpc, tracker=ptracker,
                    )

            else:

                def _probe_state():
                    # built lazily: a warm probe cache (or the rpc floor
                    # / zero budget) answers without ever paying this
                    # full-width init + bootstrap
                    return _bootstrap(
                        _init_state(
                            ecfg, model.init(),
                            tx_bytes_per_interval=tx_refill,
                            rx_bytes_per_interval=rx_refill,
                        ),
                        model, ecfg,
                    )

            cache_path = None
            if cfgo.general.data_directory:
                cache_path = _os.path.join(
                    cfgo.general.data_directory, "autotune.json"
                )
            try:
                autotune_plan = plan_rounds_per_chunk(
                    _probe_state, model, tables, ecfg,
                    requested=rounds_per_chunk,
                    budget_s=cfgo.experimental.autotune_budget_s,
                    cache_path=cache_path,
                    tracker=tracker,
                    probe_runner=probe_runner,
                    shape_key=probe_shape_key,
                )
            except Exception as e:  # noqa: BLE001 — the autotuner is an
                # optimization, never a failure: a probe crash (including
                # a chaos fault landing on the probe's chunk-0 dispatch,
                # which runs inside the installed plan but outside the
                # fallback/recovery ladders) degrades to the requested
                # chunking; the main run still hits any REAL error through
                # the proper recovery seams
                slog(
                    "warning", 0, "autotune",
                    f"compile probe failed ({type(e).__name__}: {e}); "
                    f"keeping rounds_per_chunk={rounds_per_chunk}",
                )
                autotune_plan = None
            if autotune_plan is not None:
                rounds_per_chunk = autotune_plan.rounds_per_chunk
                if tracker is not None:
                    # the probe's measured wall + the chosen chunking in
                    # the tracker fold, not just sim-stats (the trace and
                    # stats must tell one story)
                    tracker.autotune = autotune_plan.as_dict()
                flightrec.record_event("autotune", **autotune_plan.as_dict())
                if rounds_per_chunk != autotune_plan.requested:
                    slog(
                        "info", 0, "autotune",
                        f"rounds_per_chunk {autotune_plan.requested} -> "
                        f"{rounds_per_chunk} "
                        f"(probe {autotune_plan.probe_wall_s}s"
                        f" at rpc={autotune_plan.probe_rpc}, budget "
                        f"{autotune_plan.budget_s}s, {autotune_plan.source})",
                    )

        replicas = cfgo.general.replicas
        if self.mesh_plan is not None:
            # 2-D mesh plane (docs/parallelism.md "2-D mesh"): replicas
            # x host-shards on a Mesh(replica, hosts) grid (validated at
            # construction). Same run() surface as EnsembleRunner, so
            # the checkpoint/recovery plumbing below composes unchanged;
            # the stats folds below treat the batch as `replicas` worlds.
            from shadow_tpu.runtime.mesh import MeshRunner

            replicas = self.mesh_plan.replicas
            sched = MeshRunner(
                model,
                tables,
                ecfg,
                plan=self.mesh_plan,
                seed_stride=cfgo.general.replica_seed_stride,
                rounds_per_chunk=rounds_per_chunk,
                tx_bytes_per_interval=tx_refill,
                rx_bytes_per_interval=rx_refill,
                watchdog_s=cfgo.experimental.chunk_watchdog_s,
            )
        elif replicas > 1:
            # Ensemble plane (docs/ensemble.md): R vmapped replicas in one
            # device program (validated at construction). Same run()
            # surface as TpuScheduler, so the checkpoint/recovery plumbing
            # below composes unchanged.
            from shadow_tpu.runtime.ensemble import EnsembleRunner

            sched = EnsembleRunner(
                model,
                tables,
                ecfg,
                num_replicas=replicas,
                seed_stride=cfgo.general.replica_seed_stride,
                rounds_per_chunk=rounds_per_chunk,
                tx_bytes_per_interval=tx_refill,
                rx_bytes_per_interval=rx_refill,
                watchdog_s=cfgo.experimental.chunk_watchdog_s,
            )
        else:
            sched = make_scheduler(
                cfgo.experimental.scheduler,
                model,
                tables,
                ecfg,
                host_node,
                parallelism=cfgo.general.parallelism,
                rounds_per_chunk=rounds_per_chunk,
                tx_bytes_per_interval=tx_refill,
                rx_bytes_per_interval=rx_refill,
                watchdog_s=cfgo.experimental.chunk_watchdog_s,
            )

        end = cfgo.general.stop_time_ns
        hb_ns = cfgo.general.heartbeat_interval_ns
        last_hb = [0]

        # occupancy denominator, set BEFORE the run so heartbeat lines
        # and mid-run metrics divide correctly: iters_done sums per-shard
        # (or, after the ensemble flatten, per-replica) drain-loop
        # counts, each covering only H/planes lanes (utils/tracker.py)
        if self.mesh_plan is not None:
            # R*S drain loops of H/S lanes each: reduces to the ensemble
            # convention (R) at S=1 and the sharded one (S) at R=1
            num_shards = self.mesh_plan.replicas * self.mesh_plan.shards
        else:
            num_shards = replicas if replicas > 1 else (
                getattr(sched, "num_devices", 1) or 1
            )
        if tracker is not None:
            tracker.num_shards = num_shards
        recorder.num_shards = max(1, num_shards)

        def on_chunk(probe):
            # probe is an engine ChunkProbe of already-fetched ints (the
            # driver's per-chunk termination probe): progress and
            # heartbeat lines cost zero extra device syncs
            progress.update(probe.now, end, events=probe.events_handled)
            if tracker is not None:
                tracker.record_probe(probe)
            if hb_ns <= 0:
                return
            if probe.now - last_hb[0] >= hb_ns:
                last_hb[0] = probe.now
                progress.clear()
                extra = ""
                if tracker is not None:
                    # the probe's tracker lanes: aggregate drop/kind
                    # detail on the manager heartbeat, still sync-free
                    extra = (
                        f", drops loss={probe.drop_loss} "
                        f"codel={probe.drop_codel} "
                        f"unroutable={probe.drop_unroutable}"
                    )
                slog(
                    "info",
                    probe.now,
                    "manager",
                    f"heartbeat: {probe.events_handled} events, "
                    f"{probe.packets_sent} packets, sim time "
                    f"{fmt_time_ns(probe.now)}{extra}",
                )

        rep_note = f"{replicas} replicas, " if replicas > 1 else ""
        if self.mesh_plan is not None:
            rep_note = (
                f"{replicas} replicas on a {self.mesh_plan.rows}x"
                f"{self.mesh_plan.shards} mesh, "
            )
        eng = getattr(sched, "engine", None)
        eng_note = f"engine={eng}, " if eng else ""
        if getattr(sched, "route_path", None):
            eng_note += f"route={sched.route_path}:{sched.route_runs}, "
        slog("info", 0, "manager", f"starting: {num_hosts} hosts, {rep_note}"
             f"scheduler={sched.name}, {eng_note}"
             f"runahead={runahead}ns, stop={fmt_time_ns(end)}")
        t0 = time.perf_counter()
        try:
            if isinstance(sched, CpuRefScheduler):
                final = sched.run(end, on_chunk=on_chunk, tracker=tracker)
            else:
                resume_state = None
                if resume_path is not None:
                    from shadow_tpu.runtime.checkpoint import (
                        load_checkpoint,
                        reshard_note,
                    )

                    # resume_path came from latest_path, which verified
                    # the sha-256 digest moments ago — skip the second
                    # full hash. The snapshot is layout-free: a grid
                    # mismatch between ckpt.layout and meta["mesh"] is
                    # fine (the driver reshards at dispatch); only a
                    # fingerprint mismatch refuses, naming the keys.
                    resume_state, meta = load_checkpoint(
                        resume_path, sched.initial_state(), ckpt.fingerprint,
                        check_digest=False, detail=ckpt.detail,
                        layout=ckpt.layout,
                    )
                    slog("info", meta["now_ns"], "manager",
                         f"resuming from checkpoint {resume_path} "
                         f"(sim time {fmt_time_ns(meta['now_ns'])}"
                         f"{reshard_note(meta.get('mesh'), ckpt.layout)})")
                recovery = None
                if cfgo.experimental.recover:
                    from shadow_tpu.runtime.recovery import RecoveryPolicy

                    recovery = RecoveryPolicy(
                        max_recoveries=cfgo.experimental.recovery_max_retries,
                        snapshot_interval_chunks=(
                            cfgo.experimental.recovery_snapshot_chunks
                        ),
                    )
                try:
                    with guard if guard is not None else contextlib.nullcontext():
                        final = sched.run(
                            end, on_chunk=on_chunk, tracker=tracker,
                            start_state=resume_state, checkpoints=ckpt,
                            guard=guard, recovery=recovery,
                        )
                except RunInterrupted:
                    progress.clear()
                    slog("info", 0, "manager",
                         f"interrupted; checkpoints are in "
                         f"{cfgo.general.checkpoint_dir} — rerun with "
                         "--resume to continue to a bit-identical final "
                         "state")
                    raise
        except RunInterrupted:
            raise  # not a failure: a final checkpoint was committed
        except Exception as err:
            # post-mortem black box on EVERY failure path, plain
            # exceptions included — the ring already holds the failing
            # chunk's sample (_drive records the probe before raising)
            recorder.dump(failure=flightrec.failure_record(err))
            raise
        finally:
            recorder.close()
            flightrec.uninstall()
        wall = time.perf_counter() - t0
        progress.finish(end)

        if isinstance(sched, CpuRefScheduler):
            results = SimResults(
                hosts=self.hosts,
                # as the engine counts them: arrivals the ingress relay
                # deferred or dropped are in the oracle's trace, not here
                events_handled=sum(final.events_handled),
                packets_sent=sum(final.packets_sent),
                packets_dropped=sum(final.packets_dropped),
                packets_unroutable=0,
                wall_seconds=wall,
                sim_seconds=end / NS_PER_SEC,
                scheduler=sched.name,
            )
        else:
            results = SimResults(
                hosts=self.hosts,
                events_handled=int(np.asarray(final.events_handled).sum()),
                packets_sent=int(np.asarray(final.packets_sent).sum()),
                packets_dropped=int(np.asarray(final.packets_dropped).sum()),
                packets_unroutable=int(np.asarray(final.packets_unroutable).sum()),
                wall_seconds=wall,
                sim_seconds=end / NS_PER_SEC,
                scheduler=sched.name,
            )
        if replicas <= 1:
            # the per-host counters the device state and the oracle both
            # keep: what a parity check (chip_smoke.py) compares leaf for leaf
            results.extra_stats["per_host"] = {
                k: np.asarray(getattr(final, k)).tolist()
                for k in PER_HOST_COUNTERS
            }
        report = getattr(sched, "recovery_report", [])
        if report:
            # rollback-and-regrow happened: surface it in sim-stats.json
            # (the tracker registry carries the same records when attached)
            results.extra_stats["recovery"] = {
                "count": len(report),
                "events": report,
            }
        fallbacks = getattr(sched, "engine_fallbacks", [])
        watchdogs = sum(1 for r in report if r.get("kind") == "watchdog")
        if fallbacks or watchdogs:
            # the degradation ladder acted: a degraded run must be
            # VISIBLY degraded (docs/robustness.md), never silently slower
            results.extra_stats["degraded"] = {
                "engine_fallbacks": list(fallbacks),
                "watchdog_redispatches": watchdogs,
            }
        if not isinstance(sched, CpuRefScheduler):
            # where the run really ran, and on which engine (the last
            # fallback's when the ladder acted): a CPU run is visibly a
            # CPU run, and chip_smoke.py reads the device from here
            # because its own process never touches JAX
            import jax

            dev = jax.devices()[0]
            results.extra_stats["device"] = {
                "platform": dev.platform,
                "kind": dev.device_kind,
                "count": len(jax.devices()),
                "ids": sorted(d.id for d in final.seq.devices()),
                "engine": (
                    fallbacks[-1]["to"] if fallbacks
                    else getattr(sched, "engine", None)
                ),
                "route": getattr(sched, "route_path", None),
                "route_runs": getattr(sched, "route_runs", None),
            }
        if autotune_plan is not None:
            # what the autotuner decided and on what evidence — an
            # autotuned run is visibly autotuned in sim-stats.json
            results.extra_stats["autotune"] = autotune_plan.as_dict()
        self._fold_chaos(results)
        if self.mesh_plan is not None:
            # requested vs EFFECTIVE grid: device-loss degradation may
            # have re-planned the batch mid-run (runtime/mesh.py) — a
            # degraded run must be visibly degraded here too
            eff = getattr(sched, "plan", self.mesh_plan)
            results.extra_stats["mesh"] = {
                "replicas": eff.replicas,
                "shards": eff.shards,
                "rows": eff.rows,
                "requested": (
                    f"{self.mesh_plan.rows}x{self.mesh_plan.shards}"
                ),
                "effective": f"{eff.rows}x{eff.shards}",
            }
            degradations = getattr(sched, "mesh_degradations", [])
            if degradations:
                results.extra_stats["mesh"]["degradations"] = list(
                    degradations
                )
        host_tensors = None
        if replicas > 1:
            # per-replica sections + the aggregate mean/stddev/CI block
            # (docs/ensemble.md), folded from ONE bulk host_stats fetch
            # shared with the tracker fold below
            from shadow_tpu.engine.round import host_stats
            from shadow_tpu.runtime.ensemble import ensemble_stats

            host_tensors = host_stats(final)
            results.extra_stats["ensemble"] = ensemble_stats(
                final,
                sched.seeds,
                wall,
                end / NS_PER_SEC,
                seed_stride=cfgo.general.replica_seed_stride,
                host_tensors=host_tensors,
            )
        if not isinstance(sched, CpuRefScheduler):
            # memory observatory: the final state prices the run's device
            # footprint (post any rollback-and-regrow doubles), plus live
            # device stats where the backend reports them. Best-effort —
            # sim-stats must never fail over telemetry.
            try:
                from shadow_tpu.runtime import memtrack

                results.extra_stats["memory"] = memtrack.memory_section(final)
            except Exception:  # noqa: BLE001
                pass
        if recorder.metrics_path or recorder.prom_path:
            # a metrics-streamed run names its outputs in sim-stats so
            # the artifacts are discoverable from the run record
            results.extra_stats["metrics"] = {
                "samples": len(recorder.samples),
                "events": len(recorder.events),
                **({"file": recorder.metrics_path}
                   if recorder.metrics_path else {}),
                **({"prom": recorder.prom_path}
                   if recorder.prom_path else {}),
            }
        self._fold_tracker(
            tracker, results, end,
            final_state=None if isinstance(sched, CpuRefScheduler) else final,
            host_tensors=host_tensors,
        )
        slog("info", end, "manager",
             f"finished: {results.events_handled} events in {wall:.2f}s wall "
             f"({results.sim_sec_per_wall_sec:.2f} sim-s/wall-s)")
        self._write_outputs(results)
        return results

    def _fold_tracker(self, tracker, results, end, final_state=None,
                      host_tensors=None):
        """The shared run epilogue: fold the tracker registry into
        sim-stats' extra_stats and write the dispatch trace. With a
        final SimState and device counters on, performs the ONE bulk
        per-host fetch (the heartbeat path fetches only on cadence) —
        `host_tensors` supplies an already-fetched dict (the ensemble
        stats fold shares its fetch) so the run never pays it twice;
        span-only trackers (--trace-file without --tracker) publish
        phases only."""
        if tracker is None:
            return
        if tracker.counters and final_state is not None:
            from shadow_tpu.engine.round import host_stats

            hs = host_tensors if host_tensors is not None else host_stats(
                final_state
            )
            if self.config.general.replicas > 1 or self.mesh_plan is not None:
                # ensemble states fetch [R, H] tensors: flatten them to
                # the shape the host-side fold expects (exact per-replica
                # splits live in the `ensemble` stats block)
                from shadow_tpu.runtime.ensemble import flatten_host_stats

                hs = flatten_host_stats(hs)
            tracker.finalize(hs)
        results.extra_stats["tracker"] = tracker.stats_dict()
        trace_path = tracker.write_trace()
        if trace_path:
            slog("info", end, "manager", f"wrote dispatch trace: {trace_path}")

    def _setup_checkpointing(self, ecfg: EngineConfig):
        """Build the checkpoint manager + interrupt guard when
        general.checkpoint_dir asks for them, and resolve a --resume to
        the newest checkpoint. Resume validates the config fingerprint
        (the trajectory-pinning config hash) and rebuilds the engine
        config at the checkpoint's recorded buffer capacities, which may
        exceed the config values when the interrupted run had already
        regrown them. Returns (ecfg, ckpt_manager, guard, resume_path)."""
        from shadow_tpu.config.fingerprint import fingerprint_dict
        from shadow_tpu.runtime.checkpoint import (
            CheckpointError,
            CheckpointManager,
            InterruptGuard,
            config_fingerprint,
            resume_engine_cfg,
        )

        g = self.config.general
        if not g.checkpoint_dir:
            if g.resume:
                raise CheckpointError(
                    "--resume requires --checkpoint-dir (general.checkpoint_dir)"
                )
            return ecfg, None, None, None
        if self.config.experimental.scheduler != "tpu" or self.managed_mode:
            raise CheckpointError(
                "checkpointing supports scripted-model runs on the tpu "
                "scheduler; managed/hybrid runs get worker supervision "
                "instead (docs/robustness.md)"
            )
        fingerprint = config_fingerprint(self.config)
        resume_path = None
        if g.resume:
            resume_path = CheckpointManager.latest_path(g.checkpoint_dir)
            if resume_path is None:
                raise CheckpointError(
                    f"--resume: no checkpoint found in {g.checkpoint_dir}"
                )
            ecfg = resume_engine_cfg(resume_path, ecfg)
        layout = None
        if self.mesh_plan is not None:
            layout = f"{self.mesh_plan.rows}x{self.mesh_plan.shards}"
        ckpt = CheckpointManager(
            g.checkpoint_dir, g.checkpoint_interval_ns, fingerprint,
            layout=layout, detail=fingerprint_dict(self.config),
        )
        return ecfg, ckpt, InterruptGuard(), resume_path

    def _build_tracker(self, progress=None):
        """The host-side tracker registry (utils/tracker.py), or None
        when none of general.tracker, general.trace_file and
        experimental.xprof_dir asks for it. trace_file alone records
        dispatch spans, and an xprof capture wants them too: each span
        writes itself into the profiler's trace (`shadow:<name>`), on the
        clock of the device's operations. Per-host heartbeats and the
        sim-stats fold need the device counters (general.tracker)."""
        g = self.config.general
        if not (g.tracker or g.trace_file or self.config.experimental.xprof_dir):
            return None
        from shadow_tpu.utils.tracker import Tracker

        return Tracker(
            host_names=[h.name for h in self.hosts],
            heartbeat_ns=g.heartbeat_interval_ns if g.tracker else 0,
            trace_path=g.trace_file,
            clear_line=progress.clear if progress is not None else None,
            # per-host heartbeat lines name one host per row; ensemble
            # and mesh runs' per-host tensors are [R, H], so heartbeats
            # stay off there (aggregates still ride the probe)
            host_heartbeats=g.tracker and g.replicas <= 1 and not g.mesh,
            counters=g.tracker,
        )

    def _build_recorder(self, tracker=None, num_shards: int = 1):
        """The flight recorder (runtime/flightrec.py): always built — the
        bounded ring is free and the black-box dump must exist on every
        failure path — with the streaming/scrape/profiler outputs wired
        only when the config asks for them (--metrics-file /
        --metrics-prom / --xprof-dir)."""
        from shadow_tpu.runtime.flightrec import FlightRecorder

        g = self.config.general
        e = self.config.experimental
        blackbox = (
            os.path.join(g.data_directory, "flight-recorder.json")
            if g.data_directory
            else None
        )
        xprof_chunks = None
        if e.xprof_chunks:
            a, _, b = e.xprof_chunks.partition(":")
            xprof_chunks = (int(a), int(b))
        return FlightRecorder(
            num_hosts=len(self.hosts),
            num_shards=num_shards,
            metrics_path=g.metrics_file,
            metrics_max_bytes=int(g.metrics_max_mb * 1_000_000),
            metrics_keep=g.metrics_keep,
            prom_path=g.metrics_prom,
            blackbox_path=blackbox,
            heartbeat_ns=g.heartbeat_interval_ns,
            config_dict=self.config.to_dict(),
            tracker=tracker,
            xprof_dir=e.xprof_dir,
            xprof_chunks=xprof_chunks,
        )

    def _run_managed(self) -> SimResults:
        """Run real executables as managed processes under the LD_PRELOAD
        shim (spawn/resume managed_thread.rs:156-267). scheduler=tpu (the
        default) couples the CPU kernel to the device engine: guests
        execute on the CPU, their packets ride the device network plane
        (runtime/hybrid.py; reference manager.rs:392-478). scheduler=
        managed keeps the whole simulation on the serial CPU kernel. Both
        use the same round-window delivery clamp (worker.rs:399-402) and
        the same threefry streams, so their timelines are bit-identical."""
        from shadow_tpu.hostk.kernel import NetKernel, ProcessSpec

        cfgo = self.config
        if cfgo.general.checkpoint_dir or cfgo.general.resume:
            from shadow_tpu.runtime.checkpoint import CheckpointError

            raise CheckpointError(
                "checkpoint/resume supports scripted-model runs only; "
                "managed guests are live OS processes and cannot be "
                "serialized — hybrid runs get worker supervision instead "
                "(docs/robustness.md)"
            )
        host_node = [h.node_index for h in self.hosts]
        tables = compute_routing(self.graph, use_shortest_path=cfgo.network.use_shortest_path)
        tables = tables.with_hosts(host_node)

        runahead = self._resolve_runahead(tables)
        tracker = self._build_tracker()

        specs = [
            ProcessSpec(
                host=h.name,
                args=[p.path] + list(p.args),
                start_ns=p.start_time_ns,
                expected_final_state=p.expected_final_state,
                environment=p.environment,
                shutdown_ns=p.shutdown_time_ns,
            )
            for h in self.hosts
            for p in h.spec.processes
        ]
        sched_name = cfgo.experimental.scheduler
        if sched_name == "tpu" and cfgo.experimental.interface_qdisc == "rr":
            raise ValueError(
                "interface_qdisc: rr requires the serial kernel "
                "(experimental.scheduler: managed); the device engine's "
                "egress is FIFO in lane order"
            )
        if sched_name == "tpu" and cfgo.general.parallelism > 1:
            return self._run_managed_parallel(tables, runahead, specs, tracker)

        k = NetKernel(
            tables,
            host_names=[h.name for h in self.hosts],
            host_nodes=host_node,
            seed=cfgo.general.seed,
            data_dir=cfgo.general.data_directory,
            syscall_latency_ns=cfgo.experimental.syscall_latency_ns,
            vdso_latency_ns=cfgo.experimental.vdso_latency_ns,
            max_unapplied_ns=cfgo.experimental.max_unapplied_cpu_latency_ns,
            strace_mode=cfgo.experimental.strace_logging_mode,
            pcap=cfgo.experimental.use_pcap,
            host_ips=[h.ip for h in self.hosts],
            heartbeat_ns=cfgo.general.heartbeat_interval_ns,
            progress=cfgo.general.progress,
            bw_up_bits=[max(h.bw_up_bits, 0) for h in self.hosts],
            bw_down_bits=[max(h.bw_down_bits, 0) for h in self.hosts],
            bootstrap_end_ns=cfgo.general.bootstrap_end_time_ns,
            window_ns=runahead,
            tcp_sack=cfgo.experimental.use_tcp_sack,
            tcp_autotune=cfgo.experimental.use_tcp_autotune,
            qdisc=cfgo.experimental.interface_qdisc,
            use_memory_manager=cfgo.experimental.use_memory_manager,
            cpu_freq_hz=[h.cpu_freq_hz for h in self.hosts],
        )
        for s in specs:
            k.add_process(s)

        if sched_name == "tpu":
            from shadow_tpu.netstack import bw_bits_per_sec_to_refill
            from shadow_tpu.runtime.hybrid import HybridScheduler

            bw_up = np.array([max(h.bw_up_bits, 0) for h in self.hosts], dtype=np.int64)
            bw_down = np.array([max(h.bw_down_bits, 0) for h in self.hosts], dtype=np.int64)
            use_netstack = bool((bw_up > 0).any() or (bw_down > 0).any())
            ecfg = EngineConfig(
                num_hosts=len(self.hosts),
                queue_capacity=cfgo.experimental.queue_capacity,
                outbox_capacity=cfgo.experimental.outbox_capacity,
                runahead_ns=runahead,
                seed=cfgo.general.seed,
                max_iters_per_round=cfgo.experimental.max_iters_per_round,
                use_netstack=use_netstack,
                bootstrap_end_ns=cfgo.general.bootstrap_end_time_ns,
            )
            runner = HybridScheduler(
                k,
                tables,
                ecfg,
                tx_bytes_per_interval=(
                    np.asarray(bw_bits_per_sec_to_refill(bw_up)) if use_netstack else None
                ),
                rx_bytes_per_interval=(
                    np.asarray(bw_bits_per_sec_to_refill(bw_down)) if use_netstack else None
                ),
                record_capacity=cfgo.experimental.record_capacity,
            )
            runner.tracker = tracker
            run_fn, sched_label = runner.run, HybridScheduler.name
        else:
            run_fn, sched_label = k.run, "managed"

        end = cfgo.general.stop_time_ns
        slog("info", 0, "manager",
             f"starting: {len(self.hosts)} hosts, scheduler={sched_label}, "
             f"{len(k.procs)} managed processes, stop={fmt_time_ns(end)}")
        from shadow_tpu.runtime import flightrec

        recorder = self._build_recorder(tracker)
        flightrec.install(recorder)
        t0 = time.perf_counter()
        try:
            run_fn(end)
        except Exception as err:
            # worker crashes and plain exceptions get the same black box
            # as the scripted drivers (events: worker respawns, spans)
            recorder.dump(failure=flightrec.failure_record(err))
            raise
        finally:
            k.shutdown()
            recorder.close()
            flightrec.uninstall()
        wall = time.perf_counter() - t0

        stats = k.stats()
        unexpected = k.unexpected_final_states()
        for u in unexpected:
            slog("warning", end, "manager", f"unexpected final state: {u}")
        results = SimResults(
            hosts=self.hosts,
            events_handled=stats["syscalls_handled"],
            packets_sent=stats["packets_sent"],
            packets_dropped=stats["packets_dropped"],
            packets_unroutable=0,
            wall_seconds=wall,
            sim_seconds=end / NS_PER_SEC,
            scheduler=sched_label,
            unexpected_final_states=unexpected,
            extra_stats=stats,
        )
        self._fold_tracker(tracker, results, end)
        self._fold_chaos(results)
        slog("info", end, "manager",
             f"finished: {stats['syscalls_handled']} syscalls, "
             f"{stats['packets_sent']} packets in {wall:.2f}s wall")
        self._write_outputs(results)
        return results

    def _run_managed_parallel(
        self, tables, runahead: int, specs, tracker=None
    ) -> SimResults:
        """Managed run with hosts sharded over worker kernel processes
        (general.parallelism workers) and packets on the device engine —
        the role of the reference's thread_per_core scheduler
        (thread_per_core.rs:188-206) with processes instead of threads."""
        from shadow_tpu.netstack import bw_bits_per_sec_to_refill
        from shadow_tpu.runtime.hybrid import ParallelHybridScheduler

        cfgo = self.config
        bw_up = np.array([max(h.bw_up_bits, 0) for h in self.hosts], dtype=np.int64)
        bw_down = np.array([max(h.bw_down_bits, 0) for h in self.hosts], dtype=np.int64)
        use_netstack = bool((bw_up > 0).any() or (bw_down > 0).any())
        ecfg = EngineConfig(
            num_hosts=len(self.hosts),
            queue_capacity=cfgo.experimental.queue_capacity,
            outbox_capacity=cfgo.experimental.outbox_capacity,
            runahead_ns=runahead,
            seed=cfgo.general.seed,
            max_iters_per_round=cfgo.experimental.max_iters_per_round,
            use_netstack=use_netstack,
            bootstrap_end_ns=cfgo.general.bootstrap_end_time_ns,
        )
        sched = ParallelHybridScheduler(
            tables,
            ecfg,
            host_names=[h.name for h in self.hosts],
            host_nodes=[h.node_index for h in self.hosts],
            specs=specs,
            num_workers=cfgo.general.parallelism,
            seed=cfgo.general.seed,
            data_dir=cfgo.general.data_directory,
            bw_up_bits=[max(h.bw_up_bits, 0) for h in self.hosts],
            bw_down_bits=[max(h.bw_down_bits, 0) for h in self.hosts],
            host_ips=[h.ip for h in self.hosts],
            tx_bytes_per_interval=(
                np.asarray(bw_bits_per_sec_to_refill(bw_up)) if use_netstack else None
            ),
            rx_bytes_per_interval=(
                np.asarray(bw_bits_per_sec_to_refill(bw_down)) if use_netstack else None
            ),
            record_capacity=cfgo.experimental.record_capacity,
            strace_mode=cfgo.experimental.strace_logging_mode,
            pcap=cfgo.experimental.use_pcap,
            heartbeat_ns=cfgo.general.heartbeat_interval_ns,
            bootstrap_end_ns=cfgo.general.bootstrap_end_time_ns,
            tcp_sack=cfgo.experimental.use_tcp_sack,
            tcp_autotune=cfgo.experimental.use_tcp_autotune,
            syscall_latency_ns=cfgo.experimental.syscall_latency_ns,
            vdso_latency_ns=cfgo.experimental.vdso_latency_ns,
            max_unapplied_ns=cfgo.experimental.max_unapplied_cpu_latency_ns,
            cpu_freq_hz=[h.cpu_freq_hz for h in self.hosts],
        )
        sched.tracker = tracker
        end = cfgo.general.stop_time_ns
        slog("info", 0, "manager",
             f"starting: {len(self.hosts)} hosts, scheduler={sched.name} "
             f"({sched.num_workers} workers), {len(specs)} managed processes, "
             f"stop={fmt_time_ns(end)}")
        from shadow_tpu.runtime import flightrec

        recorder = self._build_recorder(tracker)
        flightrec.install(recorder)
        t0 = time.perf_counter()
        try:
            try:
                sched.run(end)
            except Exception as err:
                # the worker-crash post-mortem: respawn events already
                # ride the recorder (runtime/hybrid.py _revive)
                recorder.dump(failure=flightrec.failure_record(err))
                raise
            finally:
                sched.shutdown()
            wall = time.perf_counter() - t0
            stats = sched.stats()
            unexpected = sched.unexpected_final_states()
        finally:
            sched.close()
            recorder.close()
            flightrec.uninstall()
        for u in unexpected:
            slog("warning", end, "manager", f"unexpected final state: {u}")
        results = SimResults(
            hosts=self.hosts,
            events_handled=stats["syscalls_handled"],
            packets_sent=stats["packets_sent"],
            packets_dropped=stats["packets_dropped"],
            packets_unroutable=0,
            wall_seconds=wall,
            sim_seconds=end / NS_PER_SEC,
            scheduler=sched.name,
            unexpected_final_states=unexpected,
            extra_stats=stats,
        )
        self._fold_tracker(tracker, results, end)
        self._fold_chaos(results)
        slog("info", end, "manager",
             f"finished: {stats['syscalls_handled']} syscalls, "
             f"{stats['packets_sent']} packets in {wall:.2f}s wall")
        self._write_outputs(results)
        return results

    def _write_outputs(self, results: SimResults) -> None:
        data_dir = self.config.general.data_directory
        os.makedirs(data_dir, exist_ok=True)
        # sim-stats.json (reference: sim_stats.rs:110 write_stats_to_file)
        with open(os.path.join(data_dir, "sim-stats.json"), "w") as f:
            json.dump(
                {
                    "events_handled": results.events_handled,
                    "packets_sent": results.packets_sent,
                    "packets_dropped": results.packets_dropped,
                    "packets_unroutable": results.packets_unroutable,
                    "wall_seconds": results.wall_seconds,
                    "sim_seconds": results.sim_seconds,
                    "scheduler": results.scheduler,
                    "num_hosts": len(results.hosts),
                    "unexpected_final_states": results.unexpected_final_states,
                    **results.extra_stats,
                },
                f,
                indent=2,
            )
        # processed config (reference: manager.rs:187-198)
        with open(os.path.join(data_dir, "processed-config.json"), "w") as f:
            json.dump(self.config.to_dict(), f, indent=2, default=str)
        # hosts file (the analogue of the DNS /etc/hosts export, dns.c:115)
        with open(os.path.join(data_dir, "hosts"), "w") as f:
            for h in self.hosts:
                f.write(f"{self.ip.ip_str(h.index)} {h.name}\n")
