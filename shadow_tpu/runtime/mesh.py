"""MeshRunner: the runtime face of the 2-D mesh plane (engine/mesh.py;
docs/parallelism.md "2-D mesh").

Drop-in for EnsembleRunner/TpuScheduler on scripted-model runs with
`general.mesh` set (`--mesh RxS`): the same run() surface —
start_state / checkpoints / guard / recovery — so the Manager's
fault-tolerant run loop (StateTap two-phase commit, rollback-and-regrow,
the engine fallback ladder) composes unchanged. What the mesh adds:

  * the state is the SAME [R, ...] init_ensemble_state stack, laid out
    over a Mesh(replica, hosts) device grid — so checkpoints are
    byte-compatible with the ensemble plane's AND grid-portable
    (docs/parallelism.md "Elastic mesh"): the host snapshot is
    layout-free, the grid travels as layout metadata only, and the
    config fingerprint hashes the EFFECTIVE replica count instead of
    the grid — an RxS checkpoint resumes on any R'xS' (the driver
    reshards at dispatch), while a resume that would change the number
    of simulated worlds still refuses with a CheckpointError naming
    the offending keys;
  * device loss is a recovery rung, not a terminal fault: a
    DeviceLossError (real, from the probe fetch, or the chaos plane's
    `device-loss` fault) rolls back to the retained snapshot,
    _replan_device_loss degrades the grid onto the surviving device
    set (MeshPlan.degraded: R×S → R×S/2 → 1×S → single device),
    recompiles through the same AOT seam, and replays leaf-exact — the
    reshape is journaled as a `recovery` record and a flight-recorder
    event, and `mesh_degradations` carries it to sim-stats;
  * recovery regrows the WHOLE mesh batch (grow_mesh_state — the
    replica-vmapped grow, shard layout restored at the next dispatch):
    one (replica, shard) cell's CapacityError, which names both
    coordinates, rolls every cell back to the shared retained snapshot
    and replays on the one regrown compiled shape;
  * the sweep/daemon services batch THROUGH this runner when the spec
    sets `mesh:` — the compile cache keys mesh executables under
    (fingerprint-modulo-seed, mesh RxS, rounds_per_chunk) via
    lower_mesh_chunk, so N same-shape mesh jobs pay one XLA compile,
    persistent across daemon restarts.
"""

from __future__ import annotations

from shadow_tpu.engine.ensemble import grow_ensemble_state, replica_seeds
from shadow_tpu.engine.mesh import (
    MeshPlan,
    init_mesh_state,
    lower_mesh_chunk,
    mesh_engine_cfg,
    run_mesh_until,
)
from shadow_tpu.engine.state import EngineConfig

# the regrow step is shape-agnostic over the replica axis: the vmapped
# grow widens every replica's fixed-slot buffers together, and the mesh
# layout is re-applied by the next dispatch's shard_mesh_state
grow_mesh_state = grow_ensemble_state


def _device_alive(device) -> bool:
    """Can this device still round-trip one scalar? The liveness probe
    behind the unattributed-loss path of MeshRunner._devices: a dead
    PJRT device fails the put or the fetch, a healthy one costs
    microseconds."""
    import jax
    import numpy as np

    try:
        out = jax.device_put(np.zeros((), np.int32), device)
        jax.block_until_ready(out)
        return True
    except Exception:  # noqa: BLE001 — any failure means "not usable"
        return False


class MeshRunner:
    name = "tpu-mesh"

    def __init__(
        self,
        model,
        tables,
        cfg: EngineConfig,
        plan: MeshPlan,
        seed_stride: int = 1,
        rounds_per_chunk: int = 256,
        tx_bytes_per_interval=None,
        rx_bytes_per_interval=None,
        compile_cache=None,
        cache_key=None,
        on_rows=None,
        watchdog_s: float = 0.0,
    ):
        if cfg.num_hosts % plan.shards:
            raise ValueError(
                f"num_hosts={cfg.num_hosts} must divide evenly over "
                f"{plan.shards} host-shard(s) (general.mesh)"
            )
        # resolved once so initial_state, the chunk jit cache key, and
        # every recovery recompile agree on the engine AND the exchange
        # (mesh_engine_cfg pins all_gather — engine/mesh.py)
        self.cfg = mesh_engine_cfg(cfg)
        self.plan = plan
        self.model = model
        self.tables = tables
        self.seed_stride = seed_stride
        self.rounds_per_chunk = rounds_per_chunk
        self.tx_bytes_per_interval = tx_bytes_per_interval
        self.rx_bytes_per_interval = rx_bytes_per_interval
        self.compile_cache = compile_cache
        self.cache_key = cache_key
        self.on_rows = on_rows
        self.watchdog_s = watchdog_s
        self._mesh = None  # built lazily, reused across attempts
        # device-loss degradation history: one record per reshape
        # ({"grid_from", "grid_to", "devices", ...}), folded into
        # sim-stats' mesh block by the Manager and into the sweep
        # batch record by the service
        self.mesh_degradations: "list[dict]" = []

    @property
    def num_replicas(self) -> int:
        return self.plan.replicas

    @property
    def seeds(self) -> "list[int]":
        return replica_seeds(self.cfg, self.plan.replicas, self.seed_stride)

    def _get_mesh(self):
        if self._mesh is None:
            self._mesh = self.plan.build_mesh(self._devices())
        return self._mesh

    def _devices(self):
        """The surviving device set: all visible devices minus any the
        degradation history marked lost. Injected faults name a device
        that is still physically present, so the exclusion is what
        makes the simulated loss real — the degraded grid genuinely
        avoids the 'dead' device. A REAL loss often cannot name its
        device (the XLA error rarely does), so when the history carries
        an unattributed loss the set is additionally probed: each
        candidate must survive a tiny put+fetch, and ones that fail are
        excluded exactly like named ones. Probes run only after an
        unattributed loss (the healthy path never pays them — _get_mesh
        caches the built mesh until a replan invalidates it)."""
        import jax

        lost = {
            d["device"] for d in self.mesh_degradations if "device" in d
        }
        devices = [d for d in jax.devices() if d.id not in lost]
        if any("device" not in d for d in self.mesh_degradations):
            devices = [d for d in devices if _device_alive(d)]
        return devices or jax.devices()  # never degrade to zero devices

    def _replan_device_loss(self, err) -> "dict | None":
        """The recovery loop's replan hook (runtime/recovery.py
        replan_fn): pick the next degradation rung that fits the
        surviving device set, install it on the runner (the factory
        reads self.plan/self._mesh at dispatch time, so the very next
        attempt dispatches degraded), and return the reshape record.
        None = no rung left — the loss becomes terminal."""
        lost = getattr(err, "device_id", None)
        record = {
            "grid_from": f"{self.plan.rows}x{self.plan.shards}",
        }
        if lost is not None:
            record["device"] = int(lost)
            survivors = len(self._devices()) - (
                1 if lost not in {d["device"] for d in
                                  self.mesh_degradations if "device" in d}
                else 0
            )
        else:
            # an unattributed loss (real failures rarely name their
            # device): probe THIS loss's survivor set now, not just the
            # history's — several devices may have died at once, and an
            # over-stated count would pick a rung the next dispatch
            # cannot build (a ValueError the ladder doesn't catch)
            survivors = sum(1 for d in self._devices() if _device_alive(d))
        plan = self.plan.degraded(max(survivors, 1), self.cfg.num_hosts)
        if plan is None:
            return None
        self.mesh_degradations.append(record)
        self.plan = plan
        self._mesh = None  # rebuilt lazily against the surviving set
        record["grid_to"] = f"{plan.rows}x{plan.shards}"
        record["devices"] = plan.devices_needed
        return record

    def initial_state(self, cfg: "EngineConfig | None" = None):
        """The bootstrapped [R, ...] t=0 stack — also the template a
        resume loads a checkpoint into (same config -> same shapes; the
        mesh layout is applied at dispatch, so ensemble-plane templates
        and mesh templates are interchangeable leaf-for-leaf)."""
        cfg = cfg or self.cfg
        return init_mesh_state(
            cfg,
            self.model,
            self.plan,
            self.seed_stride,
            tx_bytes_per_interval=self.tx_bytes_per_interval,
            rx_bytes_per_interval=self.rx_bytes_per_interval,
        )

    def _launch_for(self, st, end_time_ns: int, cfg):
        """The compile-cache lookup (EnsembleRunner._launch_for's mesh
        twin): an AOT-compiled 2-D chunk executable for this
        (fingerprint-modulo-seed key, mesh shape, state shapes, static
        cfg), or None to use the process-wide jit cache."""
        if self.compile_cache is None:
            return None
        from shadow_tpu.engine.round import effective_engine
        from shadow_tpu.engine.state import trace_static_cfg
        from shadow_tpu.runtime import chaos

        static_cfg = trace_static_cfg(mesh_engine_cfg(cfg))
        eng = effective_engine(static_cfg)
        with chaos.compile_seam(eng):
            return self.compile_cache.get(
                (
                    self.cache_key,
                    "mesh",
                    self.plan.rows,
                    self.plan.shards,
                    self.rounds_per_chunk,
                ),
                st,
                static_cfg,
                lambda: lower_mesh_chunk(
                    st, end_time_ns, self.rounds_per_chunk, self.model,
                    self.tables, cfg, self.plan, mesh=self._get_mesh(),
                ).compile(),
            )

    def _runner_factory(self, end_time_ns: int, on_chunk, max_chunks, tracker):
        def factory(cfg):
            def run(st, on_state=None):
                return run_mesh_until(
                    st, end_time_ns, self.model, self.tables, cfg,
                    self.plan,
                    rounds_per_chunk=self.rounds_per_chunk,
                    max_chunks=max_chunks, on_chunk=on_chunk,
                    tracker=tracker, on_state=on_state,
                    on_rows=self.on_rows,
                    launch=self._launch_for(st, end_time_ns, cfg),
                    watchdog_s=self.watchdog_s,
                    mesh=self._get_mesh(),
                )

            return run

        return factory

    def run(self, end_time_ns: int, on_chunk=None, max_chunks: int = 100_000,
            tracker=None, start_state=None, checkpoints=None, guard=None,
            recovery=None):
        """Run the whole mesh batch to end_time_ns (the driver stops
        when the slowest replica quiesces). Mirrors EnsembleRunner.run —
        engine fallback ladder (walked only with a `recovery` policy;
        `recovery=None` fails fast on the first EngineCompileError),
        recovery loop with the whole-batch regrow — with the chunk
        dispatch on the 2-D mesh."""
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
                grow_fn=grow_mesh_state,
                # the mesh-degradation rung: a DeviceLossError re-plans
                # the batch onto the surviving grid and replays from the
                # retained snapshot, leaf-exact (docs/robustness.md)
                replan_fn=self._replan_device_loss,
            )

        self.engine_fallbacks: "list[dict]" = []
        try:
            (final, report), _ = run_with_engine_ladder(
                self.cfg, attempt,
                on_fallback=self.engine_fallbacks.append,
                fail_fast=recovery is None,
            )
        except Exception as err:
            self.recovery_report = list(getattr(err, "recoveries", []))
            raise
        self.recovery_report = report
        return final
