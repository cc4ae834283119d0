"""2-D mesh plane: sharded ensembles — replicas x host-shards in ONE
device program (docs/parallelism.md "2-D mesh").

The two scale planes this repo grew separately are mutually exclusive by
construction: the ensemble plane (engine/ensemble.py) vmaps R replicas
on a single device, and the sharded plane (engine/sharded.py) block-
shards ONE replica's hosts over a device mesh. This module composes them
on a `Mesh(replica, hosts)`:

  * every leaf of the [R, H, ...] state is sharded
    `P("replica", "hosts", ...)` — replica rows spread over the
    `replica` mesh axis, hosts block-sharded over the `hosts` axis
    INSIDE each row; per-replica scalars ([R] leaves: now, win_ns_sum,
    the round counters) shard `P("replica")`;
  * inside the shard_map block, a jax.vmap over the local replica
    sub-batch runs the UNCHANGED round engine with axis_name="hosts" —
    so the Shadow-style per-round contract (Chandy–Misra/Fujimoto
    conservative-window agreement + outbox exchange) stays exactly
    where the sharded plane put it: the window `pmin` and the exchange
    collective ride the `hosts` axis only, and replicas never
    communicate (there is no collective over "replica" anywhere in the
    round loop). PR 9's adaptive-window `pmin` is already mesh-uniform
    per replica row, so it composes unchanged;
  * the per-chunk probe widens to [R, PROBE_LANES]: each replica's row
    is psum/pmin/pmax-reduced along `hosts` only (replicated within its
    row, distinct across rows), so the existing per-replica ensemble
    driver (`_drive_ensemble`: per-replica quiescence recording,
    `_finish`/`_patch_snapshot` leaf-exactness, per-replica capacity
    rows, the sweep's on_rows stream) drives mesh chunks without
    modification.

Exactness contract (tests/test_mesh.py, pinned on the virtual 8-device
CPU mesh): slice r of a mesh run is leaf-identical — tracker leaves
included, through checkpoint/resume — to a single-device run seeded
`seed + r * stride`. It holds because each plane's own contract holds
and the composition adds no new seam: within a replica row the program
IS the sharded engine (already leaf-exact vs single-device,
tests/test_sharded.py), across rows it IS the vmapped ensemble (already
leaf-exact per slice, tests/test_ensemble.py), and the state is built
by the same init_ensemble_state stack.

One mesh-specific wrinkle: the destination-bucketed all_to_all exchange
is not batchable under the replica vmap (jax has no batching rule for
lax.all_to_all), so mesh configs resolve `exchange` to "all_gather" —
trajectory-neutral by the exchange-mode contract (delivery order is
key-driven; engine/round.py flush_outbox), at the cost of more ICI
traffic per round.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shadow_tpu import scopes
from shadow_tpu.engine.ensemble import (
    _drive_ensemble,
    _peek_next_time_ensemble,
    ensemble_engine_cfg,
    init_ensemble_state,
    num_replicas,
    replica_seeds,
    replica_slice,
)
from shadow_tpu.engine.round import (
    PROBE_OVERFLOW,
    _capacity_error,
    _tspan,
    check_capacity,
    effective_engine,
    run_rounds_scan,
    state_probe,
    validate_runahead,
)
from shadow_tpu.engine.state import EngineConfig, SimState, trace_static_cfg

# one definition of the "RxS" grid spec, shared with config validation
from shadow_tpu.config.options import parse_mesh  # noqa: F401

REPLICA_AXIS = "replica"
# the inner collective axis keeps the sharded plane's name so every
# axis_name-parameterized engine path (window pmin, exchange, probe
# reductions) is shared verbatim with engine/sharded.py
HOST_AXIS = "hosts"


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """The 2-D decomposition of one [R, H, ...] batch.

    `rows x shards` is the device grid (`Mesh(replica, hosts)`);
    `replicas` is the batch's replica count. When replicas > rows, each
    mesh row holds a replicas/rows sub-batch vmapped locally — "64
    replicas of a 10k-host world" on an 8-device 2x4 grid is rows=2
    carrying 32 vmapped replicas each. rows=1 degenerates to the pure
    sharded shape, shards=1 to the pure ensemble shape, both through
    this one code path."""

    replicas: int
    shards: int
    rows: int

    def __post_init__(self):
        if self.replicas < 1 or self.shards < 1 or self.rows < 1:
            raise ValueError("mesh replicas/shards/rows must all be >= 1")
        if self.replicas % self.rows:
            raise ValueError(
                f"mesh replicas={self.replicas} must be a multiple of the "
                f"replica-axis rows={self.rows} (each mesh row holds "
                "replicas/rows vmapped replicas)"
            )

    @property
    def devices_needed(self) -> int:
        return self.rows * self.shards

    @property
    def local_replicas(self) -> int:
        return self.replicas // self.rows

    def describe(self) -> str:
        return (
            f"{self.replicas} replica(s) x {self.shards} shard(s) on a "
            f"{self.rows}x{self.shards} Mesh(replica, hosts)"
        )

    def build_mesh(self, devices=None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        need = self.devices_needed
        if len(devices) < need:
            raise ValueError(
                f"mesh {self.rows}x{self.shards} needs {need} devices, "
                f"{len(devices)} visible"
            )
        grid = np.array(devices[:need]).reshape(self.rows, self.shards)
        return Mesh(grid, (REPLICA_AXIS, HOST_AXIS))

    @classmethod
    def for_batch(cls, replicas: int, rows: int, shards: int) -> "MeshPlan":
        """The plan for a batch of `replicas` jobs on a requested RxS
        grid, degrading the replica-axis rows to the largest divisor of
        the batch size when it does not fill the grid — a split/retried
        single-job batch on a 2x4 sweep mesh runs 1x4 (pure sharded)
        through the same code path instead of refusing."""
        rows_eff = max(
            (d for d in range(1, replicas + 1)
             if replicas % d == 0 and d <= rows),
            default=1,
        )
        return cls(replicas=replicas, shards=shards, rows=rows_eff)

    def degraded(self, devices_available: int,
                 num_hosts: int) -> "MeshPlan | None":
        """The next mesh-degradation rung after a device loss
        (docs/robustness.md "Device loss"): the first SMALLER grid that
        fits the surviving device count and still divides the host
        axis, walked in preference order R×S/2 (halve the shard axis,
        every replica row intact), 1×S (collapse the replica rows onto
        one row of shards), then 1×S/2 … 1×1 (single device — the pure
        vmapped ensemble). Each candidate resolves through for_batch so
        the replicas-per-row constraint can never refuse a rung. None
        when already at 1×1 with nothing below — the loss is terminal.

        Sound as a *degradation* ladder for the same reason the engine
        ladder is: the state is layout-free ([R, H, ...] regardless of
        grid) and every grid is slice-exact to the single-device run
        (tests/test_mesh.py), so falling a rung changes wall-clock and
        ICI traffic, never a result leaf."""
        cands: "list[tuple[int, int]]" = []
        if self.shards > 1:
            cands.append((self.rows, self.shards // 2))
        if self.rows > 1:
            cands.append((1, self.shards))
        s = self.shards // 2
        while s >= 1:
            cands.append((1, s))
            s //= 2
        for rows, shards in cands:
            if rows * shards >= self.devices_needed:
                continue  # a rung must shed devices, not rearrange them
            if num_hosts % shards:
                continue
            if rows * shards <= devices_available:
                return MeshPlan.for_batch(self.replicas, rows, shards)
        return None


def mesh_engine_cfg(cfg: EngineConfig) -> EngineConfig:
    """The engine config a mesh batch actually traces: the ensemble
    resolution (done-mask armed) plus the exchange pinned to all_gather:
    lax.all_to_all has no vmap batching rule. The exchange modes are
    trajectory-identical by contract (flush_outbox: delivery order is
    key-driven), so the pin cannot change a slice."""
    return dataclasses.replace(ensemble_engine_cfg(cfg), exchange="all_gather")


def mesh_state_specs(st: SimState, plan: MeshPlan):
    """PartitionSpec pytree for an init_ensemble_state [R, ...] stack:
    [R] per-replica scalars shard over the replica axis, [R, H, ...]
    host-led leaves shard (replica, hosts); there are no fully
    replicated leaves in a mesh state."""
    del plan  # the specs depend only on leaf rank

    def spec(x):
        n = jnp.ndim(x)
        if n == 0:
            raise ValueError(
                "mesh states have no scalar leaves (every leaf leads "
                "with the replica axis) — not an init_ensemble_state "
                "stack?"
            )
        if n == 1:
            return P(REPLICA_AXIS)
        return P(REPLICA_AXIS, HOST_AXIS, *([None] * (n - 2)))

    return jax.tree.map(spec, st)


def shard_mesh_state(st: SimState, mesh: Mesh, plan: MeshPlan) -> SimState:
    specs = mesh_state_specs(st, plan)
    return jax.device_put(
        st,
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P),
        ),
    )


def init_mesh_state(
    cfg: EngineConfig,
    model,
    plan: MeshPlan,
    seed_stride: int = 1,
    tx_bytes_per_interval=None,
    rx_bytes_per_interval=None,
) -> SimState:
    """The bootstrapped [R, ...] initial stack — by construction the
    SAME pytree init_ensemble_state builds (replica r's row IS the
    single-world state for seed + r*stride), so slice-exactness is
    inherited, and a mesh checkpoint template equals an ensemble one."""
    if cfg.num_hosts % plan.shards:
        raise ValueError(
            f"num_hosts={cfg.num_hosts} must divide evenly over "
            f"{plan.shards} host-shard(s)"
        )
    return init_ensemble_state(
        cfg,
        model,
        plan.replicas,
        seed_stride,
        tx_bytes_per_interval=tx_bytes_per_interval,
        rx_bytes_per_interval=rx_bytes_per_interval,
    )


def _state_sig(st) -> tuple:
    """Hashable shape/dtype signature of a state pytree (the part of
    the chunk-fn cache key the static cfg does not cover once buffers
    are regrown — the compile cache's state_signature, duplicated here
    because engine code must not import runtime)."""
    return tuple(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree.leaves(st)
    )


# process-wide cache of jitted 2-D chunk dispatchers, the shard_map
# analogue of engine/round.py's module-level _run_chunk_jit: a fresh
# jax.jit wrapper per run_mesh_until call would retrace AND recompile
# every run, so the wrapper is keyed by everything that shapes the
# traced program (tables ride as traced arguments — the jit wrapper
# itself retraces when their shapes change)
_CHUNK_FNS: dict = {}


def _mesh_chunk_fn(st: SimState, plan: MeshPlan, mesh: Mesh,
                   rounds_per_chunk: int, model, tables, cfg: EngineConfig):
    """The jitted 2-D chunk dispatch for this state's shapes: a
    shard_map over Mesh(replica, hosts) whose block vmaps the sharded
    round engine over its local replica sub-batch. Donation mirrors
    engine/round.py _run_chunk_jit (the [R, H, ...] HBM state is aliased
    chunk-to-chunk). Cached per (mesh, chunking, model, cfg, state
    shape), so repeated runs of one world reuse one executable."""
    key = (
        mesh, plan, rounds_per_chunk, model, cfg,
        jax.tree.structure(st), _state_sig(st),
    )
    fn = _CHUNK_FNS.get(key)
    if fn is not None:
        return fn
    specs = mesh_state_specs(st, plan)
    tspecs = jax.tree.map(lambda _: P(), tables)

    @scopes.keyed
    def chunk(st_local, tables_r, end):
        def one(s):
            s = run_rounds_scan(
                s, end, rounds_per_chunk, model, tables_r, cfg,
                axis_name=HOST_AXIS,
            )
            # per-replica probe row, reduced along `hosts` ONLY: within
            # a replica row the collectives make it replicated; across
            # rows it stays that row's own values
            with jax.named_scope(scopes.PROBE):
                return s, state_probe(s, axis_name=HOST_AXIS)

        return jax.vmap(one)(st_local)

    f = shard_map(
        chunk,
        mesh=mesh,
        in_specs=(specs, tspecs, P()),
        out_specs=(specs, P(REPLICA_AXIS, None)),
        check_vma=False,
    )
    fn = jax.jit(f, donate_argnums=(0,))
    _CHUNK_FNS[key] = fn
    return fn


def lower_mesh_chunk(
    st: SimState, end, rounds_per_chunk: int, model, tables,
    cfg: EngineConfig, plan: MeshPlan, mesh: "Mesh | None" = None,
):
    """The AOT compile-cache seam, mesh variant (the `lower_ensemble_
    chunk` twin runtime/compile_cache.py consumers key under the mesh
    shape): returns a Lowered whose .compile() yields an executable
    called as `exe(st, tables, end)` with the input state donated. The
    static cfg is canonicalized through trace_static_cfg, so worlds
    differing only in seed lower to the identical key — the sweep's
    one-compile-per-world contract extends to mesh batches."""
    cfg = trace_static_cfg(mesh_engine_cfg(cfg))
    if mesh is None:
        mesh = plan.build_mesh()
    st = shard_mesh_state(st, mesh, plan)
    fn = _mesh_chunk_fn(st, plan, mesh, rounds_per_chunk, model, tables, cfg)
    return fn.lower(st, tables, jnp.asarray(end, jnp.int64))


def _mesh_capacity_detail(st: SimState, plan: MeshPlan) -> "list[dict]":
    """(replica, shard)-coordinate overflow breakdown, fetched only on
    the failure path: the probe's per-replica rows say WHICH replica
    blew but not which shard; this one bulk fetch of the four counter
    grids reshapes [R, H] -> [R, S, local] and names every saturated
    (replica, shard) cell with its overflow split and high-water marks,
    so regrow/debugging targets the hot cell instead of the row sum."""
    s = plan.shards
    qov, oov, qhw, ohw = (
        np.asarray(jax.device_get(a)).reshape(plan.replicas, s, -1)
        for a in (
            st.queue.overflow,
            st.outbox.overflow,
            st.tracker.queue_hwm,
            st.tracker.outbox_hwm,
        )
    )
    cells = []
    for r in range(plan.replicas):
        for j in range(s):
            if qov[r, j].sum() or oov[r, j].sum():
                cells.append(
                    {
                        "replica": r,
                        "shard": j,
                        "queue_overflow": int(qov[r, j].sum()),
                        "outbox_overflow": int(oov[r, j].sum()),
                        # hwm lanes accumulate only under cfg.tracker
                        "queue_hwm": int(qhw[r, j].max()),
                        "outbox_hwm": int(ohw[r, j].max()),
                    }
                )
    return cells


def mesh_capacity_error(rows: np.ndarray, st: SimState, plan: MeshPlan):
    """A CapacityError naming BOTH mesh coordinates: the first saturated
    (replica, shard) cell — not whichever plane raised first — with the
    saturated counter split and its high-water marks, plus err.replica /
    err.shard / err.mesh_cells for recovery records. Rollback-and-regrow
    (runtime/recovery.py with grow_mesh_state) then regrows the WHOLE
    mesh batch, keeping every cell on the one shared compiled shape.

    `rows` is the FAILING chunk's verified probe; `st` is the live state
    — under pipelining one chunk past it (the sharded driver's
    capacity_detail has the same property), so the per-cell counters are
    diagnostics that can only over-count, never under. The primary cell
    is therefore anchored to the first replica the PROBE convicted; its
    shard comes from that replica's live cells."""
    from shadow_tpu.engine.ensemble import _replica_capacity_error

    cells = _mesh_capacity_detail(st, plan)
    bad = np.nonzero(rows[:, PROBE_OVERFLOW] > 0)[0]
    probe_r = int(bad[0]) if bad.size else None
    first = next(
        (c for c in cells if c["replica"] == probe_r), cells[0] if cells else None
    )
    if first is None:
        # the state was donated/regrown under us: fall back to the row
        # split (still names the replica)
        err = _replica_capacity_error(rows)
        err.shard = None
        return err
    err = _capacity_error(
        sum(c["queue_overflow"] + c["outbox_overflow"] for c in cells),
        queue_ov=first["queue_overflow"],
        outbox_ov=first["outbox_overflow"],
        queue_hwm=first["queue_hwm"],
        outbox_hwm=first["outbox_hwm"],
    )
    err.replica = first["replica"]
    err.shard = first["shard"]
    err.mesh_cells = cells
    detail = (
        f"(replica {first['replica']}, shard {first['shard']}) of "
        f"{plan.replicas}x{plan.shards}"
    )
    if len(cells) > 1:
        detail += f" (+{len(cells) - 1} more saturated cell(s))"
    err.args = (f"{err.args[0]} [{detail}]",)
    err.shard_detail = "; ".join(
        f"(r{c['replica']}, s{c['shard']}): queue_ov={c['queue_overflow']} "
        f"outbox_ov={c['outbox_overflow']}"
        + (
            f" queue_hwm={c['queue_hwm']} outbox_hwm={c['outbox_hwm']}"
            if c["queue_hwm"] or c["outbox_hwm"]
            else ""
        )
        for c in cells
    )
    return err


def run_mesh_until(
    st: SimState,
    end_time: int,
    model,
    tables,
    cfg: EngineConfig,
    plan: MeshPlan,
    rounds_per_chunk: int = 64,
    max_chunks: int = 10_000,
    on_chunk=None,
    pipeline: bool = True,
    tracker=None,
    on_state=None,
    on_rows=None,
    launch=None,
    watchdog_s: float = 0.0,
    mesh: "Mesh | None" = None,
) -> SimState:
    """Host-side 2-D mesh driver: chunked shard_map(vmap(...)) dispatch
    until every replica quiesces. `st` is an init_mesh_state [R, ...]
    stack, `cfg` the per-replica single-world config (resolved through
    mesh_engine_cfg). The driver IS the ensemble driver
    (engine/ensemble.py _drive_ensemble): per-replica [R, PROBE_LANES]
    probe rows, per-replica quiescence recording with leaf-exact
    now/round-counter restoration, two-phase checkpoint commits,
    depth-2 pipelining, the sweep's on_rows stream — only the chunk
    launch and the capacity-error naming are mesh-specific. `launch`
    overrides the dispatch with a pre-compiled executable
    (lower_mesh_chunk + .compile(), via the compile cache) called as
    `exe(st, tables, end)`."""
    with _tspan(tracker, "run"):
        cfg = mesh_engine_cfg(cfg)
        with _tspan(tracker, "validate_runahead"):
            validate_runahead(cfg, tables)
        r = num_replicas(st)  # loud on a non-batched state
        if r != plan.replicas:
            raise ValueError(
                f"state carries {r} replica(s), plan expects {plan.replicas}"
            )
        if cfg.num_hosts % plan.shards:
            raise ValueError(
                f"num_hosts={cfg.num_hosts} must divide evenly over "
                f"{plan.shards} host-shard(s)"
            )
        if mesh is None:
            mesh = plan.build_mesh()
        with _tspan(tracker, "shard_state"):
            st = shard_mesh_state(st, mesh, plan)
        with _tspan(tracker, "peek_next_time"):
            quiescent = int(_peek_next_time_ensemble(st)) >= end_time
        if quiescent:
            check_capacity(st)
            return st
        with _tspan(tracker, "put_end_time"):
            end = jnp.asarray(end_time, jnp.int64)
        with _tspan(tracker, "donate_copy"):
            st = st.donatable()

        if launch is None:
            jit_cfg = trace_static_cfg(cfg)
            compiled = _mesh_chunk_fn(
                st, plan, mesh, rounds_per_chunk, model, tables, jit_cfg
            )

            def launch(s):
                return compiled(s, tables, end)

            def compile_chunk(s):
                return compiled.lower(s, tables, end).compile()

        else:
            exe, compile_chunk = launch, None  # compiled in the cache's seam

            def launch(s):
                return exe(s, tables, end)

        def capacity_error(rows, live_st):
            return mesh_capacity_error(rows, live_st, plan)

        return _drive_ensemble(
            launch, st, end_time, max_chunks, on_chunk, pipeline,
            desc=f"{max_chunks}x{rounds_per_chunk} rounds ({plan.describe()})",
            tracker=tracker, on_state=on_state, on_rows=on_rows,
            watchdog_s=watchdog_s, engine=effective_engine(cfg),
            capacity_error=capacity_error, compile_chunk=compile_chunk,
        )


__all__ = [
    "HOST_AXIS",
    "REPLICA_AXIS",
    "MeshPlan",
    "init_mesh_state",
    "lower_mesh_chunk",
    "mesh_capacity_error",
    "mesh_engine_cfg",
    "mesh_state_specs",
    "parse_mesh",
    "replica_seeds",
    "replica_slice",
    "run_mesh_until",
    "shard_mesh_state",
]
