"""Ensemble plane: R independent replicas of one scenario in one device
program (docs/ensemble.md).

Sound network-simulation experiments need many seeded trials, not one —
the Tor measurement line behind the reference ("Once is Never Enough",
Jansen et al., USENIX Security 2021) showed single-run conclusions are
statistically unsound. The TPU answer is batching: every leaf of the
HBM-resident SimState gains a leading replica axis [R, ...] and the
existing round engines run under ONE jax.vmap — one compile, one kernel
launch per drain iteration, R worlds. Compilation and dispatch overhead
(the dominant cost at small/medium H) amortize across the whole batch.

Independence is exact, not statistical: replica r's PRNG streams come
from rng.replica_keys — row r IS host_keys(seed + r * stride) — and the
seed enters the state nowhere else, so the final [R, ...] state's slice r
is leaf-identical to a single-replica run with that derived seed
(tests/test_ensemble.py pins this on phold and tgen, plain and pump
engines, tracker leaves included, through a checkpoint/resume cycle).

What makes the batch correct under vmap:

  * per-replica done-mask — vmap any-reduces the drain while_loop's
    condition across the batch, so the loop runs until the slowest
    replica finishes; run_round's body re-tests its own predicate and
    takes an identity branch once this replica is done (engine/round.py),
    so finished replicas are frozen no-ops instead of accumulating
    drift in iters_done;
  * per-replica probe — the chunk probe gains a replica dimension
    [R, PROBE_LANES]; quiescence and capacity lanes reduce per replica:
    the driver stops only when EVERY replica is quiescent, records each
    replica's probe row at ITS OWN quiescence chunk (restoring now and
    the idle-round count exactly as the single-replica driver would have
    left them), and a nonzero overflow lane raises a CapacityError that
    names the replica — rollback-and-regrow (runtime/recovery.py) then
    rolls back and regrows the WHOLE batch, keeping every replica on
    the one shared compiled shape;
  * engine support — plain and pump both vmap directly. Ensembles run
    on a single device; replicas x host shards is the mesh plane
    (engine/mesh.py).

The driver below mirrors engine/round.py `_drive` (depth-2 pipelining,
donated chunk states, two-phase checkpoint commit) with the probe logic
widened per replica.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu import equeue, rng
from shadow_tpu.engine.round import (
    PROBE_EXCH_HWM,
    PROBE_LAND_HWM,
    PROBE_LANES,
    PROBE_NEXT_TIME,
    PROBE_NOW,
    PROBE_OUTBOX_HWM,
    PROBE_OUTBOX_OV,
    PROBE_OVERFLOW,
    PROBE_QUEUE_HWM,
    PROBE_QUEUE_OV,
    PROBE_ROUNDS_IDLE,
    PROBE_ROUNDS_LIVE,
    PROBE_WIN_NS,
    ChunkProbe,
    RunInterrupted,
    WatchdogExpired,
    _capacity_error,
    _fetch_probe,
    _launch_chunk0,
    _tspan,
    bootstrap,
    check_capacity,
    device_loss_from,
    effective_engine,
    run_rounds_scan,
    state_probe,
    validate_runahead,
)
from shadow_tpu import scopes
from shadow_tpu.engine.state import (
    EngineConfig,
    SimState,
    grow_state,
    init_state,
    state_to_host,
    trace_static_cfg,
)

# probe lanes that aggregate across replicas as sums; the rest are
# extrema (PROBE_NEXT_TIME/PROBE_NOW min, high-water marks / round
# counters / window-width sums max — see _aggregate_probe)
_SUM_LANES = frozenset(range(PROBE_LANES)) - {
    PROBE_NEXT_TIME,
    PROBE_NOW,
    PROBE_QUEUE_HWM,
    PROBE_OUTBOX_HWM,
    PROBE_EXCH_HWM,
    PROBE_LAND_HWM,
    PROBE_ROUNDS_LIVE,
    PROBE_ROUNDS_IDLE,
    PROBE_WIN_NS,
}


def ensemble_engine_cfg(cfg: EngineConfig) -> EngineConfig:
    """The engine config an ensemble actually traces: cfg.ensemble arms
    the per-replica done-mask in run_round (semantics-neutral; unbatched
    runs skip its cost — engine/state.py). Nothing else changes: the
    engine, pump_k and exchange are the ones asked for."""
    return dataclasses.replace(cfg, ensemble=True)


def replica_seeds(cfg: EngineConfig, num_replicas: int, stride: int = 1):
    """The derived seed of each replica — replica r of an ensemble is
    leaf-identical to a single run with this seed."""
    return [cfg.seed + r * stride for r in range(num_replicas)]


def init_ensemble_state(
    cfg: EngineConfig,
    model,
    num_replicas: int,
    seed_stride: int = 1,
    tx_bytes_per_interval=None,
    rx_bytes_per_interval=None,
) -> SimState:
    """The bootstrapped [R, ...] initial state: R single-replica states
    built EXACTLY as init_state+bootstrap would build them for the
    derived seeds (the independence contract is by construction, not by
    re-derivation), stacked along a new leading replica axis."""
    if num_replicas < 1:
        raise ValueError("num_replicas must be >= 1")
    # the one seam where a replica's identity enters the state: row r of
    # rng.replica_keys IS host_keys(seed + r * stride), i.e. the key set
    # init_state builds for the derived seed (tests/test_rng.py pins the
    # grid collision-free)
    keys = rng.replica_keys(cfg.seed, num_replicas, cfg.num_hosts, seed_stride)
    states = []
    for r, seed in enumerate(replica_seeds(cfg, num_replicas, seed_stride)):
        rcfg = dataclasses.replace(cfg, seed=seed)
        st = init_state(
            rcfg,
            model.init(),
            tx_bytes_per_interval=tx_bytes_per_interval,
            rx_bytes_per_interval=rx_bytes_per_interval,
        )
        states.append(bootstrap(st.replace(rng_key=keys[r]), model, rcfg))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def num_replicas(st: SimState) -> int:
    """Replica count of an ensemble state (st.now is [R] there)."""
    if st.now.ndim != 1:
        raise ValueError("not an ensemble state: expected now with shape [R]")
    return st.now.shape[0]


def replica_slice(st: SimState, r: int) -> SimState:
    """Replica r's single-world SimState view (leaf slices, no copy)."""
    return jax.tree.map(lambda l: l[r], st)


def grow_ensemble_state(
    st: SimState,
    queue_capacity: "int | None" = None,
    outbox_capacity: "int | None" = None,
) -> SimState:
    """grow_state vmapped over the replica axis: every replica's
    fixed-slot buffers widen together, keeping the batch on one compiled
    shape. Trajectory-neutral per replica for the same reason the
    single-world grow is (engine/state.py)."""
    return jax.vmap(
        lambda s: grow_state(
            s, queue_capacity=queue_capacity, outbox_capacity=outbox_capacity
        )
    )(st)


@scopes.keyed
def _run_ensemble_chunk(st, end, num_rounds, model, tables, cfg):
    def one(s):
        s = run_rounds_scan(s, end, num_rounds, model, tables, cfg)
        with jax.named_scope(scopes.PROBE):
            return s, state_probe(s)

    return jax.vmap(one)(st)


# same cache/donation discipline as engine/round.py _run_chunk_jit: the
# [R, ...] state is donated chunk-to-chunk; drivers feed it only states
# they own (SimState.donatable()).
_run_ensemble_chunk_jit = jax.jit(
    _run_ensemble_chunk, static_argnums=(2, 3, 5), donate_argnums=(0,)
)


def lower_ensemble_chunk(st, end, rounds_per_chunk, model, tables, cfg):
    """The compiled-executable reuse seam (runtime/compile_cache.py):
    AOT-lower the ensemble chunk for this state's shapes. The returned
    Lowered's .compile() yields an executable called as
    `exe(st, end, tables)` (statics baked in, input state donated) that
    `run_ensemble_until` accepts via its `launch` override — so a sweep can pay ONE compile
    for N same-shape jobs and hold the executable across batches. The
    static cfg is canonicalized through trace_static_cfg (the seed never
    enters the traced program), so worlds differing only in seed lower
    to the identical key."""
    cfg = trace_static_cfg(ensemble_engine_cfg(cfg))
    return jax.jit(
        _run_ensemble_chunk, static_argnums=(2, 3, 5), donate_argnums=(0,)
    ).lower(st, jnp.asarray(end, jnp.int64), rounds_per_chunk, model, tables, cfg)


def _aggregate_probe(rows: np.ndarray) -> ChunkProbe:
    """Collapse the [R, PROBE_LANES] probe to one ChunkProbe for
    progress/heartbeat/checkpoint-cadence consumers: counter lanes sum
    across replicas, next_time/now take the MIN (quiescence and progress
    follow the slowest replica — `now` reaches end_time exactly when the
    whole batch is done), high-water/round lanes take the max."""
    vals = []
    for lane in range(PROBE_LANES):
        col = rows[:, lane]
        if lane in (PROBE_NEXT_TIME, PROBE_NOW):
            vals.append(int(col.min()))
        elif lane in _SUM_LANES:
            vals.append(int(col.sum()))
        else:
            vals.append(int(col.max()))
    return ChunkProbe(*vals)


def _replica_capacity_error(rows: np.ndarray) -> "Exception":
    """A CapacityError for the first replica whose overflow lane fired,
    carrying the replica index (err.replica) so recovery reports and CLI
    messages can name the failing world."""
    bad = np.nonzero(rows[:, PROBE_OVERFLOW] > 0)[0]
    r = int(bad[0])
    row = rows[r]
    err = _capacity_error(
        int(row[PROBE_OVERFLOW]),
        queue_ov=int(row[PROBE_QUEUE_OV]),
        outbox_ov=int(row[PROBE_OUTBOX_OV]),
        queue_hwm=int(row[PROBE_QUEUE_HWM]),
        outbox_hwm=int(row[PROBE_OUTBOX_HWM]),
    )
    err.replica = r
    detail = f"replica {r} of {rows.shape[0]}"
    if bad.size > 1:
        detail += f" (+{bad.size - 1} more replica(s) saturated)"
    err.args = (f"{err.args[0]} [{detail}]",)
    return err


def _patch_snapshot(host: SimState, final_rows: "dict[int, np.ndarray]") -> SimState:
    """Rewrite a host (state_to_host) snapshot's `now` and idle-round count
    for every replica already recorded quiescent, to the values of its
    OWN quiescence chunk's probe row — the values _finish will restore at
    the end of the run. A replica that quiesces early keeps taking idle
    rounds on device while slower replicas drain (touching exactly these
    leaves), so an unpatched mid-run checkpoint would bake those extra
    idle rounds in and a resumed run could never end leaf-exact vs the
    uninterrupted one (tests/test_ensemble.py pins the straddling case).
    Replicas not (yet) in final_rows — still live, or quiescing inside
    the in-flight chunk the snapshot was taken from — are already at
    their true values and stay untouched."""
    if not final_rows:
        return host
    now = np.array(host.now, copy=True)
    ri = np.array(host.tracker.rounds_idle, copy=True)
    for r, row in final_rows.items():
        now[r] = row[PROBE_NOW]
        ri[r] = row[PROBE_ROUNDS_IDLE]
    return host.replace(now=now, tracker=host.tracker.replace(rounds_idle=ri))


def _finish(out: SimState, final_rows: "dict[int, np.ndarray]") -> SimState:
    """Restore each replica's `now` and idle-round count to the values its
    probe carried at ITS OWN quiescence chunk. A replica that quiesced
    early keeps taking idle rounds while slower replicas drain (and under
    pipelining one extra in-flight chunk runs after the last replica
    quiesces); those idle rounds touch ONLY now and tracker.rounds_idle —
    leaves the probe carries — so writing the recorded rows
    back makes every slice leaf-exact to the single-replica driver, which
    stops at that replica's own quiescence chunk."""
    r = num_replicas(out)
    now = jnp.asarray(
        [int(final_rows[i][PROBE_NOW]) for i in range(r)], out.now.dtype
    )
    ri = jnp.asarray(
        [int(final_rows[i][PROBE_ROUNDS_IDLE]) for i in range(r)],
        out.tracker.rounds_idle.dtype,
    )
    return out.replace(now=now, tracker=out.tracker.replace(rounds_idle=ri))


def _drive_ensemble(
    launch, st, end_time, max_chunks, on_chunk, pipeline, desc,
    tracker=None, on_state=None, on_rows=None,
    watchdog_s: float = 0.0, engine: str = "pump",
    capacity_error=None, compile_chunk=None,
):
    """The ensemble twin of engine/round.py `_drive`: same depth-2
    pipeline and donation discipline, same two-phase checkpoint commit,
    but the probe is [R, PROBE_LANES] and every termination decision
    reduces per replica. Per-host heartbeats are not emitted here (the
    per-host tensors are [R, H]; the manager disables them for ensemble
    runs — docs/ensemble.md). `on_rows(rows)` receives the raw
    [R, PROBE_LANES] numpy probe each chunk, BEFORE aggregation — the
    sweep scheduler's per-job progress stream (one row per job, zero
    extra device syncs; runtime/sweep.py). `watchdog_s`/`engine` and
    the chaos capacity/stall/compile hooks mirror engine/round.py
    `_drive` — the degradation ladder covers both drivers — as does
    `compile_chunk` (_launch_chunk0).
    `capacity_error(rows, live_state)` overrides how an overflow
    becomes an exception (the 2-D mesh driver names (replica, shard)
    coordinates from the live state — engine/mesh.py); the default
    names the replica from the probe rows alone."""
    from shadow_tpu.runtime import chaos, flightrec

    R = num_replicas(st)
    # the chunk-launch seam for the `device-loss` chaos fault
    # (docs/robustness.md "Device loss"): each dispatch consults the
    # plan at its launch ordinal BEFORE the chunk goes out, so an
    # injected loss lands exactly where a real device failure would
    # first be provoked — replayable because the ordinal sequence is
    # deterministic. No plan installed = one global None check.
    real_launch = launch
    launch_ord = [0]

    def launch(s):
        at = launch_ord[0]
        launch_ord[0] += 1
        if chaos.active() is not None:
            # a device-loss fault's `target` names the LOST device id,
            # and the launch site advertises the devices THIS state
            # actually occupies — losing an idle device cannot touch
            # the run, so target=7 never fires against a grid on 0..3
            spec = chaos.fire(
                "device-loss", at=at,
                tags=tuple(str(d.id) for d in s.now.devices()),
            )
            if spec is not None:
                raise chaos.injected_device_loss(at, spec)
        return real_launch(s)

    # Replicas quiescent at ENTRY (a resumed checkpoint whose batch was
    # only partially done) are pre-recorded from the entry state itself:
    # their snapshot was patched to their own quiescence values
    # (_patch_snapshot), so the entry state — not any later chunk's
    # probe, which would re-accumulate idle rounds — carries the exact
    # leaves _finish must restore.
    flightrec.begin_segment()  # mirrors engine/round.py _drive
    scopes.last_probes = None  # this plane keeps none; never another entry's
    with _tspan(tracker, "entry_probe"):
        entry_rows = np.asarray(jax.device_get(_peek_probe_ensemble(st)))
    final_rows: "dict[int, np.ndarray]" = {
        r: entry_rows[r]
        for r in range(R)
        if int(entry_rows[r, PROBE_NEXT_TIME]) >= end_time
    }
    pend_st, pend_probe = _launch_chunk0(
        launch, st, tracker, engine, compile_chunk
    )
    launched = 1
    fetched = 0
    pending_snap = None
    while True:
        nxt = None
        if pipeline and launched < max_chunks:
            with _tspan(tracker, "chunk_launch", chunk=launched):
                nxt = launch(pend_st)
            launched += 1
        with _tspan(tracker, "probe_fetch", chunk=fetched):
            try:
                rows = np.asarray(
                    _fetch_probe(pend_probe, watchdog_s, fetched)
                )
            except (WatchdogExpired, RunInterrupted, KeyboardInterrupt):
                raise
            except Exception as err:
                # real device/runtime failures surface HERE — the probe
                # fetch is the first host<->device sync after a launch —
                # as jaxlib XlaRuntimeErrors; translate them into the
                # typed DeviceLossError the mesh degradation rungs act
                # on (runtime/recovery.py). Anything else (engine bugs,
                # donation misuse) propagates as what it is.
                loss = device_loss_from(err, fetched)
                if loss is None:
                    raise
                raise loss from err
        fetched += 1
        with _tspan(tracker, "probe_decide", chunk=fetched - 1):
            # the flight-recorder seam mirrors engine/round.py `_drive`:
            # aggregate and record BEFORE the capacity checks so a
            # post-mortem's last sample is the failing chunk's probe
            probe = _aggregate_probe(rows)
            flightrec.observe_probe(probe, chunk=fetched - 1)
            injected = chaos.fire("capacity", at=fetched - 1)
            if injected is not None:
                raise chaos.injected_capacity_error(fetched - 1, injected)
            if int(rows[:, PROBE_OVERFLOW].sum()):
                from shadow_tpu.engine.round import attach_capacity_bytes

                live = nxt[0] if nxt is not None else pend_st
                if capacity_error is not None:
                    err = capacity_error(rows, live)
                else:
                    err = _replica_capacity_error(rows)
                attach_capacity_bytes(err, live)
                raise err
            if on_rows is not None:
                on_rows(rows)
            if on_chunk is not None:
                on_chunk(probe)
            for r in range(R):
                if r not in final_rows and int(rows[r, PROBE_NEXT_TIME]) >= end_time:
                    final_rows[r] = rows[r]
            if on_state is not None:
                if pending_snap is not None and pending_snap[0] <= fetched - 1:
                    on_state.commit(pending_snap[1])
                    pending_snap = None
                interrupted = on_state.interrupted()
                if (
                    pending_snap is None and on_state.due(probe, fetched - 1)
                ) or interrupted:
                    src = nxt[0] if nxt is not None else pend_st
                    with _tspan(tracker, "state_snapshot", chunk=launched - 1):
                        host = _patch_snapshot(state_to_host(src), final_rows)
                    if nxt is None:
                        on_state.commit(host)
                    elif interrupted:
                        if (
                            int(host.queue.overflow.sum()) == 0
                            and int(host.outbox.overflow.sum()) == 0
                        ):
                            on_state.commit(host)
                    else:
                        pending_snap = (launched - 1, host)
                if interrupted:
                    raise RunInterrupted(
                        f"run interrupted at sim time {probe.now} ns"
                    )
        if len(final_rows) == R:
            out = nxt[0] if nxt is not None else pend_st
            with _tspan(tracker, "quiescent_restore"):
                return _finish(out, final_rows)
        if nxt is None:
            if launched < max_chunks:
                with _tspan(tracker, "chunk_launch", chunk=launched):
                    nxt = launch(pend_st)
                launched += 1
            else:
                raise RuntimeError(
                    f"simulation did not reach end_time={end_time} within "
                    f"{desc}; raise max_chunks/rounds_per_chunk"
                )
        pend_st, pend_probe = nxt


@jax.jit
def _peek_next_time_ensemble(st: SimState) -> jax.Array:
    return jnp.min(equeue.next_time(st.queue))


@jax.jit
def _peek_probe_ensemble(st: SimState) -> jax.Array:
    """[R, PROBE_LANES] probe of a state at rest (the entry-prefill read;
    one tiny fetch per run, never per chunk)."""
    return jax.vmap(state_probe)(st)


def run_ensemble_until(
    st: SimState,
    end_time: int,
    model,
    tables,
    cfg: EngineConfig,
    rounds_per_chunk: int = 64,
    max_chunks: int = 10_000,
    on_chunk=None,
    pipeline: bool = True,
    tracker=None,
    on_state=None,
    on_rows=None,
    launch=None,
    watchdog_s: float = 0.0,
) -> SimState:
    """Host-side ensemble driver: chunked vmapped device scans until no
    replica has work left before end_time. `st` is an init_ensemble_state
    [R, ...] pytree; the returned state has the same shape. `cfg` must be
    the per-replica (single-world) config — it is resolved through
    ensemble_engine_cfg. Everything else matches run_until: depth-2 pipeline,
    donated chunk states, ChunkProbe on_chunk callbacks (aggregated
    across replicas), tracker spans, on_state checkpoint taps.
    `on_rows(rows)` streams the raw per-replica probe (see
    _drive_ensemble). `launch` overrides the chunk dispatch with a
    pre-compiled executable: a callable `exe(st, end, tables) ->
    (st, probe)` (lower_ensemble_chunk + .compile(), via the sweep
    scheduler's compile cache) — it must have been lowered for exactly
    this state shape and a trace_static_cfg-canonicalized version of
    this cfg."""
    with _tspan(tracker, "run"):
        cfg = ensemble_engine_cfg(cfg)
        with _tspan(tracker, "validate_runahead"):
            validate_runahead(cfg, tables)
        num_replicas(st)  # loud on a non-ensemble state
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
            # seed is canonicalized out of the static cfg so the process-wide
            # jit cache, like the AOT path, reuses one executable across
            # same-shape worlds that differ only in seed
            jit_cfg = trace_static_cfg(cfg)

            chunk_args = (end, rounds_per_chunk, model, tables, jit_cfg)

            def launch(s):
                return _run_ensemble_chunk_jit(s, *chunk_args)

            def compile_chunk(s):
                return _run_ensemble_chunk_jit.lower(s, *chunk_args).compile()

        else:
            exe, compile_chunk = launch, None  # compiled in the cache's seam

            def launch(s):
                return exe(s, end, tables)

        return _drive_ensemble(
            launch, st, end_time, max_chunks, on_chunk, pipeline,
            desc=f"{max_chunks}x{rounds_per_chunk} rounds",
            tracker=tracker, on_state=on_state, on_rows=on_rows,
            watchdog_s=watchdog_s, engine=effective_engine(ensemble_engine_cfg(cfg)),
            compile_chunk=compile_chunk,
        )
