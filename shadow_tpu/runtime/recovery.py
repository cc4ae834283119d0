"""Rollback-and-regrow capacity recovery (docs/robustness.md).

The engine's fixed-slot buffers (event queue, outbox, exchange buckets)
fail loudly on overflow: the per-chunk probe carries the overflow split,
so a CapacityError surfaces at the chunk where the first event was
dropped (engine/round.py). Until now that was fatal. Here it becomes a
recoverable fault:

  1. roll back to the newest VERIFIED clean state — the retained host
     snapshot a StateRetainer committed at a chunk boundary whose probe
     passed the capacity check, or the caller's never-donated entry state
     when no snapshot exists yet;
  2. regrow the saturated buffer along an escalation ladder (x`growth`
     per recovery, targeting the counter the CapacityError names —
     queue vs outbox — with a bounded retry budget);
  3. recompile (capacities are static XLA shapes) and replay from the
     rollback point.

Replay is deterministic: growing a buffer is trajectory-neutral for a
state that never overflowed (engine/state.py grow_state), so the
recovered run is leaf-exact to a run that started with the larger
capacity — the determinism contract survives the fault
(tests/test_robustness.py pins this).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from shadow_tpu.engine.round import (
    CapacityError,
    DeviceLossError,
    WatchdogExpired,
    run_until,
)
from shadow_tpu.engine.state import grow_state, state_from_host, state_to_host
from shadow_tpu.runtime.checkpoint import StateTap
from shadow_tpu.utils.shadow_log import slog


@dataclasses.dataclass
class RecoveryPolicy:
    """The escalation ladder's budget. max_recoveries=0 restores the old
    fail-fast behavior (`--no-recover`)."""

    max_recoveries: int = 4
    growth: int = 2
    snapshot_interval_chunks: int = 32


class StateRetainer:
    """Keeps the newest verified host snapshot as the rollback point.
    Snapshots arrive through StateTap.commit, i.e. only after their own
    chunk's probe passed the capacity check — a retained state can never
    contain a silent drop. Holding it on the host (numpy) keeps it valid
    across buffer donation."""

    def __init__(self, every_chunks: int):
        self.every = max(1, int(every_chunks))
        self.host_state = None
        self._last_chunk = 0

    def due(self, chunk_idx: int) -> bool:
        return chunk_idx - self._last_chunk >= self.every

    def commit(self, host_state) -> None:
        self.host_state = host_state
        self._last_chunk += self.every

    def seed(self, host_state) -> None:
        """Install a rollback point directly (the regrown replay start)."""
        self.host_state = host_state
        self._last_chunk = 0


def grown_cfg(cfg, err: CapacityError, growth: int):
    """The next rung of the escalation ladder: double (x`growth`) the
    capacity of the buffer the CapacityError names. Queue growth also
    widens an explicit deliver_lanes bound (the landing's per-destination
    bound is a queue-side resource — its overflow counts into
    queue.overflow; widening it costs nothing since the landing is a
    pull). When the error carries no split (older callers),
    grow both."""
    q_ov = getattr(err, "queue_overflow", 0)
    o_ov = getattr(err, "outbox_overflow", 0)
    if not q_ov and not o_ov:
        q_ov = o_ov = 1
    changes = {}
    if q_ov:
        changes["queue_capacity"] = cfg.queue_capacity * growth
        if cfg.deliver_lanes > 0:
            changes["deliver_lanes"] = cfg.deliver_lanes * growth
    if o_ov:
        changes["outbox_capacity"] = cfg.outbox_capacity * growth
        if cfg.a2a_capacity > 0:
            # sharded all_to_all bucket overflow counts into the outbox
            # lane; an explicit bucket size must grow too or the replay
            # would deterministically hit the identical bucket overflow
            changes["a2a_capacity"] = cfg.a2a_capacity * growth
    return dataclasses.replace(cfg, **changes)


def run_until_recovering(
    st,
    end_time: int,
    model=None,
    tables=None,
    cfg=None,
    *,
    rounds_per_chunk: int = 64,
    max_chunks: int = 10_000,
    on_chunk=None,
    pipeline: bool = True,
    tracker=None,
    policy: "RecoveryPolicy | None" = None,
    checkpoints=None,
    guard=None,
    runner_factory=None,
    grow_fn=None,
    watchdog_s: float = 0.0,
    replan_fn=None,
):
    """run_until with the recovery loop wrapped around it. Returns
    (final_state, recoveries) where recoveries is the list of recovery
    records ([] for a clean run). `runner_factory(cfg) -> run(st,
    on_state=...) -> SimState` overrides the driver (the sharded
    scheduler passes a ShardedRunner builder); the default is the
    single-device run_until. `checkpoints`/`guard` ride the same StateTap
    (one shared snapshot per due point). `grow_fn` overrides the regrow step
    (default grow_state; the ensemble runner passes the replica-vmapped
    grow_ensemble_state so the whole [R, ...] batch widens together).
    `replan_fn(err)` arms the mesh-degradation rung for DeviceLossError
    (docs/robustness.md "Device loss"): it re-plans the runner onto the
    surviving device set and returns a record dict (grid_from/grid_to)
    — the next runner_factory(cfg) call dispatches on the degraded grid
    and the replay from the retained snapshot stays leaf-exact, the
    watchdog shape with a swapped layout. It returns None (or is None)
    when no rung is left, which makes the loss terminal but
    structured."""
    policy = policy or RecoveryPolicy()
    grow = grow_fn or grow_state

    if runner_factory is None:

        def runner_factory(run_cfg):
            def run(run_st, on_state=None):
                return run_until(
                    run_st,
                    end_time,
                    model,
                    tables,
                    run_cfg,
                    rounds_per_chunk=rounds_per_chunk,
                    max_chunks=max_chunks,
                    on_chunk=on_chunk,
                    pipeline=pipeline,
                    tracker=tracker,
                    on_state=on_state,
                    watchdog_s=watchdog_s,
                )

            return run

    # The retainer is armed LAZILY, after the first CapacityError: the
    # zero-fault path (every healthy run) pays no per-N-chunk full-state
    # fetch and holds no host copy — its rollback point is the caller's
    # never-donated entry state, which already exists for free. Replay
    # attempts DO retain snapshots, so repeated rungs never replay the
    # whole run again.
    retainer = None
    cur_st, cur_cfg = st, cfg
    recoveries: "list[dict]" = []
    while True:
        tap = None
        if retainer is not None or checkpoints is not None or guard is not None:
            tap = StateTap(checkpoints=checkpoints, retainer=retainer, guard=guard)
        if checkpoints is not None:
            # checkpoints written during this attempt must record the
            # attempt's (possibly regrown) cfg knobs for resume
            checkpoints.engine_cfg = cur_cfg
        try:
            final = runner_factory(cur_cfg)(cur_st, on_state=tap)
            return final, recoveries
        except (CapacityError, WatchdogExpired, DeviceLossError) as err:
            from shadow_tpu.runtime import flightrec

            is_loss = isinstance(err, DeviceLossError)
            replanned = None
            if is_loss and len(recoveries) < policy.max_recoveries:
                # re-plan BEFORE the budget check below so a loss with
                # no rung left (replan_fn None / ladder exhausted)
                # takes the terminal path with its degradation history
                replanned = replan_fn(err) if replan_fn is not None else None
            if len(recoveries) >= policy.max_recoveries or (
                is_loss and replanned is None
            ):
                # terminal: surface what the run survived before it died,
                # so a degraded-then-failed run stays visibly degraded
                # (sweep manifests read this off the exception), and
                # write the black-box post-mortem — the recorder's last
                # sample is the failing chunk's probe (_drive records it
                # before raising)
                err.recoveries = list(recoveries)
                flightrec.post_mortem(err, recoveries=len(recoveries))
                raise
            is_watchdog = isinstance(err, WatchdogExpired)
            if retainer is not None and retainer.host_state is not None:
                base_host = retainer.host_state
                try:
                    base = state_from_host(base_host, cur_st)
                except Exception as mat_err:  # noqa: BLE001
                    # materializing the snapshot commits leaves to the
                    # DEFAULT device; if a real loss took that one out,
                    # surface a structured terminal error instead of a
                    # raw runtime crash escaping this handler
                    err.recoveries = list(recoveries)
                    err.args = (
                        f"{err.args[0]} — and the retained snapshot "
                        "cannot be materialized (default device lost? "
                        f"{type(mat_err).__name__}); restart this "
                        "process on the surviving devices and resume "
                        "from the checkpoint directory",
                    )
                    flightrec.post_mortem(err, recoveries=len(recoveries))
                    raise err from mat_err
                # the host snapshot mirrors `base`: read the rollback
                # sim time from numpy, never through the device — a
                # REAL device loss must not crash its own handler
                from_ns = int(np.min(np.asarray(base_host.now)))
            else:
                base_host = None
                base = cur_st  # the caller's never-donated entry state
                # ensemble states carry a [R] `now`: the rollback point
                # is the slowest replica's window (the batch replays
                # together)
                try:
                    from_ns = int(np.min(np.asarray(base.now)))
                except Exception as fetch_err:  # noqa: BLE001
                    # the rollback base itself is unreadable: a real
                    # device loss took the only copy of the
                    # un-snapshotted state with it. No replay is
                    # physically possible — surface a structured,
                    # actionable error instead of a raw runtime crash
                    # escaping this handler.
                    err.recoveries = list(recoveries)
                    err.args = (
                        f"{err.args[0]} — and the rollback state is "
                        "unreadable through the lost device "
                        f"({type(fetch_err).__name__}); recovery needs "
                        "a retained snapshot or --checkpoint-dir",
                    )
                    flightrec.post_mortem(err, recoveries=len(recoveries))
                    raise err from fetch_err
            if is_loss:
                # the device is gone, not the buffers: keep cfg and
                # shapes (the [R, H, ...] state is layout-free), replay
                # from the retained clean snapshot — the next dispatch
                # reshards it onto the degraded grid the replan hook
                # just installed. The watchdog shape with a new layout.
                new_cfg, grown = cur_cfg, base
                record = {
                    "kind": "device-loss",
                    "chunk": err.chunk,
                    "replay_from_ns": from_ns,
                    **replanned,
                }
                if err.device_id is not None:
                    record["device"] = err.device_id
                if getattr(err, "injected", False):
                    record["injected"] = True  # chaos plane, not real loss
                if checkpoints is not None and record.get("grid_to"):
                    # checkpoints written after the reshape must carry
                    # the EFFECTIVE grid as their layout metadata — the
                    # daemon journal reads it off the resume path
                    checkpoints.layout = record["grid_to"]
                slog(
                    "warning", from_ns, "recovery",
                    f"device loss at chunk {err.chunk}"
                    + (f" (device {err.device_id})"
                       if err.device_id is not None else "")
                    + f"; degrading mesh {record.get('grid_from', '?')}"
                    f" -> {record.get('grid_to', '?')} and replaying "
                    f"from sim time {from_ns} ns "
                    f"(recovery {len(recoveries) + 1}/"
                    f"{policy.max_recoveries})",
                )
                recoveries.append(record)
            elif is_watchdog:
                # the dispatch stalled, not the buffers: abandon the
                # in-flight chunk, keep the shapes, re-dispatch from the
                # retained clean snapshot (docs/robustness.md watchdog)
                new_cfg, grown = cur_cfg, base
                record = {
                    "kind": "watchdog",
                    "chunk": err.chunk,
                    "deadline_s": err.deadline_s,
                    "replay_from_ns": from_ns,
                }
                slog(
                    "warning", from_ns, "recovery",
                    f"chunk {err.chunk} dispatch blew the "
                    f"{err.deadline_s:.3g}s watchdog; abandoning the "
                    f"in-flight chunk and re-dispatching from sim time "
                    f"{from_ns} ns "
                    f"(recovery {len(recoveries) + 1}/{policy.max_recoveries})",
                )
                recoveries.append(record)
            else:
                new_cfg = grown_cfg(cur_cfg, err, policy.growth)
                # memory observatory: price the regrown state BEFORE
                # allocating it — the one moment rollback-and-regrow can
                # still warn that the double it is about to apply will
                # not fit the device. Best-effort: pricing works on host
                # snapshots and device states alike, and never blocks
                # the recovery itself.
                headroom: dict = {}
                mem_note = ""
                try:
                    from shadow_tpu.engine.state import fmt_bytes, tree_nbytes
                    from shadow_tpu.runtime import memtrack

                    headroom["bytes_current"] = tree_nbytes(base)
                    headroom["bytes_regrown"] = memtrack.price_regrow(
                        base,
                        queue_capacity=new_cfg.queue_capacity,
                        outbox_capacity=new_cfg.outbox_capacity,
                    )
                    mem_note = (
                        f"; state {fmt_bytes(headroom['bytes_current'])}"
                        f" -> {fmt_bytes(headroom['bytes_regrown'])}"
                    )
                    dm = memtrack.device_memory()
                    limit = (dm or {}).get("bytes_limit")
                    if limit and headroom["bytes_regrown"] > limit:
                        headroom["would_exceed_hbm"] = True
                        mem_note += (
                            f" WOULD EXCEED the {fmt_bytes(limit)} "
                            "device limit"
                        )
                except Exception:  # noqa: BLE001 — pricing is telemetry
                    headroom, mem_note = {}, ""
                grown = grow(
                    base,
                    queue_capacity=new_cfg.queue_capacity,
                    outbox_capacity=new_cfg.outbox_capacity,
                )
                record = {
                    "kind": "capacity",
                    "queue_overflow": getattr(err, "queue_overflow", 0),
                    "outbox_overflow": getattr(err, "outbox_overflow", 0),
                    "queue_capacity": new_cfg.queue_capacity,
                    "outbox_capacity": new_cfg.outbox_capacity,
                    "replay_from_ns": from_ns,
                    **headroom,
                }
                if getattr(err, "injected", False):
                    record["injected"] = True  # chaos plane, not real load
                if getattr(err, "replica", None) is not None:
                    # ensemble runs: name the replica that saturated even
                    # though the whole batch rolls back and regrows together
                    record["replica"] = err.replica
                recoveries.append(record)
                slog(
                    "warning",
                    from_ns,
                    "recovery",
                    f"capacity exhausted (queue_ov={record['queue_overflow']}, "
                    f"outbox_ov={record['outbox_overflow']}); rolling back to "
                    f"sim time {from_ns} ns and regrowing to "
                    f"queue_capacity={new_cfg.queue_capacity}, "
                    f"outbox_capacity={new_cfg.outbox_capacity}{mem_note} "
                    f"(recovery {len(recoveries)}/{policy.max_recoveries})",
                )
            if tracker is not None and hasattr(tracker, "record_recovery"):
                tracker.record_recovery(record)
            # flight recorder: the recovery is an event in the metrics
            # stream AND a survivable-failure black box (overwritten by a
            # later, more terminal dump if the run eventually dies)
            flightrec.record_event("recovery", **record)
            flightrec.post_mortem(
                failure={"kind": f"recovery:{record['kind']}",
                         "recovered": True, **record},
            )
            cur_st, cur_cfg = grown, new_cfg
            if retainer is None:
                retainer = StateRetainer(policy.snapshot_interval_chunks)
            # the replay may overflow again before reaching a fresh
            # snapshot: seed the rollback point with the regrown start so
            # the next rung never replays stale shapes (or the whole run).
            # Watchdog/device-loss rungs keep the shapes, so when the base
            # came from a host snapshot that snapshot IS the seed — no
            # device round-trip (and no read through a lost device).
            if grown is base and base_host is not None:
                retainer.seed(base_host)
            else:
                retainer.seed(state_to_host(grown))
