"""Chaos plane: deterministic fault injection and the degradation
ladder it validates (docs/robustness.md "Chaos testing").

The repo's production story — checkpoint/restore, rollback-and-regrow,
worker supervision, the sweep service — claims to survive a catalog of
faults, but a claim that is never exercised is aspirational (Basiri et
al., *Chaos Engineering*, IEEE Software 2016). This module makes every
claimed-survivable seam injectable, **deterministically**:

  * a `FaultPlan` is built from `--chaos-seed` / the `chaos.*` config
    section and holds a list of `FaultSpec`s (fault kind, trigger site,
    optional target, budget). Trigger sites left as ``at: auto`` are
    drawn from the plan's own PRNG stream (seeded by
    ``(chaos.seed, kind, ordinal)``), so the same seed + config always
    yields the same injection schedule — a chaos run is replayable
    bit-for-bit, which is what lets the chaos matrix assert
    "leaf-identical to the fault-free run" rather than "usually fine";
  * the runtime's seams consult the installed plan through `fire()`:
    the chunk-dispatch drivers (capacity / stall / compile faults,
    engine/round.py and engine/ensemble.py), the checkpoint writer
    (corrupt / truncate, runtime/checkpoint.py), the hybrid window loop
    (worker kill / hang, runtime/hybrid.py), and the sweep scheduler
    (preemption storms, runtime/sweep.py). With no plan installed every
    hook is a single module-global ``is None`` check — the zero-chaos
    path costs nothing.

Fault kinds (the injection catalog):

  ``capacity``      raise a CapacityError at chunk `at` — exercises
                    rollback-and-regrow and the sweep's poison-job
                    quarantine (`target` = job name restricts it to
                    batches carrying that job).
  ``stall``         sleep `stall_s` seconds in the dispatch path at
                    chunk `at` — exercises the chunk-dispatch watchdog
                    (`experimental.chunk_watchdog_s`).
  ``compile``       fail the chunk compile for the engine named by
                    `target` (or whichever tries first) — exercises the
                    engine fallback ladder (pump → plain).
  ``ckpt-corrupt``  flip bytes inside checkpoint file number `at` after
                    it is written — exercises the sha-256 integrity
                    check and `latest_path`'s fall-back-to-valid.
  ``ckpt-truncate`` truncate checkpoint file number `at` — exercises
                    the truncation → CheckpointError path.
  ``worker-kill``   SIGKILL hybrid worker `target` before window
                    broadcast `at` — exercises respawn-and-replay.
  ``worker-hang``   SIGSTOP hybrid worker `target` (the bounded RPC
                    recv times out, the worker is killed + respawned).
  ``preempt``       arm the sweep scheduler's preemption guard at batch
                    chunk `at` even with no higher-priority arrival —
                    a preemption storm is several of these.
  ``daemon-kill``   SIGKILL the serve daemon (runtime/daemon.py) at
                    site ordinal `at`; `target` picks the site class
                    (``admit`` / ``batch-start`` / ``chunk`` /
                    ``checkpoint``, no target = first match anywhere) —
                    exercises the crash-safe journal + checkpoint
                    replay: restart on the same spool loses zero jobs.
  ``spool-corrupt`` flip bytes inside spool journal record number `at`
                    after its atomic write — exercises the journal's
                    per-record sha-256 check and the accepted-spec
                    re-admission fallback.
  ``cache-corrupt`` flip bytes inside persistent compile-cache entry
                    number `at` after its atomic write — exercises the
                    cache's integrity check: a damaged entry degrades
                    to a recompile warning, never a failure.
  ``device-loss``   raise a DeviceLossError at chunk-launch ordinal
                    `at` (`target` = the lost jax device id, optional)
                    — exercises the elastic-mesh degradation rungs:
                    rollback to the retained snapshot, re-plan onto the
                    surviving device set (MeshPlan.degraded), recompile,
                    replay leaf-exact (docs/robustness.md "Device
                    loss"). Terminal-but-structured outside the mesh
                    plane.

Opposite the injections sits the degradation ladder the chaos matrix
validates (tests/test_chaos.py): the watchdog re-dispatch
(runtime/recovery.py, kind="watchdog" recovery records), the engine
fallback ladder (`run_with_engine_ladder`, used by TpuScheduler and
EnsembleRunner), checkpoint fall-back-to-valid, and the sweep's
split → retry-with-backoff → quarantine path. Every rung ends in either
a completed run leaf-identical to the fault-free one or a structured,
named failure — never a hang or a bare traceback.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random

from shadow_tpu.config.options import FAULT_KINDS
from shadow_tpu.utils.shadow_log import slog

# default range for `at: auto` trigger draws (chunk/window ordinals):
# early chunks, where every run path is still live
AUTO_AT_MAX = 4

# caps for persistent (count: -1) faults, which fire once per chunk: the
# fired record list in sim-stats/sweep-manifest and the warning log must
# stay O(1) in run length, not grow with every chunk of a 100k-chunk run
MAX_FIRED_RECORDS = 100
MAX_FIRED_LOGS = 5


@dataclasses.dataclass
class FaultSpec:
    """One injectable fault. `at` is the site ordinal the fault fires at
    (chunk index, checkpoint number, window broadcast number — whatever
    the seam counts): an int pins it, "auto" draws it from the plan's
    PRNG stream, None fires at the first opportunity. `target`
    restricts firing to sites tagged with that string (an engine name,
    a worker index, a sweep job name); None matches any site. `count`
    bounds total firings (-1 = persistent: fires every time it
    matches)."""

    kind: str
    at: "int | str | None" = None
    target: "str | None" = None
    count: int = 1
    stall_s: float = 1.0  # kind="stall" only: injected dispatch delay

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown chaos fault kind {self.kind!r} "
                f"(expected one of {sorted(FAULT_KINDS)})"
            )
        if self.kind == "compile" and self.at is not None:
            # the compile seams fire at the first matching compile (there
            # is no chunk ordinal yet when chunk 0 compiles) — a sited
            # compile fault would silently never fire
            raise ValueError(
                "compile faults fire at the first matching compile and "
                "take no @AT site; use target=<engine> to pick the engine"
            )
        if self.at is not None and self.at != "auto":
            self.at = int(self.at)
            if self.at < 0:
                raise ValueError(
                    "chaos fault at must be >= 0 (a site ordinal) or 'auto'"
                )
        self.count = int(self.count)
        if self.count == 0 or self.count < -1:
            raise ValueError("chaos fault count must be >= 1 or -1 (persistent)")
        self.stall_s = float(self.stall_s)
        if self.stall_s < 0:
            raise ValueError("chaos fault stall_s must be >= 0 seconds")


class FaultPlan:
    """A deterministic injection schedule. Reproducibility contract:
    two plans built from the same (seed, faults) fire at identical
    sites in identical order — `at: auto` draws come from
    ``random.Random((seed, kind, ordinal))``, never from wall clock or
    global RNG state — so a chaos run can be replayed exactly
    (`reset()` restores the budgets for the replay)."""

    def __init__(self, seed: int = 0, faults=(), at_max: int = AUTO_AT_MAX):
        self.seed = int(seed)
        self.at_max = int(at_max)
        self.faults: "list[FaultSpec]" = []
        for i, f in enumerate(faults):
            spec = f if isinstance(f, FaultSpec) else FaultSpec(**dict(f))
            if spec.at == "auto":
                draw = random.Random(f"{self.seed}:{spec.kind}:{i}")
                spec = dataclasses.replace(spec, at=draw.randrange(self.at_max))
            self.faults.append(spec)
        self._budget = [s.count for s in self.faults]
        self._fires = [0 for _ in self.faults]
        self.fired: "list[dict]" = []

    def reset(self) -> None:
        """Restore every fault's budget (replay the same schedule)."""
        self._budget = [s.count for s in self.faults]
        self._fires = [0 for _ in self.faults]
        self.fired = []

    def should_fire(self, kind: str, at=None, tags=()) -> "FaultSpec | None":
        for i, spec in enumerate(self.faults):
            if spec.kind != kind or self._budget[i] == 0:
                continue
            if spec.target is not None and spec.target not in tags:
                continue
            if spec.at is not None and at != spec.at:
                continue
            if self._budget[i] > 0:
                self._budget[i] -= 1
            self._fires[i] += 1
            if len(self.fired) < MAX_FIRED_RECORDS:
                rec = {"kind": kind, "at": at}
                if spec.target is not None:
                    rec["target"] = spec.target
                self.fired.append(rec)
            if self._fires[i] <= MAX_FIRED_LOGS:
                slog("warning", 0, "chaos",
                     f"injecting fault: {kind} at site {at}"
                     + (f" (target {spec.target})" if spec.target else "")
                     + (" — further firings of this fault logged silently"
                        if self._fires[i] == MAX_FIRED_LOGS else ""))
            return spec
        return None

    def report(self) -> dict:
        """The `chaos` block of sim-stats.json: what actually fired —
        a degraded run must be visibly degraded, never silently so.
        `fired` holds the first MAX_FIRED_RECORDS records;
        `fired_total` is the true count (a persistent fault firing every
        chunk must not grow the stats file with run length)."""
        rep = {
            "seed": self.seed,
            "planned": len(self.faults),
            "fired": list(self.fired),
        }
        total = sum(self._fires)
        if total > len(self.fired):
            rep["fired_total"] = total
        return rep


# --- installation -------------------------------------------------------
# One plan per process, installed around a run by the CLI (or a test's
# `installed()` context). Seams consult it through fire(); ambient tags
# (scoped_tags) let a seam that does not know its logical identity —
# the ensemble driver has replica rows, not sweep job names — still be
# targeted by name.

_PLAN: "FaultPlan | None" = None
_TAGS: tuple = ()


def install(plan: "FaultPlan | None") -> None:
    global _PLAN
    _PLAN = plan


def uninstall() -> None:
    install(None)


def active() -> "FaultPlan | None":
    return _PLAN


@contextlib.contextmanager
def installed(plan: "FaultPlan | None"):
    prev = _PLAN
    install(plan)
    try:
        yield plan
    finally:
        install(prev)


@contextlib.contextmanager
def scoped_tags(*tags: str):
    """Add ambient site tags (e.g. the running batch's sweep job names)
    for the duration of the block; fault targets match against them."""
    global _TAGS
    prev = _TAGS
    _TAGS = prev + tuple(tags)
    try:
        yield
    finally:
        _TAGS = prev


def fire(kind: str, at=None, tags=()) -> "FaultSpec | None":
    """The one hook every seam calls: returns the matching FaultSpec
    (consuming one unit of its budget) or None. No plan installed =
    one global read, nothing else."""
    if _PLAN is None:
        return None
    return _PLAN.should_fire(kind, at=at, tags=tuple(tags) + _TAGS)


def plan_from_config(chaos_cfg) -> "FaultPlan | None":
    """FaultPlan from a ChaosOptions section (config/options.py), or
    None when it declares no faults (the zero-chaos fast path)."""
    if chaos_cfg is None or not chaos_cfg.faults:
        return None
    return FaultPlan(seed=chaos_cfg.seed, faults=chaos_cfg.faults)


def parse_fault_arg(arg: str) -> dict:
    """Parse one --chaos-fault flag value into a fault dict:
    ``KIND[@AT][:key=val...]`` — e.g. ``capacity@2``,
    ``stall@1:stall_s=0.5``, ``capacity:target=ph-s3:count=-1``.
    AT is an int or ``auto``."""
    head, *opts = arg.split(":")
    kind, _, at_s = head.partition("@")
    fault: dict = {"kind": kind.strip()}
    if at_s:
        fault["at"] = at_s if at_s == "auto" else int(at_s)
    for opt in opts:
        key, sep, val = opt.partition("=")
        if not sep:
            raise ValueError(f"--chaos-fault option {opt!r} is not key=val")
        key = key.strip()
        if key == "count":
            fault["count"] = int(val)
        elif key == "stall_s":
            fault["stall_s"] = float(val)
        elif key == "target":
            fault["target"] = val
        elif key == "at":
            fault["at"] = val if val == "auto" else int(val)
        else:
            raise ValueError(f"unknown --chaos-fault option {key!r}")
    FaultSpec(**fault)  # validate loudly at parse time
    return fault


def injected_capacity_error(at, spec: "FaultSpec | None" = None):
    """The CapacityError a `capacity` fault raises: structurally
    identical to a real overflow (recovery targets the queue), tagged
    `injected` so reports can distinguish simulated faults from real
    saturation."""
    from shadow_tpu.engine.round import CapacityError

    detail = f", target {spec.target}" if spec is not None and spec.target else ""
    err = CapacityError(
        f"injected fault: event capacity exhausted at chunk {at} "
        f"(chaos plane{detail})"
    )
    err.queue_overflow = 1
    err.injected = True
    return err


def injected_device_loss(at, spec: "FaultSpec | None" = None):
    """The DeviceLossError a `device-loss` fault raises at the
    chunk-launch seam (engine/ensemble.py _drive_ensemble):
    structurally identical to a real XLA runtime failure's translation
    (engine/round.py device_loss_from), tagged `injected`, carrying the
    lost device id when the fault's `target` names one."""
    from shadow_tpu.engine.round import DeviceLossError

    device_id = None
    if spec is not None and spec.target is not None:
        try:
            device_id = int(spec.target)
        except ValueError:
            device_id = None
    return DeviceLossError(at, device_id=device_id)


@contextlib.contextmanager
def compile_seam(engine: str):
    """The one compile-failure seam behind every engine-compile site —
    the ahead-of-time compile before _drive's chunk-0 launch
    (engine/round.py _launch_chunk0) and the runners' AOT cache fill
    (runtime/ensemble.py / runtime/mesh.py _launch_for): fires an
    injected `compile` fault targeting `engine`, passes driver-level
    control exceptions through untouched, and wraps anything else in a
    typed EngineCompileError the fallback ladder can act on. Only
    tracing, lowering and compiling happen inside it — never the
    launch — so an error the device raises when the program runs (out of
    memory, a JaxRuntimeError of a lost device) is never relabelled a
    compile failure."""
    from shadow_tpu.engine.round import (
        CapacityError,
        DeviceLossError,
        EngineCompileError,
        RunInterrupted,
        WatchdogExpired,
    )

    try:
        if fire("compile", tags=(engine,)) is not None:
            raise RuntimeError(
                f"injected fault: {engine} engine compile failed (chaos plane)"
            )
        yield
    except (CapacityError, RunInterrupted, WatchdogExpired,
            EngineCompileError, DeviceLossError, KeyboardInterrupt):
        raise
    except Exception as e:
        raise EngineCompileError(engine, e) from e


def damage_file(path: str, truncate: bool) -> None:
    """The `ckpt-corrupt` / `ckpt-truncate` payload: truncate the file
    to half its size, or overwrite a span in the middle with a marker
    pattern. Applied AFTER the atomic write completes — the fault
    simulates bit-rot/partial storage loss on a checkpoint that was
    fully committed, which is exactly what the sha-256 digest and
    `latest_path`'s fall-back-to-valid defend against."""
    import os

    size = os.path.getsize(path)
    if truncate:
        os.truncate(path, max(size // 2, 1))
        return
    with open(path, "r+b") as f:
        f.seek(max(size // 2 - 16, 0))
        f.write(b"\xde\xad\xbe\xef" * 8)


# --- engine fallback ladder --------------------------------------------
# pump → plain. Sound as a *degradation* ladder because the two engines
# are leaf-exact bit-identical on every model (tests/test_pump.py):
# falling the rung changes wall-clock, never a single result leaf.


def next_engine_cfg(cfg):
    """The next rung down from cfg's effective engine, or None at the
    bottom. "auto" resolves to what it would actually run (pump when
    pump_k > 0, else plain)."""
    import dataclasses as _dc

    from shadow_tpu.engine.round import effective_engine

    if effective_engine(cfg) == "pump":
        return _dc.replace(cfg, engine="plain")
    return None


def run_with_engine_ladder(cfg, attempt, on_fallback=None, fail_fast=False):
    """Run `attempt(cfg)`, downgrading the engine one rung per
    EngineCompileError until plain fails too (then the original error
    propagates — a structured, named failure). Returns
    (attempt result, fallback records). Each record lands in
    sim-stats.json's `degraded` section, so a degraded run is visibly
    degraded, never silently slower. With
    `fail_fast` (--no-recover: the runners pass `recovery is None`) the
    first EngineCompileError propagates and no rung is walked."""
    from shadow_tpu.engine.round import EngineCompileError

    fallbacks: "list[dict]" = []
    while True:
        try:
            return attempt(cfg), fallbacks
        except EngineCompileError as err:
            nxt = None if fail_fast else next_engine_cfg(cfg)
            if nxt is None:
                raise
            rec = {
                "from": err.engine or cfg.engine,
                "to": nxt.engine,
                "reason": str(err.__cause__ or err)[:300],
            }
            fallbacks.append(rec)
            slog(
                "warning", 0, "engine",
                f"{rec['from']} engine failed to compile "
                f"({rec['reason']}); falling back to {rec['to']} "
                "(bit-identical results, possibly slower)",
            )
            # flight recorder (runtime/flightrec.py): a fallback is a
            # survivable degradation — event in the metrics stream plus
            # a black-box snapshot of the moment the ladder acted
            from shadow_tpu.runtime import flightrec

            flightrec.record_event("engine_fallback", **rec)
            flightrec.post_mortem(
                failure={"kind": "engine_fallback", "recovered": True, **rec}
            )
            if on_fallback is not None:
                on_fallback(rec)
            cfg = nxt
