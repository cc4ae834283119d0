"""Chaos matrix (docs/robustness.md "Chaos testing"): deterministic
fault injection against every seam the runtime claims to survive, and
the degradation ladder opposite it.

The contract under test: for every fault class x (run / resume / sweep)
path, the outcome is either a completed run **leaf-identical to the
fault-free run** (same seed, same FaultPlan replayed) or a structured,
named failure — never a hang, an uncaught traceback, or silent
divergence. The tier-1 subset (`-m chaos`, not slow) is the fast smoke:
one fault per class on a small world; the slow tier drives the same
matrix through the CLI and the hybrid worker fleet.
"""

import json
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest
import yaml

from test_pipeline import _assert_leaves_exact, _phold_world

from shadow_tpu.engine import EngineConfig
from shadow_tpu.engine.round import (
    EngineCompileError,
    WatchdogExpired,
    run_until,
)
from shadow_tpu.engine.state import state_to_host
from shadow_tpu.runtime import chaos
from shadow_tpu.runtime.checkpoint import (
    CheckpointError,
    CheckpointManager,
    load_checkpoint,
    peek_checkpoint_meta,
    save_checkpoint,
    verify_checkpoint,
)
from shadow_tpu.runtime.chaos import (
    FaultPlan,
    next_engine_cfg,
    parse_fault_arg,
    run_with_engine_ladder,
)
from shadow_tpu.runtime.cli_run import run_from_config, run_sweep
from shadow_tpu.runtime.recovery import RecoveryPolicy, run_until_recovering
from shadow_tpu.simtime import NS_PER_MS

pytestmark = pytest.mark.chaos


# ---- the FaultPlan determinism contract ---------------------------------


def test_fault_plan_deterministic_and_replayable():
    """Two plans from the same (seed, faults) fire at identical sites in
    identical order — including `at: auto` draws — and reset() restores
    the budgets so a chaos run can be replayed exactly."""
    faults = [
        {"kind": "capacity", "at": "auto"},
        {"kind": "stall", "at": 2, "stall_s": 0.1},
        {"kind": "compile", "target": "pump", "count": -1},
    ]
    a, b = FaultPlan(seed=9, faults=faults), FaultPlan(seed=9, faults=faults)
    assert [s.at for s in a.faults] == [s.at for s in b.faults]
    # a different seed draws a different schedule (over the kind+ordinal
    # stream, so two auto faults of one kind land independently)
    many = FaultPlan(
        seed=1,
        faults=[{"kind": "capacity", "at": "auto"} for _ in range(8)],
    )
    assert len({s.at for s in many.faults}) > 1
    # budget accounting: count=1 fires once, count=-1 forever
    assert a.should_fire("capacity", at=a.faults[0].at) is not None
    assert a.should_fire("capacity", at=a.faults[0].at) is None
    assert a.should_fire("compile", tags=("pump",)) is not None
    assert a.should_fire("compile", tags=("pump",)) is not None
    # target mismatch never fires, site mismatch never fires
    assert a.should_fire("compile", tags=("plain",)) is None
    assert a.should_fire("stall", at=0) is None
    a.reset()
    assert a.fired == []
    assert a.should_fire("capacity", at=a.faults[0].at) is not None
    assert a.report()["planned"] == 3 and len(a.report()["fired"]) == 1


def test_persistent_fault_report_stays_bounded():
    """A count=-1 fault fires once per chunk; the fired record list and
    the warning log must stay O(1) in run length — the report keeps the
    first MAX_FIRED_RECORDS records plus the true total."""
    plan = FaultPlan(faults=[{"kind": "capacity", "count": -1}])
    for i in range(chaos.MAX_FIRED_RECORDS + 50):
        assert plan.should_fire("capacity", at=i) is not None
    rep = plan.report()
    assert len(rep["fired"]) == chaos.MAX_FIRED_RECORDS
    assert rep["fired_total"] == chaos.MAX_FIRED_RECORDS + 50
    # small chaos runs keep the exact shape (no fired_total key)
    small = FaultPlan(faults=[{"kind": "capacity"}])
    small.should_fire("capacity", at=0)
    assert "fired_total" not in small.report()


def test_fire_without_plan_is_inert():
    chaos.uninstall()
    assert chaos.active() is None
    assert chaos.fire("capacity", at=0) is None
    with chaos.installed(FaultPlan(faults=[{"kind": "capacity"}])) as p:
        assert chaos.fire("capacity") is p.faults[0]
    assert chaos.active() is None


def test_parse_fault_arg():
    assert parse_fault_arg("capacity@2") == {"kind": "capacity", "at": 2}
    assert parse_fault_arg("stall@1:stall_s=0.5") == {
        "kind": "stall", "at": 1, "stall_s": 0.5,
    }
    assert parse_fault_arg("capacity:target=ph-s3:count=-1") == {
        "kind": "capacity", "target": "ph-s3", "count": -1,
    }
    assert parse_fault_arg("ckpt-corrupt@auto")["at"] == "auto"
    with pytest.raises(ValueError, match="unknown chaos fault kind"):
        parse_fault_arg("frobnicate@1")
    with pytest.raises(ValueError, match="key=val"):
        parse_fault_arg("capacity:count")
    with pytest.raises(ValueError, match="count must be"):
        parse_fault_arg("capacity:count=0")
    # the compile seams carry no site ordinal: a sited compile fault
    # would silently never fire, so it is rejected at parse time
    with pytest.raises(ValueError, match="no @AT site"):
        parse_fault_arg("compile@1")
    with pytest.raises(ValueError, match="no @AT site"):
        parse_fault_arg("compile@auto:target=pump")


def test_chaos_config_section_validates_values_eagerly():
    # the YAML path must fail at config load time with a one-line error
    # (-> CliUserError), matching the --chaos-fault path — never a
    # traceback mid-run when the FaultPlan is built
    from shadow_tpu.config.options import ChaosOptions

    for bad, match in (
        ({"kind": "capacity", "at": "soon"}, "invalid literal"),
        ({"kind": "capacity", "at": -1}, "at must be"),
        ({"kind": "capacity", "count": 0}, "count must be"),
        ({"kind": "stall", "stall_s": "fast"}, "could not convert"),
        ({"kind": "stall", "stall_s": -1}, "stall_s must be"),
    ):
        with pytest.raises(ValueError, match=match):
            ChaosOptions.from_dict({"faults": [bad]})
    # YAML-typical string values coerce cleanly
    ok = ChaosOptions.from_dict(
        {"faults": [{"kind": "stall", "at": "2", "stall_s": "0.5"}]}
    )
    assert ok.faults == [{"kind": "stall", "at": "2", "stall_s": "0.5"}]


# ---- the engine fallback ladder (pump -> plain) -------------------------


def _ecfg(engine, pump_k=3):
    return EngineConfig(
        num_hosts=2, queue_capacity=4, outbox_capacity=4, runahead_ns=1,
        seed=0, engine=engine, pump_k=pump_k,
    )


def test_next_engine_cfg_walks_the_ladder():
    down = next_engine_cfg(_ecfg("pump"))
    assert (down.engine, down.pump_k) == ("plain", 3)  # only the engine changes
    assert next_engine_cfg(_ecfg("plain")) is None
    # "auto" resolves to what it would actually run before stepping down
    assert next_engine_cfg(_ecfg("auto", pump_k=3)).engine == "plain"
    assert next_engine_cfg(_ecfg("auto", pump_k=0)) is None


def test_engine_ladder_falls_to_plain_then_fails_structured():
    attempts = []

    def flaky(cfg):
        attempts.append(cfg.engine)
        if cfg.engine != "plain":
            raise EngineCompileError(cfg.engine, RuntimeError("boom"))
        return "done"

    result, fallbacks = run_with_engine_ladder(_ecfg("pump"), flaky)
    assert result == "done"
    assert attempts == ["pump", "plain"]
    assert [(f["from"], f["to"]) for f in fallbacks] == [("pump", "plain")]
    assert "boom" in fallbacks[0]["reason"]

    def hopeless(cfg):
        raise EngineCompileError(cfg.engine, RuntimeError("bad lowering"))

    # the bottom rung failing is terminal — a typed, named failure
    with pytest.raises(EngineCompileError, match="plain"):
        run_with_engine_ladder(_ecfg("pump"), hopeless)


# ---- checkpoint integrity (sha-256 + fall-back-to-valid) ----------------


def test_checkpoint_corrupt_and_truncated_raise_named(tmp_path):
    cfg, model, tables, st0 = _phold_world()
    good = str(tmp_path / "ckpt-0001.npz")
    save_checkpoint(good, state_to_host(st0), {"fingerprint": "fp"})
    assert verify_checkpoint(good) is None
    assert peek_checkpoint_meta(good)["sha256"]

    corrupt = str(tmp_path / "corrupt.npz")
    trunc = str(tmp_path / "trunc.npz")
    for p in (corrupt, trunc):
        save_checkpoint(p, state_to_host(st0), {"fingerprint": "fp"})
    chaos.damage_file(corrupt, truncate=False)
    chaos.damage_file(trunc, truncate=True)
    for p in (corrupt, trunc):
        assert verify_checkpoint(p) is not None
        # never a bare zipfile.BadZipFile — a CheckpointError naming the file
        with pytest.raises(CheckpointError, match=p.replace("\\", ".")):
            load_checkpoint(p, st0, "fp")
    with pytest.raises(CheckpointError, match="corrupt or truncated"):
        peek_checkpoint_meta(trunc)


def test_checkpoint_sha256_catches_payload_tamper(tmp_path):
    """A leaf flipped WITHOUT breaking the zip structure is exactly what
    the digest exists for: the structural checks pass, the sha fails."""
    _cfg, _model, _tables, st0 = _phold_world()
    path = str(tmp_path / "ckpt-0001.npz")
    save_checkpoint(path, state_to_host(st0), {"fingerprint": "fp"})
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    leaf = arrays["leaf_00000"]
    arrays["leaf_00000"] = (leaf.astype(np.int64) + 1).astype(leaf.dtype)
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    assert verify_checkpoint(path) == "payload failed its sha-256 integrity check"
    with pytest.raises(CheckpointError, match="sha-256"):
        load_checkpoint(path, st0, "fp")


def test_latest_path_skips_corrupt_falls_back_to_valid(tmp_path):
    """One bad write can no longer take the whole resume path down: the
    newest-first walk skips damaged files with a warning and lands on
    the newest VALID checkpoint."""
    _cfg, _model, _tables, st0 = _phold_world()
    host = state_to_host(st0)
    older = str(tmp_path / "ckpt-00000000000000000001.npz")
    newer = str(tmp_path / "ckpt-00000000000000000002.npz")
    save_checkpoint(older, host, {"fingerprint": "fp"})
    save_checkpoint(newer, host, {"fingerprint": "fp"})
    chaos.damage_file(newer, truncate=True)
    assert CheckpointManager.latest_path(str(tmp_path)) == older
    # verify=False restores the raw lexical-newest lookup
    assert CheckpointManager.latest_path(str(tmp_path), verify=False) == newer
    chaos.damage_file(older, truncate=False)
    assert CheckpointManager.latest_path(str(tmp_path)) is None


def test_ckpt_faults_damage_manager_writes(tmp_path):
    """The ckpt-corrupt / ckpt-truncate chaos faults hit the Nth write of
    a CheckpointManager, after the atomic commit."""
    _cfg, _model, _tables, st0 = _phold_world()
    host = state_to_host(st0)
    plan = FaultPlan(faults=[{"kind": "ckpt-truncate", "at": 1}])
    with chaos.installed(plan):
        mgr = CheckpointManager(str(tmp_path), 0, "fp")
        p0 = mgr.write(host)
        host2 = host.replace(now=host.now + 1)
        p1 = mgr.write(host2)
    assert verify_checkpoint(p0) is None
    assert verify_checkpoint(p1) is not None
    assert plan.report()["fired"] == [{"kind": "ckpt-truncate", "at": 1}]
    assert CheckpointManager.latest_path(str(tmp_path)) == p0


# ---- signal robustness (pinning PR 4 behavior that was never tested) ----


def test_double_sigint_second_signal_aborts_immediately():
    """The first SIGINT sets the guard flag AND restores the previous
    handlers, so a second signal takes the default path (immediate
    KeyboardInterrupt — no second checkpoint attempt) instead of being
    swallowed by a wedged run. Run in a subprocess so the prev handler
    is Python's default, exactly as in a real CLI run."""
    code = (
        "import os, signal\n"
        "from shadow_tpu.runtime.checkpoint import InterruptGuard\n"
        "g = InterruptGuard()\n"
        "with g:\n"
        "    os.kill(os.getpid(), signal.SIGINT)\n"
        "    assert g.fired(0), 'first signal must arm the guard'\n"
        "    assert not g._prev, 'first signal must restore prev handlers'\n"
        "    try:\n"
        "        os.kill(os.getpid(), signal.SIGINT)\n"
        "        raise SystemExit('second SIGINT was swallowed')\n"
        "    except KeyboardInterrupt:\n"
        "        pass\n"
        "print('DOUBLE_SIGINT_OK')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        cwd=str(pathlib.Path(__file__).parent.parent),
        env={**__import__('os').environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "DOUBLE_SIGINT_OK" in r.stdout, r.stdout + r.stderr


def test_sigterm_mid_save_checkpoint_leaves_dir_loadable(tmp_path, monkeypatch):
    """A kill landing mid-save (modeled as the writer dying after partial
    tmp-file bytes) must leave the directory loadable: the atomic
    tmp+rename means the half-written file never takes the ckpt-*.npz
    name, and latest_path still returns the previous valid checkpoint."""
    from shadow_tpu.runtime import checkpoint as cp

    _cfg, _model, _tables, st0 = _phold_world()
    host = state_to_host(st0)
    mgr = CheckpointManager(str(tmp_path), 0, "fp")
    p0 = mgr.write(host)

    real_savez = np.savez

    def dying_savez(f, **arrays):
        f.write(b"PK\x03\x04 partial write, then SIGTERM")
        raise SystemExit(143)  # what SIGTERM's default disposition does

    monkeypatch.setattr(cp.np, "savez", dying_savez)
    with pytest.raises(SystemExit):
        mgr.write(host.replace(now=host.now + 1))
    monkeypatch.setattr(cp.np, "savez", real_savez)

    assert CheckpointManager.latest_path(str(tmp_path)) == p0
    restored, meta = load_checkpoint(p0, st0, "fp")
    _assert_leaves_exact(st0, restored)
    # the partial tmp file is present but invisible to the ckpt glob
    leftovers = list(pathlib.Path(tmp_path).glob("*.tmp.*"))
    assert leftovers, "the interrupted write should leave its tmp file"


# ---- engine-level matrix: injected faults end leaf-identical ------------


def test_stall_watchdog_redispatch_leaf_exact():
    """A stalled chunk dispatch blows the watchdog; the driver abandons
    the in-flight chunk and re-dispatches from the retained snapshot —
    and the final state is leaf-identical to the fault-free run (the
    watchdog path replays, never perturbs, the trajectory)."""
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    # deadline well above a real chunk fetch on a loaded 1-core box (a
    # legitimate fetch blowing it would add a spurious recovery), well
    # below the injected stall so the fault reliably trips it
    plan = FaultPlan(faults=[{"kind": "stall", "at": 1, "stall_s": 2.5}])
    with chaos.installed(plan):
        final, recoveries = run_until_recovering(
            st0, end, model, tables, cfg, rounds_per_chunk=4,
            policy=RecoveryPolicy(max_recoveries=3, snapshot_interval_chunks=2),
            watchdog_s=0.75,
        )
    # ≥1 tolerates a contention-induced expiry riding along — the hard
    # contract is the kind, the injection record, and leaf-exactness
    kinds = [r["kind"] for r in recoveries]
    assert kinds and set(kinds) == {"watchdog"}
    assert recoveries[0]["deadline_s"] == 0.75
    assert plan.report()["fired"] == [{"kind": "stall", "at": 1}]
    _assert_leaves_exact(straight, final)


def test_watchdog_budget_exhausted_is_structured():
    """A persistent stall past the recovery budget surfaces as a typed
    WatchdogExpired naming the chunk and deadline — never a hang. The
    terminal exception carries the recoveries the run survived first, so
    a degraded-then-failed run stays visibly degraded (the sweep manifest
    reads this for quarantined jobs)."""
    cfg, model, tables, st0 = _phold_world()
    plan = FaultPlan(faults=[{"kind": "stall", "stall_s": 0.2, "count": -1}])
    with chaos.installed(plan):
        with pytest.raises(WatchdogExpired, match="watchdog deadline") as ei:
            run_until_recovering(
                st0, 40 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4,
                policy=RecoveryPolicy(max_recoveries=1),
                watchdog_s=0.05,
            )
    assert [r["kind"] for r in ei.value.recoveries] == ["watchdog"]


def test_injected_capacity_recovers_leaf_exact():
    """An injected CapacityError takes the real rollback-and-regrow path
    (tagged `injected` in the recovery record) and the completed run is
    leaf-exact vs a fault-free run that STARTED at the regrown capacity
    — the same exactness bar as a real overflow."""
    cfg, model, tables, st0 = _phold_world(queue_capacity=64)
    end = 40 * NS_PER_MS
    plan = FaultPlan(faults=[{"kind": "capacity", "at": 1}])
    with chaos.installed(plan):
        final, recoveries = run_until_recovering(
            st0, end, model, tables, cfg, rounds_per_chunk=4,
            policy=RecoveryPolicy(max_recoveries=2, snapshot_interval_chunks=2),
        )
    assert [r["kind"] for r in recoveries] == ["capacity"]
    assert recoveries[0]["injected"] is True
    assert final.queue.capacity == 128  # x2 growth ladder
    cfg2, model2, tables2, st2 = _phold_world(queue_capacity=128)
    reference = run_until(st2, end, model2, tables2, cfg2, rounds_per_chunk=4)
    _assert_leaves_exact(reference, final)


def test_compile_fault_falls_back_leaf_exact():
    """An injected compile fault on the pump engine walks the runtime
    ladder down to plain, and the completed run is leaf-identical to a
    straight plain run (the engines are leaf-exact by contract, so a
    fallback changes wall-clock, never a result leaf). The injection
    fires BEFORE the doomed engine compiles, so this smoke costs no
    extra executable."""
    import dataclasses

    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)

    pump_cfg = dataclasses.replace(cfg, engine="pump", pump_k=3)
    plan = FaultPlan(faults=[{"kind": "compile", "target": "pump"}])
    with chaos.installed(plan):
        final, fallbacks = run_with_engine_ladder(
            pump_cfg,
            lambda c: run_until(st0, end, model, tables, c, rounds_per_chunk=4),
        )
    assert [(f["from"], f["to"]) for f in fallbacks] == [("pump", "plain")]
    _assert_leaves_exact(straight, final)

    # a plain-engine compile failure has no rung left: structured error
    plain_plan = FaultPlan(faults=[{"kind": "compile", "target": "plain"}])
    with chaos.installed(plain_plan):
        with pytest.raises(EngineCompileError, match="plain"):
            run_with_engine_ladder(
                cfg,
                lambda c: run_until(
                    st0, end, model, tables, c, rounds_per_chunk=4
                ),
            )


def test_stall_without_watchdog_completes_identically():
    """Watchdog off: a stall is only a delay — the run completes with no
    recovery and a bit-identical final state."""
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    plan = FaultPlan(faults=[{"kind": "stall", "at": 1, "stall_s": 0.1}])
    with chaos.installed(plan):
        final = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    _assert_leaves_exact(straight, final)


# ---- sweep path: poison-job quarantine (the acceptance pin) -------------


def _mini_sweep_service(retry_max: int):
    """A SweepService shell with just the state _handle_failure touches —
    the retry/quarantine ladder is pure bookkeeping, so it unit-tests
    without building a world or compiling anything."""
    import types

    from shadow_tpu.runtime.sweep import SweepService

    svc = SweepService.__new__(SweepService)
    svc.spec = types.SimpleNamespace(retry_max=retry_max, retry_backoff_s=0.0)
    svc.clock_ns = 0
    svc.job_attempts = {}
    svc.job_records = {}
    svc.job_progress = {"j0": {"now_ns": 0, "events": 0}}
    svc.batches = []
    return svc


def _mini_job_batch():
    import types

    from shadow_tpu.runtime.sweep import Batch

    job = types.SimpleNamespace(
        name="j0", entry="e", seed=1, priority=0, arrival_ns=0,
        group_key="g" * 16,
        config=types.SimpleNamespace(
            general=types.SimpleNamespace(data_directory="d")
        ),
    )
    batch = Batch(
        jobs=[job], base_seed=1, stride=1, priority=0, arrival_ns=0,
        group_key=job.group_key, index=0,
    )
    return job, batch


def test_sweep_failure_terminal_status_failed_vs_quarantined():
    """The ladder's terminal statuses: `quarantined` is reserved for a
    repeat offender (failed again after a retry); with retry_max: 0 the
    first failure is terminal and the job is recorded plain `failed` —
    both count against the exit code (docs/service.md)."""
    err = ValueError("boom")

    # retry_max=0: never retried, so never a "repeat offender"
    svc = _mini_sweep_service(retry_max=0)
    job, batch = _mini_job_batch()
    svc._handle_failure(batch, err, pending=[])
    rec = svc.job_records["j0"]
    assert rec["status"] == "failed"
    assert rec["failure"] == "ValueError"
    assert rec["failed_attempts"] == 1

    # retry_max=1: first failure re-queues, second quarantines
    svc = _mini_sweep_service(retry_max=1)
    job, batch = _mini_job_batch()
    pending: list = []
    svc._handle_failure(batch, err, pending)
    assert "j0" not in svc.job_records and len(pending) == 1  # retried
    svc._handle_failure(pending.pop(), err, pending)
    rec = svc.job_records["j0"]
    assert rec["status"] == "quarantined"
    assert rec["failed_attempts"] == 2


def test_sweep_untyped_batch_error_walks_ladder_not_abort():
    """An UNTYPED runtime error in one batch (an XLA device error, a bug
    in our own code) must walk the same split/retry/quarantine ladder as
    the typed kinds — never abort the sweep before the manifest is
    written, voiding the other N−1 jobs with a bare traceback."""
    svc = _mini_sweep_service(retry_max=0)
    job, batch = _mini_job_batch()

    def boom(b, pending):
        raise RuntimeError("XLA runtime error: RESOURCE_EXHAUSTED")

    svc._run_batch = boom
    svc._drain([batch])  # must NOT raise
    rec = svc.job_records["j0"]
    assert rec["status"] == "failed"
    assert rec["failure"] == "RuntimeError"
    assert "RESOURCE_EXHAUSTED" in rec["error"]


SWEEP_BASE = """
general:
  stop_time: 80 ms
  heartbeat_interval: null
network:
  graph:
    type: 1_gbit_switch
experimental:
  rounds_per_chunk: 4
  recover: false
hosts:
  peer:
    network_node_id: 0
    quantity: 8
    processes:
      - path: phold
        args:
          min_delay: "2 ms"
          max_delay: "12 ms"
"""

SWEEP_JOBS = """
  jobs:
    - name: ph
      seed_range: [0, 8]
"""


def _sweep_spec(tmp_path, name, base_name, out):
    spec = tmp_path / f"{name}.yaml"
    spec.write_text(
        f"sweep:\n  name: {name}\n  base: {base_name}\n"
        f"  output_dir: {out}\n  retry_max: 1\n{SWEEP_JOBS}"
    )
    return spec


@pytest.fixture(scope="module")
def fault_free_sweep(tmp_path_factory):
    """The fault-free 8-job reference sweep the poison run must match."""
    root = tmp_path_factory.mktemp("chaos-sweep")
    (root / "base.yaml").write_text(SWEEP_BASE)
    out = root / "clean"
    assert run_sweep(str(_sweep_spec(root, "clean", "base.yaml", out))) == 0
    return root, json.loads((out / "sweep-manifest.json").read_text())


@pytest.mark.slow
def test_sweep_poison_job_quarantined_rest_identical(fault_free_sweep):
    """THE acceptance pin: an 8-job sweep with one poison job (persistent
    injected CapacityError targeting ph-s3) completes the other 7 jobs
    with sim-stats identical to the fault-free sweep, quarantines the
    poison job in sweep-manifest.json with its failure kind, and exits
    non-zero."""
    root, clean = fault_free_sweep
    base = yaml.safe_load(SWEEP_BASE)
    base["chaos"] = {
        "faults": [
            {"kind": "capacity", "at": 1, "target": "ph-s3", "count": -1}
        ]
    }
    (root / "poison.yaml").write_text(yaml.dump(base))
    out = root / "poisoned"
    rc = run_sweep(str(_sweep_spec(root, "poisoned", "poison.yaml", out)))
    assert rc == 1  # a quarantined job must fail the process
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["jobs_total"] == 8 and m["jobs_done"] == 7
    assert m["jobs_quarantined"] == 1 and m["jobs_failed"] == 0

    by_name = {r["name"]: r for r in m["jobs"]}
    poison = by_name["ph-s3"]
    assert poison["status"] == "quarantined"
    assert poison["failure"] == "capacity"
    assert poison["failed_attempts"] == 2  # first solo try + retry_max=1
    assert "injected" in poison["error"]
    # the original packed batch split; the poison job's retries failed
    statuses = {b["status"] for b in m["batches"]}
    assert "split" in statuses and "failed" in statuses
    # the chaos section makes the injection visible in the manifest
    assert all(f["target"] == "ph-s3" for f in m["chaos"]["fired"])

    clean_by_name = {r["name"]: r for r in clean["jobs"]}
    for name, rec in by_name.items():
        if name == "ph-s3":
            continue
        assert rec["status"] == "done"
        assert rec["stats"] == clean_by_name[name]["stats"], name
        # published per-job sim-stats match the fault-free sweep's too
        poisoned_stats = json.loads(
            (out / "jobs" / name / "sim-stats.json").read_text()
        )
        clean_stats = json.loads(
            (root / "clean" / "jobs" / name / "sim-stats.json").read_text()
        )
        for s in (poisoned_stats, clean_stats):
            s.pop("wall_seconds")
        assert poisoned_stats == clean_stats, name


@pytest.mark.slow
def test_sweep_preempt_storm_changes_nothing(fault_free_sweep):
    """A chaos `preempt` storm (guard armed twice with no higher-priority
    arrival) forces checkpoint/requeue/resume cycles — and every job's
    published stats still match the fault-free sweep, because each
    resume is bit-exact."""
    root, clean = fault_free_sweep
    base = yaml.safe_load(SWEEP_BASE)
    base["chaos"] = {"faults": [{"kind": "preempt", "at": 2, "count": 2}]}
    (root / "stormbase.yaml").write_text(yaml.dump(base))
    out = root / "storm"
    spec = root / "storm.yaml"
    spec.write_text(
        f"sweep:\n  name: storm\n  base: stormbase.yaml\n"
        f"  output_dir: {out}\n  retry_max: 1\n"
        "  jobs:\n    - name: ph\n      seeds: [0, 1]\n"
    )
    assert run_sweep(str(spec)) == 0
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["jobs_done"] == 2 and m["preemptions"] == 2
    assert len(m["chaos"]["fired"]) == 2
    clean_by_name = {r["name"]: r for r in clean["jobs"]}
    for r in m["jobs"]:
        assert r["status"] == "done"
        assert r["stats"] == clean_by_name[r["name"]]["stats"], r["name"]


# ---- CLI-level matrix: one fault per class through shadow-tpu run -------

CLI_BASE = """
general:
  stop_time: 100 ms
  heartbeat_interval: null
network:
  graph:
    type: 1_gbit_switch
experimental:
  rounds_per_chunk: 4
hosts:
  peer:
    network_node_id: 0
    quantity: 8
    processes:
      - path: phold
        args:
          min_delay: "2 ms"
          max_delay: "12 ms"
"""

_CORE_KEYS = (
    "events_handled", "packets_sent", "packets_dropped",
    "packets_unroutable", "num_hosts",
)


def _cli_run(root, tag, chaos_cfg=None, experimental=None, general=None):
    cfg = yaml.safe_load(CLI_BASE)
    cfg["general"]["data_directory"] = str(root / tag)
    if general:
        cfg["general"].update(general)
    if experimental:
        cfg["experimental"].update(experimental)
    if chaos_cfg:
        cfg["chaos"] = chaos_cfg
    path = root / f"{tag}.yaml"
    path.write_text(yaml.dump(cfg))
    rc = run_from_config(str(path))
    stats_path = root / tag / "sim-stats.json"
    # an interrupted run (exit 130) stops before writing sim-stats.json
    stats = json.loads(stats_path.read_text()) if stats_path.exists() else None
    return rc, stats


@pytest.mark.slow
def test_chaos_matrix_cli_run_path(tmp_path):
    """One injected fault per engine-facing class through the real CLI
    entry point: every run completes with core stats identical to the
    fault-free baseline, exits 0, and publishes chaos + degraded
    sections — a degraded run is visibly degraded, never silently
    slower or quietly wrong."""
    rc0, s0 = _cli_run(tmp_path, "baseline")
    assert rc0 == 0
    core0 = {k: s0[k] for k in _CORE_KEYS}
    assert "chaos" not in s0 and "degraded" not in s0

    # stall -> watchdog re-dispatch (deadline well above a real chunk
    # fetch on a loaded box, well below the injected stall; ≥1 tolerates
    # a contention-induced expiry riding along — the hard contract is
    # identical core stats plus a visibly degraded report)
    rc, s = _cli_run(
        tmp_path, "stall",
        chaos_cfg={"faults": [{"kind": "stall", "at": 1, "stall_s": 2.5}]},
        experimental={"chunk_watchdog_s": 0.75},
    )
    assert rc == 0 and {k: s[k] for k in _CORE_KEYS} == core0
    assert s["degraded"]["watchdog_redispatches"] >= 1
    assert s["recovery"]["events"][0]["kind"] == "watchdog"
    assert s["chaos"]["fired"] == [{"kind": "stall", "at": 1}]

    # compile failure -> engine fallback ladder (pump -> plain)
    rc, s = _cli_run(
        tmp_path, "compile",
        chaos_cfg={"faults": [{"kind": "compile", "target": "pump"}]},
        experimental={"engine": "pump", "pump_k": 4},
    )
    assert rc == 0 and {k: s[k] for k in _CORE_KEYS} == core0
    assert s["degraded"]["engine_fallbacks"] == [{
        "from": "pump", "to": "plain",
        "reason": "injected fault: pump engine compile failed (chaos plane)",
    }]

    # injected capacity -> rollback-and-regrow, tagged injected
    rc, s = _cli_run(
        tmp_path, "capacity",
        chaos_cfg={"faults": [{"kind": "capacity", "at": 1}]},
    )
    assert rc == 0 and {k: s[k] for k in _CORE_KEYS} == core0
    assert s["recovery"]["count"] == 1
    assert s["recovery"]["events"][0]["injected"] is True


@pytest.mark.slow
def test_chaos_matrix_cli_resume_path(tmp_path, monkeypatch):
    """Resume path: the run is interrupted mid-flight and its FINAL
    checkpoint is truncated by an injected fault — resume must fall back
    to the previous valid checkpoint with a warning and still reach the
    fault-free final stats."""
    rc0, s0 = _cli_run(tmp_path, "baseline")
    core0 = {k: s0[k] for k in _CORE_KEYS}

    monkeypatch.setenv("SHADOW_TPU_TEST_INTERRUPT_AT_NS", str(50 * NS_PER_MS))
    ckpt_dir = str(tmp_path / "ckpts")
    rc, _ = _cli_run(
        tmp_path, "interrupted",
        chaos_cfg={"faults": [{"kind": "ckpt-truncate", "at": 2}]},
        general={"checkpoint_dir": ckpt_dir, "checkpoint_interval": "20 ms"},
    )
    assert rc == 130  # interrupted-with-checkpoint exit status
    damaged = [
        p for p in pathlib.Path(ckpt_dir).glob("ckpt-*.npz")
        if verify_checkpoint(str(p)) is not None
    ]
    assert len(damaged) == 1, "the final checkpoint write must be truncated"

    monkeypatch.delenv("SHADOW_TPU_TEST_INTERRUPT_AT_NS")
    rc, s = _cli_run(
        tmp_path, "resumed",
        general={
            "checkpoint_dir": ckpt_dir, "checkpoint_interval": "20 ms",
            "resume": True,
        },
    )
    assert rc == 0 and {k: s[k] for k in _CORE_KEYS} == core0


# ---- hybrid worker faults: kill / hang under supervision ----------------


def test_worker_fault_injection_seam():
    """Tier-1 smoke for the worker-kill / worker-hang classes: the
    injection seam SIGKILLs / SIGSTOPs exactly the targeted worker
    process (full supervision equivalence runs in the slow tier)."""
    import multiprocessing as mp
    import os
    import signal as sig
    import types

    from shadow_tpu.runtime.hybrid import ParallelHybridScheduler

    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=time.sleep, args=(60,)) for _ in range(2)]
    for p in procs:
        p.start()
    stub = types.SimpleNamespace(
        _workers=[(p, None) for p in procs], _windows_sent=0
    )
    inject = ParallelHybridScheduler._inject_worker_faults
    try:
        # no plan installed: a no-op
        inject(stub)
        assert all(p.is_alive() for p in procs)
        plan = FaultPlan(faults=[
            {"kind": "worker-kill", "at": 0, "target": "worker1"},
            {"kind": "worker-hang", "at": 0, "target": "worker0"},
        ])
        with chaos.installed(plan):
            inject(stub)
        procs[1].join(10)
        assert not procs[1].is_alive(), "worker1 must be SIGKILLed"
        assert procs[0].is_alive(), "worker0 is stopped, not dead"
        state = pathlib.Path(f"/proc/{procs[0].pid}/stat").read_text()
        assert state.split()[2] == "T", "worker0 must be SIGSTOPped"
        assert sorted(f["kind"] for f in plan.report()["fired"]) == [
            "worker-hang", "worker-kill",
        ]
    finally:
        for p in procs:
            if p.is_alive():
                os.kill(p.pid, sig.SIGKILL)
            p.join(10)


GUESTS = pathlib.Path(__file__).parent / "guests"


@pytest.fixture(scope="module")
def hybrid_bins(tmp_path_factory):
    out = tmp_path_factory.mktemp("chaos-guests")
    built = {}
    for name in ("tcp_echo_server", "tcp_client"):
        dst = out / name
        subprocess.run(
            ["cc", "-O2", "-o", str(dst), str(GUESTS / f"{name}.c")], check=True
        )
        built[name] = str(dst)
    return built


def _run_hybrid(tmp_path, bins, name, plan=None, **kw):
    """One hybrid run under an optional fault plan; returns the
    cross-run-comparable outcome tuple (stats, sorted event log, guest
    info, respawn counters) — the same equivalence surface
    tests/test_hybrid_supervision.py pins."""
    from shadow_tpu.graph import compute_routing
    from shadow_tpu.hostk.kernel import ProcessSpec
    from shadow_tpu.runtime.hybrid import ParallelHybridScheduler
    from shadow_tpu.simtime import NS_PER_SEC
    from tests.topo import two_node_graph

    graph = two_node_graph(10, 0.0)
    host_names, host_nodes = ["server0", "client0"], [0, 1]
    tables = compute_routing(graph).with_hosts(host_nodes)
    cfg = EngineConfig(
        num_hosts=2, queue_capacity=256, outbox_capacity=64,
        runahead_ns=1 * NS_PER_MS, seed=5,
    )
    specs = [
        ProcessSpec(host="server0", args=[bins["tcp_echo_server"], "8080", "1"]),
        ProcessSpec(
            host="client0",
            args=[bins["tcp_client"], "server0", "8080", "6000"],
            start_ns=100 * NS_PER_MS,
        ),
    ]
    sched = ParallelHybridScheduler(
        tables, cfg, host_names=host_names, host_nodes=host_nodes,
        specs=specs, num_workers=2, seed=5, data_dir=tmp_path / name, **kw,
    )
    ctx = chaos.installed(plan) if plan is not None else chaos.installed(None)
    with ctx:
        try:
            try:
                sched.run(30 * NS_PER_SEC)
            finally:
                sched.shutdown()
            stats = sched.stats()
            log = sorted(sched.event_log())
            info = {
                p["host"]: (p["stdout"], p["exit_code"], p["syscalls"])
                for p in sched.proc_info()
            }
            return stats, log, info, list(sched._respawns)
        finally:
            sched.close()


@pytest.mark.slow
def test_worker_kill_and_hang_faults_recover_identically(tmp_path, hybrid_bins):
    """The worker-kill and worker-hang chaos faults land on the real
    supervision path (bounded recv -> kill -> respawn -> replay) and the
    run's outcomes are identical to an undisturbed run — the in-process
    twin of the SIGKILL harness tests/test_hybrid_supervision.py uses."""
    clean = _run_hybrid(tmp_path, hybrid_bins, "clean")
    assert clean[3] == [0, 0]

    kill_plan = FaultPlan(
        faults=[{"kind": "worker-kill", "at": 1, "target": "worker1"}]
    )
    killed = _run_hybrid(tmp_path, hybrid_bins, "killed", plan=kill_plan)
    assert killed[3] == [0, 1]  # exactly one respawn, of the killed worker
    assert kill_plan.report()["fired"] == [
        {"kind": "worker-kill", "at": 1, "target": "worker1"}
    ]
    assert killed[:3] == clean[:3]

    hang_plan = FaultPlan(
        faults=[{"kind": "worker-hang", "at": 1, "target": "worker1"}]
    )
    t0 = time.monotonic()
    hung = _run_hybrid(
        tmp_path, hybrid_bins, "hung", plan=hang_plan, rpc_timeout_s=5,
    )
    assert hung[3] == [0, 1]  # the hung worker was killed + respawned
    assert hung[:3] == clean[:3]
    assert time.monotonic() - t0 < 300  # bounded: never an indefinite hang
