"""Elastic mesh (ISSUE 15): device-loss tolerance and grid-portable
checkpoints — the engine/unit tier.

Contracts pinned here:

  * `MeshPlan.degraded` walks the documented rung order (R×S → R×S/2 →
    1×S → single device), honors the surviving-device count and the
    host-axis divisibility, and terminates at None;
  * an injected `device-loss` fault mid-mesh-run degrades the grid and
    replays leaf-exact vs the fault-free run (modulo the established
    per-shard iteration diagnostics), with the reshape journaled as a
    kind="device-loss" recovery record;
  * real XLA runtime errors translate to DeviceLossError
    (device_loss_from); driver-control and plain errors do not;
  * outside the mesh plane a device loss is terminal but structured;
  * CapacityError's (replica, shard) naming and the whole-batch regrow
    stay correct on degenerate grids REACHED VIA DEGRADATION, not just
    grids requested up front (the satellite pin);
  * the sweep retry backoff is exponential with seeded, bounded jitter
    (deterministic replay, no lockstep stampede);
  * fingerprint portability: `general.mesh` is layout metadata — grids
    hash alike, replica-count changes refuse naming the key.
"""

import dataclasses
import pathlib

import jax
import numpy as np
import pytest

from test_pipeline import _phold_world

from shadow_tpu.engine.mesh import MeshPlan, init_mesh_state, run_mesh_until
from shadow_tpu.engine.round import (
    CapacityError,
    DeviceLossError,
    WatchdogExpired,
    device_loss_from,
)
from shadow_tpu.engine.state import state_to_host
from shadow_tpu.runtime import chaos
from shadow_tpu.runtime.mesh import MeshRunner
from shadow_tpu.runtime.recovery import RecoveryPolicy
from shadow_tpu.simtime import NS_PER_MS


def _assert_batch_exact(a, b, what=""):
    """Leaf-exact modulo the two established sharded-execution
    deviations (tests/test_mesh.py): per-shard iteration diagnostics
    and dead-slot queue garbage (live queue content is compared in
    canonical pop order via the host snapshot)."""
    from test_mesh import _canon_queue, per_shard_leaf

    ha, hb = state_to_host(a), state_to_host(b)
    grid_leaves = (".queue.time", ".queue.tie", ".queue.kind",
                   ".queue.data", ".queue.aux")
    fa = jax.tree_util.tree_leaves_with_path(ha)
    fb = jax.tree_util.tree_leaves_with_path(hb)
    assert len(fa) == len(fb)
    for (path, la), (_, lb) in zip(fa, fb):
        ks = jax.tree_util.keystr(path)
        if per_shard_leaf(ks) or ks in grid_leaves:
            continue
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"mismatch{what} at {ks}"
        )
    for r in range(a.now.shape[0]):
        qa = jax.tree.map(lambda l: l[r], a.queue)
        qb = jax.tree.map(lambda l: l[r], b.queue)
        for h in range(qa.num_hosts):
            assert _canon_queue(qa, h) == _canon_queue(qb, h), (
                f"queue content mismatch{what} at replica {r} host {h}"
            )


# --- degradation ladder units -------------------------------------------


def test_mesh_degradation_ladder_order():
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    # lose one of 8 devices: halve the shard axis first
    nxt = plan.degraded(7, 8)
    assert (nxt.rows, nxt.shards) == (2, 2)
    # walk all the way down: 2x2 -> 2x1 -> 1x1 -> terminal
    nxt2 = nxt.degraded(7, 8)
    assert (nxt2.rows, nxt2.shards) == (2, 1)
    nxt3 = nxt2.degraded(7, 8)
    assert (nxt3.rows, nxt3.shards) == (1, 1)
    assert nxt3.local_replicas == 2  # both worlds vmapped on one device
    assert nxt3.degraded(8, 8) is None  # nothing below single device


def test_mesh_degradation_honors_survivors_and_divisibility():
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    # only 3 survivors: R×S/2 (4 devices) and 1×S (4) don't fit — 1×2 does
    nxt = plan.degraded(3, 8)
    assert (nxt.rows, nxt.shards) == (1, 2)
    # an odd shard axis halves to 1 (integer rung), keeping the rows
    plan6 = MeshPlan(replicas=2, shards=3, rows=2)
    nxt6 = plan6.degraded(5, 6)
    assert (nxt6.rows, nxt6.shards) == (2, 1)
    # a rung must SHED devices, never rearrange: 1x1 from 1x1 is None
    assert MeshPlan(replicas=4, shards=1, rows=1).degraded(8, 8) is None


# --- DeviceLossError translation ----------------------------------------


def test_device_loss_from_translates_xla_runtime_errors():
    from jax.errors import JaxRuntimeError as XlaRuntimeError

    err = XlaRuntimeError("INTERNAL: device failed")
    loss = device_loss_from(err, 5)
    assert isinstance(loss, DeviceLossError)
    assert loss.chunk == 5 and not loss.injected
    assert device_loss_from(
        XlaRuntimeError("UNAVAILABLE: client disconnected"), 2
    ) is not None
    # non-loss XLA statuses must NOT degrade the grid (the allowlist):
    # OOM on fewer devices is worse, and deterministic errors would
    # just replay into themselves down the whole ladder
    for status in ("RESOURCE_EXHAUSTED: out of memory",
                   "INVALID_ARGUMENT: shape mismatch",
                   "FAILED_PRECONDITION: donated buffer",
                   "DEADLINE_EXCEEDED: collective timeout"):
        assert device_loss_from(XlaRuntimeError(status), 1) is None
    # driver-control and plain errors pass through untouched
    assert device_loss_from(WatchdogExpired(1, 0.5), 1) is None
    assert device_loss_from(RuntimeError("Array has been deleted"), 1) is None
    assert device_loss_from(ValueError("shape"), 1) is None
    # an already-typed loss is returned as itself
    pre = DeviceLossError(2, device_id=3)
    assert device_loss_from(pre, 9) is pre


# --- injected device loss: degrade + leaf-exact replay ------------------


def test_device_loss_degrades_mesh_and_replays_leaf_exact():
    """The tentpole pin: an injected device-loss mid-batch completes on
    a degraded grid with results leaf-exact vs fault-free, the reshape
    recorded as a kind="device-loss" recovery record naming both
    grids."""
    assert jax.device_count() == 8
    cfg, model, tables, _ = _phold_world(num_hosts=8)
    end = 40 * NS_PER_MS
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    ref = run_mesh_until(
        init_mesh_state(cfg, model, plan, 1), end, model, tables, cfg, plan,
        rounds_per_chunk=4,
    )

    runner = MeshRunner(model, tables, cfg, plan=plan, rounds_per_chunk=4)
    fault = chaos.FaultPlan(
        seed=0, faults=[{"kind": "device-loss", "at": 2, "target": "3"}]
    )
    with chaos.installed(fault):
        final = runner.run(
            end,
            recovery=RecoveryPolicy(max_recoveries=4,
                                    snapshot_interval_chunks=2),
        )
    assert runner.plan.devices_needed < plan.devices_needed
    assert runner.mesh_degradations, "the reshape must be journaled"
    d = runner.mesh_degradations[0]
    assert d["grid_from"] == "2x4" and d["device"] == 3
    rec = runner.recovery_report[0]
    assert rec["kind"] == "device-loss" and rec["injected"]
    assert rec["grid_from"] == "2x4" and rec["grid_to"] == d["grid_to"]
    assert rec["device"] == 3 and "replay_from_ns" in rec
    # the degraded grid genuinely avoids the lost device
    assert all(
        dev.id != 3 for dev in np.asarray(runner._get_mesh().devices).ravel()
    )
    _assert_batch_exact(final, ref, " (device-loss replay)")


def test_device_loss_terminal_outside_mesh_is_structured():
    """No second device to degrade onto: the pure-ensemble runner's
    device loss is terminal, typed, and carries its (empty) recovery
    history instead of hanging or mutating results."""
    from shadow_tpu.runtime.ensemble import EnsembleRunner

    cfg, model, tables, _ = _phold_world(num_hosts=8)
    runner = EnsembleRunner(model, tables, cfg, num_replicas=2,
                            rounds_per_chunk=4)
    fault = chaos.FaultPlan(
        seed=0, faults=[{"kind": "device-loss", "at": 1}]
    )
    with chaos.installed(fault):
        with pytest.raises(DeviceLossError, match="lost a device at chunk 1"):
            runner.run(
                40 * NS_PER_MS,
                recovery=RecoveryPolicy(max_recoveries=4,
                                        snapshot_interval_chunks=2),
            )
    # losing a device the run does NOT occupy cannot touch it: a fault
    # targeting an idle device never fires (the launch seam advertises
    # only the state's own devices), so the single-device run completes
    idle = str(max(d.id for d in jax.devices()))
    fault2 = chaos.FaultPlan(
        seed=0, faults=[{"kind": "device-loss", "at": 1, "target": idle}]
    )
    with chaos.installed(fault2):
        runner.run(
            40 * NS_PER_MS,
            recovery=RecoveryPolicy(max_recoveries=4,
                                    snapshot_interval_chunks=2),
        )
    assert not fault2.fired, "an idle device's loss must not fire"


# --- satellite: degenerate grids reached via degradation ----------------


def test_capacity_naming_on_grid_reached_via_degradation():
    """(replica, shard) naming must stay correct on a grid the run
    DEGRADED onto, not just one requested up front: after a device loss
    burns the only recovery rung, the real overflow's terminal
    CapacityError names coordinates within the degraded grid."""
    cfg, model, tables, _ = _phold_world(num_hosts=8, queue_capacity=2)
    cfg = dataclasses.replace(cfg, outbox_capacity=1)
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    runner = MeshRunner(model, tables, cfg, plan=plan, rounds_per_chunk=4)
    fault = chaos.FaultPlan(
        seed=0, faults=[{"kind": "device-loss", "at": 0, "target": "7"}]
    )
    with chaos.installed(fault):
        with pytest.raises(CapacityError, match=r"\(replica \d, shard \d\)") as ei:
            runner.run(
                40 * NS_PER_MS,
                recovery=RecoveryPolicy(max_recoveries=1,
                                        snapshot_interval_chunks=2),
            )
    err = ei.value
    degraded_shards = runner.plan.shards
    assert degraded_shards < 4  # the loss really degraded the grid first
    assert err.replica is not None and 0 <= err.replica < 2
    assert err.shard is not None and 0 <= err.shard < degraded_shards
    assert err.mesh_cells and all(
        c["shard"] < degraded_shards for c in err.mesh_cells
    )
    # the terminal error still carries the device-loss degradation it
    # survived before dying (visibly-degraded contract)
    assert [r["kind"] for r in err.recoveries] == ["device-loss"]


def test_whole_batch_regrow_on_grid_reached_via_degradation():
    """Rollback-and-regrow after the grid degraded: the regrown replay
    on the smaller grid is leaf-exact vs a fault-free run that started
    at the grown capacity."""
    cfg_small, model, tables, _ = _phold_world(num_hosts=8, queue_capacity=2)
    end = 60 * NS_PER_MS
    plan = MeshPlan(replicas=2, shards=2, rows=1)
    runner = MeshRunner(
        model, tables, cfg_small, plan=plan, rounds_per_chunk=4
    )
    fault = chaos.FaultPlan(
        seed=0, faults=[{"kind": "device-loss", "at": 0, "target": "1"}]
    )
    with chaos.installed(fault):
        final = runner.run(
            end,
            recovery=RecoveryPolicy(max_recoveries=5,
                                    snapshot_interval_chunks=2),
        )
    kinds = [r["kind"] for r in runner.recovery_report]
    assert kinds[0] == "device-loss" and "capacity" in kinds
    grown_cap = next(
        r["queue_capacity"] for r in reversed(runner.recovery_report)
        if r["kind"] == "capacity"
    )
    assert grown_cap > cfg_small.queue_capacity
    assert runner.plan.devices_needed < plan.devices_needed

    cfg_big = dataclasses.replace(cfg_small, queue_capacity=grown_cap)
    ens_big = run_mesh_until(
        init_mesh_state(cfg_big, model, plan, 1),
        end, model, tables, cfg_big, plan, rounds_per_chunk=4,
    )
    _assert_batch_exact(final, ens_big, " (regrow on degraded grid)")


# --- satellite: seeded retry backoff jitter -----------------------------


def test_retry_backoff_seeded_bounded_jitter():
    from shadow_tpu.runtime.sweep import retry_backoff_s

    # deterministic: same (job, attempt) -> identical value, replay-safe
    assert retry_backoff_s(1.0, "t.ph-s3", 1) == retry_backoff_s(
        1.0, "t.ph-s3", 1
    )
    # bounded: jitter factor in [0.5, 1.5) around the exponential base
    for attempt in (1, 2, 3):
        base = 1.0 * 2 ** (attempt - 1)
        v = retry_backoff_s(1.0, "t.ph-s3", attempt)
        assert base * 0.5 <= v < base * 1.5
    # de-lockstepped: split siblings retry at different walls
    vals = {round(retry_backoff_s(1.0, f"t.ph-s{i}", 1), 6) for i in range(8)}
    assert len(vals) == 8
    # zero base stays zero (backoff disabled)
    assert retry_backoff_s(0.0, "t.ph-s3", 2) == 0.0


# --- grid-portable fingerprints + refusal UX ----------------------------


_CFG = """
general:
  stop_time: 1 s
  seed: {seed}
  {extra}
hosts:
  peer:
    network_node_id: 0
    quantity: 8
    processes:
      - path: phold
        args: {{min_delay: "2 ms", max_delay: "12 ms"}}
"""


def _cfg(seed=1, extra=""):
    from shadow_tpu.config import load_config_str

    return load_config_str(_CFG.format(seed=seed, extra=extra))


def test_fingerprint_mesh_is_layout_metadata():
    from shadow_tpu.config.fingerprint import config_fingerprint

    on_2x4 = config_fingerprint(_cfg(extra="mesh: 2x4"))
    # the same two worlds on any layout hash alike...
    assert on_2x4 == config_fingerprint(
        _cfg(extra="replicas: 2\n  mesh: 1x2")
    )
    assert on_2x4 == config_fingerprint(_cfg(extra="replicas: 2"))
    # ...but changing the number of simulated worlds still refuses
    assert on_2x4 != config_fingerprint(_cfg(extra="replicas: 3"))
    assert on_2x4 != config_fingerprint(_cfg(extra="mesh: 4x2"))  # R=4


def test_checkpoint_mismatch_names_keys_and_grids(tmp_path):
    """The resume-refusal UX satellite: a genuine world mismatch names
    the offending keys and both grids, never two opaque hashes; a
    grid-only difference is not a mismatch at all."""
    from shadow_tpu.config.fingerprint import (
        config_fingerprint,
        fingerprint_dict,
    )
    from shadow_tpu.runtime.checkpoint import (
        CheckpointError,
        CheckpointManager,
        load_checkpoint,
    )

    cfg, model, tables, st = _phold_world(num_hosts=8)
    host = state_to_host(st)
    saved_cfg = _cfg(seed=1, extra="mesh: 2x4")
    ckpt = CheckpointManager(
        str(tmp_path), 0, config_fingerprint(saved_cfg),
        layout="2x4", detail=fingerprint_dict(saved_cfg),
    )
    path = ckpt.write(host, final=True)

    # same world, different grid: loads fine (layout is metadata)
    other_grid = _cfg(seed=1, extra="replicas: 2\n  mesh: 1x2")
    load_checkpoint(
        path, st, config_fingerprint(other_grid),
        detail=fingerprint_dict(other_grid), layout="1x2",
    )

    # different world: refusal names the key and both grids
    bad = _cfg(seed=2, extra="replicas: 2\n  mesh: 1x2")
    with pytest.raises(CheckpointError) as ei:
        load_checkpoint(
            path, st, config_fingerprint(bad),
            detail=fingerprint_dict(bad), layout="1x2",
        )
    msg = str(ei.value)
    assert "general.seed: 1 != 2" in msg
    assert "grid 2x4" in msg and "grid 1x2" in msg
    assert "…" not in msg  # named keys, not truncated hashes


# --- service wiring: a device-lossy sweep batch finishes degraded -------


def test_sweep_batch_survives_device_loss(tmp_path):
    """Acceptance (service wiring): a mesh sweep batch that hits device
    loss finishes on the degraded grid instead of quarantining — every
    job done, the reshape in the batch's manifest record."""
    import json

    from shadow_tpu.runtime.cli_run import run_sweep

    base = tmp_path / "base.yaml"
    base.write_text(
        """
general:
  stop_time: 60 ms
  heartbeat_interval: null
network:
  graph:
    type: 1_gbit_switch
experimental:
  rounds_per_chunk: 4
chaos:
  faults:
    - kind: device-loss
      at: 1
      target: "1"
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
    )
    out = tmp_path / "out"
    spec = tmp_path / "sweep.yaml"
    spec.write_text(
        f"""
sweep:
  base: base.yaml
  output_dir: {out}
  capacity: 2
  mesh: 2x2
  jobs:
    - name: ph
      seed_range: [0, 2]
"""
    )
    assert run_sweep(str(spec)) == 0, "the batch must finish, not quarantine"
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["jobs_done"] == 2
    assert m["jobs_failed"] == 0 and m["jobs_quarantined"] == 0
    b = m["batches"][0]
    assert b["status"] == "done"
    assert b["recoveries"] >= 1
    assert b["mesh_effective"] != "2x2"
    assert b["mesh_degradations"][0]["grid_from"] == "2x2"
    # both jobs published standalone-shaped stats
    for job in m["jobs"]:
        assert job["status"] == "done"
        stats = json.loads(
            (pathlib.Path(job["data_directory"]) / "sim-stats.json").read_text()
        )
        assert stats["events_handled"] > 0
