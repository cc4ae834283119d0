"""Fault-tolerant run loop (docs/robustness.md): deterministic
checkpoint/restore and rollback-and-regrow capacity recovery.

The contract under test is the determinism invariant extended across
faults: a run interrupted at a chunk boundary and resumed from its
checkpoint must reach a final SimState (including the tracker plane)
bit-identical to an uninterrupted run, and a run that recovers from a
capacity blowup by regrowing the saturated buffer must be leaf-exact to
a run that started with the larger capacity."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from test_pipeline import _assert_leaves_exact, _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu.engine.round import CapacityError, RunInterrupted, run_until
from shadow_tpu.engine.sharded import AXIS, ShardedRunner
from shadow_tpu.engine.state import grow_state, state_from_host, state_to_host
from shadow_tpu.runtime.checkpoint import (
    CheckpointError,
    CheckpointManager,
    InterruptGuard,
    StateTap,
    load_checkpoint,
    peek_checkpoint_meta,
    resume_engine_cfg,
    save_checkpoint,
)
from shadow_tpu.runtime.recovery import (
    RecoveryPolicy,
    run_until_recovering,
)
from shadow_tpu.simtime import NS_PER_MS
from shadow_tpu.utils.tracker import Tracker


def test_state_host_roundtrip():
    """state_to_host/state_from_host is lossless, including the typed
    PRNG key leaves that numpy cannot hold natively."""
    _cfg, _model, _tables, st0 = _phold_world()
    host = state_to_host(st0)
    _assert_leaves_exact(st0, state_from_host(host, st0))


def test_checkpoint_file_roundtrip(tmp_path):
    cfg, model, tables, st0 = _phold_world()
    st = run_until(st0, 10 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4)
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, state_to_host(st), {"fingerprint": "fp", "now_ns": 1})
    restored, meta = load_checkpoint(path, st0, "fp")
    _assert_leaves_exact(st, restored)
    assert meta["fingerprint"] == "fp"
    assert meta["queue_capacity"] == cfg.queue_capacity
    # the meta is peekable without loading the leaf arrays
    assert peek_checkpoint_meta(path)["num_leaves"] == meta["num_leaves"]
    with pytest.raises(CheckpointError, match="different config"):
        load_checkpoint(path, st0, "other-fp")


def test_checkpoint_template_shape_mismatch(tmp_path):
    """A checkpoint can only restore into the exact world it came from."""
    cfg, model, tables, st0 = _phold_world(num_hosts=6)
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, state_to_host(st0), {"fingerprint": "fp"})
    _cfg2, _m2, _t2, other = _phold_world(num_hosts=4)
    with pytest.raises(CheckpointError):
        load_checkpoint(path, other, "fp")


def test_checkpoint_of_an_older_format_is_refused(tmp_path, monkeypatch):
    """A file of the version before this build's is refused by its version,
    in words that name both, before any leaf is read."""
    from shadow_tpu.runtime import checkpoint

    now = checkpoint.CHECKPOINT_VERSION
    _cfg, _model, _tables, st0 = _phold_world()
    path = str(tmp_path / "ckpt.npz")
    monkeypatch.setattr(checkpoint, "CHECKPOINT_VERSION", now - 1)
    save_checkpoint(path, state_to_host(st0), {"fingerprint": "fp"})
    monkeypatch.undo()
    with pytest.raises(CheckpointError, match=f"format version {now - 1}.*reads version {now}"):
        load_checkpoint(path, st0, "fp")


def _interrupt_then_resume(cfg, model, tables, st0, end, ckpt_dir,
                           interval_ns, interrupt_at_ns, rpc=4):
    """Drive run_until with a checkpoint tap until the (deterministic)
    test interrupt fires, then restore the newest checkpoint and run it
    to completion. Returns the resumed final state."""
    ck = CheckpointManager(str(ckpt_dir), interval_ns, "fp")
    guard = InterruptGuard(test_interrupt_at_ns=interrupt_at_ns)
    tap = StateTap(checkpoints=ck, guard=guard)
    with pytest.raises(RunInterrupted):
        run_until(st0, end, model, tables, cfg, rounds_per_chunk=rpc,
                  on_state=tap)
    path = CheckpointManager.latest_path(str(ckpt_dir))
    assert path is not None
    restored, meta = load_checkpoint(path, st0, "fp")
    assert 0 < meta["now_ns"] < end
    return run_until(restored, end, model, tables, cfg, rounds_per_chunk=rpc)


@pytest.mark.parametrize("tracker_on", [False, True])
def test_interrupt_resume_bit_exact_phold(tmp_path, tracker_on):
    """Kill-mid-run → resume reaches a bit-identical final state — with
    the device tracker plane both off and on (the tracker leaves ride
    the checkpoint and must stay trajectory-exact too)."""
    cfg, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg, tracker=tracker_on)
    end = 40 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    resumed = _interrupt_then_resume(
        cfg, model, tables, st0, end, tmp_path,
        interval_ns=8 * NS_PER_MS, interrupt_at_ns=20 * NS_PER_MS,
    )
    assert int(resumed.events_handled.sum()) > 0
    _assert_leaves_exact(straight, resumed)


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["plain", "pump"])
def test_interrupt_resume_bit_exact_tgen(tmp_path, engine):
    """Resume bit-exactness on the flagship TCP workload, per engine
    (slow tier: each engine compiles its own chunk executable twice; the
    tier-1 resume coverage is the phold tracker-on/off pair above)."""
    cfg0, model, tables, st0 = _tgen_world(8, 0.02, 20_000_000, seed=3)
    cfg = (
        dataclasses.replace(cfg0, engine="plain")
        if engine == "plain"
        else dataclasses.replace(cfg0, engine=engine, pump_k=3)
    )
    end = 30 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=2)
    resumed = _interrupt_then_resume(
        cfg, model, tables, st0, end, tmp_path,
        interval_ns=4 * NS_PER_MS, interrupt_at_ns=10 * NS_PER_MS, rpc=2,
    )
    assert int(resumed.events_handled.sum()) > 0
    _assert_leaves_exact(straight, resumed)


def test_resume_ignores_retired_pool_capacity_field(tmp_path):
    """A checkpoint written before the segment exchange was retired carries
    `pool_capacity: 0` in its meta (the front door could set no other
    value). Resume rebuilds the engine config from the meta's width knobs
    (Manager and the sweep, through resume_engine_cfg): it passes that
    field by, and the resumed run is leaf-exact to the uninterrupted one."""
    import json

    import numpy as np

    cfg, model, tables, st0 = _phold_world()
    end = 60 * NS_PER_MS
    straight = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)

    ck = CheckpointManager(str(tmp_path), 10 * NS_PER_MS, "fp")
    ck.engine_cfg = cfg  # as run_until_recovering sets it: meta records the knobs
    tap = StateTap(checkpoints=ck, guard=InterruptGuard(test_interrupt_at_ns=25 * NS_PER_MS))
    with pytest.raises(RunInterrupted):
        run_until(st0, end, model, tables, cfg, rounds_per_chunk=4, on_state=tap)
    path = CheckpointManager.latest_path(str(tmp_path))
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays["__meta__"][()]))
    assert "a2a_capacity" in meta and "pool_capacity" not in meta
    arrays["__meta__"] = np.asarray(json.dumps({**meta, "pool_capacity": 0}))
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    assert peek_checkpoint_meta(path)["pool_capacity"] == 0

    assert resume_engine_cfg(path, cfg) == cfg
    grown = dataclasses.replace(cfg, queue_capacity=2 * cfg.queue_capacity, a2a_capacity=7)
    assert resume_engine_cfg(path, grown) == cfg  # the recorded widths win
    restored, _ = load_checkpoint(path, st0, "fp")
    resumed = run_until(restored, end, model, tables, cfg, rounds_per_chunk=4)
    assert int(resumed.events_handled.sum()) > 0
    _assert_leaves_exact(straight, resumed)


@pytest.mark.slow
def test_interrupt_resume_bit_exact_sharded(tmp_path):
    """Resume through the sharded driver: the checkpoint is written from
    the (gathered) sharded state and restored into a re-sharded run."""
    import numpy as np
    from jax.sharding import Mesh

    cfg, model, tables, st0 = _phold_world(num_hosts=8)
    end = 40 * NS_PER_MS
    mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)
    straight = runner.run_until(st0, end)

    ck = CheckpointManager(str(tmp_path), 8 * NS_PER_MS, "fp")
    guard = InterruptGuard(test_interrupt_at_ns=20 * NS_PER_MS)
    runner2 = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)
    with pytest.raises(RunInterrupted):
        runner2.run_until(st0, end, on_state=StateTap(checkpoints=ck, guard=guard))
    restored, _meta = load_checkpoint(
        CheckpointManager.latest_path(str(tmp_path)), st0, "fp"
    )
    runner3 = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)
    resumed = runner3.run_until(restored, end)
    _assert_leaves_exact(straight, resumed)


def test_grow_state_preserves_contents():
    cfg, model, tables, st0 = _phold_world()
    st = run_until(st0, 10 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4)
    grown = grow_state(st, queue_capacity=cfg.queue_capacity * 2,
                       outbox_capacity=16)
    assert grown.queue.capacity == cfg.queue_capacity * 2
    assert grown.outbox.valid.shape[1] == 16
    old = cfg.queue_capacity
    assert jnp.array_equal(grown.queue.time[:, :old], st.queue.time[:, :old])
    assert jnp.array_equal(grown.queue.count, st.queue.count)
    assert jnp.array_equal(grown.queue.head_time, st.queue.head_time)
    # new slots read as canonical free slots
    assert bool(jnp.all(grown.queue.time[:, old:] == grown.queue.time.max()))
    with pytest.raises(ValueError, match="shrink"):
        grow_state(st, queue_capacity=old - 1)


def test_regrow_recovers_leaf_exact():
    """A workload sized to overflow the seed queue capacity completes via
    rollback-and-regrow, the recovery is visible in the tracker fold, and
    the trajectory is leaf-exact vs a run that STARTED with the grown
    capacity."""
    cfg, model, tables, st0 = _phold_world(queue_capacity=2)
    end = 60 * NS_PER_MS
    with pytest.raises(CapacityError):
        run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)

    tracker = Tracker()
    final, recoveries = run_until_recovering(
        st0, end, model, tables, cfg, rounds_per_chunk=4, tracker=tracker,
        policy=RecoveryPolicy(max_recoveries=4, snapshot_interval_chunks=2),
    )
    assert len(recoveries) >= 1
    assert recoveries[0]["queue_overflow"] > 0
    assert tracker.stats_dict()["recoveries"] == recoveries
    grown_cap = final.queue.capacity
    assert grown_cap > 2

    cfg2, model2, tables2, st2 = _phold_world(queue_capacity=grown_cap)
    reference = run_until(st2, end, model2, tables2, cfg2, rounds_per_chunk=4)
    _assert_leaves_exact(reference, final)


def test_recovery_budget_exhausted_raises():
    """max_recoveries=0 is fail-fast (--no-recover): the original
    CapacityError surfaces unchanged."""
    cfg, model, tables, st0 = _phold_world(queue_capacity=2)
    with pytest.raises(CapacityError):
        run_until_recovering(
            st0, 60 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4,
            policy=RecoveryPolicy(max_recoveries=0),
        )


@pytest.mark.slow
def test_sharded_capacity_error_names_shard():
    """The sharded probe arrives mesh-summed; the CapacityError must
    still say WHICH shard saturated (per-shard overflow fetched on the
    failure path only)."""
    import numpy as np
    from jax.sharding import Mesh

    cfg, model, tables, st0 = _phold_world(num_hosts=8)
    mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)
    # seed overflow on a host row owned by shard 2 (rows 4-5 of 8 over 4)
    bad = st0.replace(
        queue=st0.queue.replace(overflow=st0.queue.overflow.at[4].add(3))
    )
    with pytest.raises(CapacityError, match="shard 2") as ei:
        runner.run_until(bad, 400 * NS_PER_MS)
    assert "shard 2" in (ei.value.shard_detail or "")
    assert "shard 0" not in (ei.value.shard_detail or "")
