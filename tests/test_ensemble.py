"""Ensemble plane (engine/ensemble.py, runtime/ensemble.py): R vmapped
replicas in one device program, with EXACT per-replica independence.

Contracts pinned here:

  * replica r of an R-replica ensemble is leaf-identical to a
    single-replica run with the derived seed (seed + r * stride) — on
    phold and tgen, plain and pump engines, tracker leaves included;
  * the pipelined ensemble driver is leaf-exact vs the synchronous one
    (per-replica quiescence rows restore now/rounds exactly);
  * a checkpoint taken mid-ensemble-run resumes to the bit-identical
    final [R, ...] state, and each resumed slice still matches its
    single-replica run;
  * one replica's capacity blowup raises a CapacityError naming the
    replica, and rollback-and-regrow recovers the WHOLE batch to a
    final state leaf-exact vs starting with the larger capacity;
  * the ensemble resolution arms the done-mask and changes nothing else
    of the config.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu.engine.ensemble import (
    ensemble_engine_cfg,
    grow_ensemble_state,
    init_ensemble_state,
    num_replicas,
    replica_seeds,
    replica_slice,
    run_ensemble_until,
)
from shadow_tpu.engine.round import CapacityError, bootstrap, run_until
from shadow_tpu.engine.state import init_state, state_to_host
from shadow_tpu.netstack import bw_bits_per_sec_to_refill
from shadow_tpu.simtime import NS_PER_MS


def _assert_leaves_exact(a, b, what=""):
    """Every leaf but TrackerState.flush_cols, the one that says which
    PROGRAM ran: an ensemble's flush keeps one block, the whole outbox
    (its block count would be batched: engine/round.py flush_outbox),
    where a single run takes the blocks the round needs."""
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        ks = jax.tree_util.keystr(path)
        if ks.endswith(".flush_cols"):
            assert jnp.all(la >= lb), f"mismatch{what} at {ks}"
            continue
        assert jnp.array_equal(la, lb), f"mismatch{what} at {ks}"


def _single_run(cfg, model, tables, seed, end, rounds_per_chunk, bw=None):
    """A single-replica run exactly as a user with this seed would run it."""
    rcfg = dataclasses.replace(cfg, seed=seed)
    st = init_state(
        rcfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw
    )
    st = bootstrap(st, model, rcfg)
    return run_until(st, end, model, tables, rcfg, rounds_per_chunk=rounds_per_chunk)


def test_ensemble_matches_single_phold_plain():
    cfg, model, tables, _ = _phold_world()
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 40 * NS_PER_MS
    stride = 7
    ens0 = init_ensemble_state(cfg, model, 3, stride)
    ens = run_ensemble_until(ens0, end, model, tables, cfg, rounds_per_chunk=4)
    assert num_replicas(ens) == 3
    totals = set()
    for r, seed in enumerate(replica_seeds(cfg, 3, stride)):
        single = _single_run(cfg, model, tables, seed, end, 4)
        _assert_leaves_exact(replica_slice(ens, r), single, f" (replica {r})")
        totals.add(int(single.events_handled.sum()))
    assert len(totals) > 1  # seeds actually diverged the trajectories


@pytest.mark.parametrize("engine,k", [("plain", 0), ("pump", 3)])
def test_ensemble_matches_single_tgen(engine, k):
    cfg0, model, tables, _ = _tgen_world(8, 0.02, 20_000_000, seed=3)
    cfg = dataclasses.replace(cfg0, tracker=True, engine=engine, pump_k=k)
    bw = bw_bits_per_sec_to_refill(20_000_000)
    end = 30 * NS_PER_MS
    ens0 = init_ensemble_state(
        cfg, model, 2, 3, tx_bytes_per_interval=bw, rx_bytes_per_interval=bw
    )
    ens = run_ensemble_until(ens0, end, model, tables, cfg, rounds_per_chunk=8)
    for r, seed in enumerate(replica_seeds(cfg, 2, 3)):
        single = _single_run(cfg, model, tables, seed, end, 8, bw=bw)
        _assert_leaves_exact(replica_slice(ens, r), single, f" (replica {r})")


def test_ensemble_pipelined_matches_sync():
    cfg, model, tables, _ = _phold_world(seed=17)
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 30 * NS_PER_MS
    ens0 = init_ensemble_state(cfg, model, 3, 2)
    sync = run_ensemble_until(
        ens0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=False
    )
    piped = run_ensemble_until(
        ens0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=True
    )
    assert int(piped.events_handled.sum()) > 0
    _assert_leaves_exact(sync, piped)


def test_ensemble_checkpoint_resume_exact(tmp_path):
    """A checkpoint tapped at a chunk boundary mid-ensemble-run resumes
    to the bit-identical final batch, and every resumed slice still
    matches its single-replica run — the determinism contract survives
    serializing the whole [R, ...] state."""
    from shadow_tpu.runtime.checkpoint import (
        CheckpointManager,
        StateTap,
        load_checkpoint,
    )

    cfg, model, tables, _ = _phold_world(seed=29)
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 40 * NS_PER_MS
    ens0 = init_ensemble_state(cfg, model, 2, 1)

    straight = run_ensemble_until(ens0, end, model, tables, cfg, rounds_per_chunk=4)

    ckpt = CheckpointManager(str(tmp_path), 10 * NS_PER_MS, "fp-test")
    tap = StateTap(checkpoints=ckpt)
    run_ensemble_until(
        ens0, end, model, tables, cfg, rounds_per_chunk=4, on_state=tap
    )
    assert ckpt.written, "the cadence must have written a checkpoint"

    # written[-1]: the manager prunes older checkpoints (keep=2)
    restored, meta = load_checkpoint(ckpt.written[-1], ens0, "fp-test")
    assert meta["queue_capacity"] == cfg.queue_capacity  # [-1] axis, not H
    resumed = run_ensemble_until(
        restored, end, model, tables, cfg, rounds_per_chunk=4
    )
    _assert_leaves_exact(straight, resumed)
    for r, seed in enumerate(replica_seeds(cfg, 2, 1)):
        single = _single_run(cfg, model, tables, seed, end, 4)
        _assert_leaves_exact(replica_slice(resumed, r), single, f" (replica {r})")


def test_ensemble_checkpoint_straddling_quiescence_exact(tmp_path):
    """Regression: a checkpoint that lands AFTER one replica quiesced but
    BEFORE the batch finished must still resume to the bit-identical
    final state. The early replica keeps taking idle rounds on device
    while the slow one drains, so an unpatched snapshot would bake those
    extra now/round-counter updates in (_patch_snapshot) and the resumed
    driver would re-record them (entry prefill). seed=11 + rpc=1 makes
    the replicas quiesce in different chunks, so the cadence provably
    produces a straddling checkpoint (asserted, not assumed)."""
    import numpy as np

    from shadow_tpu import equeue
    from shadow_tpu.runtime.checkpoint import (
        CheckpointManager,
        StateTap,
        load_checkpoint,
    )

    cfg, model, tables, _ = _phold_world(seed=11)
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 40 * NS_PER_MS
    ens0 = init_ensemble_state(cfg, model, 2, 1)
    ckpt = CheckpointManager(str(tmp_path), 2 * NS_PER_MS, "fp", keep=50)
    straight = run_ensemble_until(
        ens0, end, model, tables, cfg, rounds_per_chunk=1,
        on_state=StateTap(checkpoints=ckpt),
    )
    straddling = []
    for p in ckpt.written:
        st, _ = load_checkpoint(p, ens0, "fp")
        quiet = (
            np.asarray(jnp.min(equeue.next_time(st.queue), axis=-1)) >= end
        )
        if quiet.any() and not quiet.all():
            straddling.append(st)
    assert straddling, "scenario regressed: no checkpoint straddles"
    resumed = run_ensemble_until(
        straddling[-1], end, model, tables, cfg, rounds_per_chunk=1
    )
    _assert_leaves_exact(straight, resumed)


def test_ensemble_capacity_error_names_replica():
    cfg, model, tables, _ = _phold_world(queue_capacity=2)
    cfg = dataclasses.replace(cfg, outbox_capacity=1)
    ens0 = init_ensemble_state(cfg, model, 3, 1)
    with pytest.raises(CapacityError, match=r"replica \d of 3") as ei:
        run_ensemble_until(
            ens0, 40 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4
        )
    assert ei.value.replica is not None
    assert 0 <= ei.value.replica < 3


def test_ensemble_recovery_regrows_whole_batch():
    """Rollback-and-regrow through the shared recovery loop: one
    replica's overflow rolls the whole batch back, every replica's
    buffers widen together, and the recovered final state is leaf-exact
    vs an ensemble that started at the larger capacity."""
    from shadow_tpu.runtime.recovery import RecoveryPolicy, run_until_recovering

    cfg_small, model, tables, _ = _phold_world(queue_capacity=2)
    end = 60 * NS_PER_MS
    R = 2

    def factory(run_cfg):
        def run(st, on_state=None):
            return run_ensemble_until(
                st, end, model, tables, run_cfg,
                rounds_per_chunk=4, on_state=on_state,
            )

        return run

    ens_small = init_ensemble_state(cfg_small, model, R, 1)
    final, recoveries = run_until_recovering(
        ens_small,
        end,
        cfg=cfg_small,
        policy=RecoveryPolicy(max_recoveries=4, snapshot_interval_chunks=2),
        runner_factory=factory,
        grow_fn=grow_ensemble_state,
    )
    assert recoveries, "the tiny queue must have overflowed at least once"
    assert "replica" in recoveries[0]  # the record names the failing world
    grown_cap = recoveries[-1]["queue_capacity"]
    assert grown_cap > cfg_small.queue_capacity

    cfg_big = dataclasses.replace(cfg_small, queue_capacity=grown_cap)
    ens_big = run_ensemble_until(
        init_ensemble_state(cfg_big, model, R, 1),
        end, model, tables, cfg_big, rounds_per_chunk=4,
    )
    _assert_leaves_exact(final, ens_big)


def test_ensemble_cfg_keeps_the_engine_asked_for():
    cfg, _, _, _ = _phold_world()
    for asked in (
        dataclasses.replace(cfg, engine="plain", pump_k=4, exchange="all_gather"),
        dataclasses.replace(cfg, engine="pump", pump_k=4),
        cfg,
    ):
        assert not asked.ensemble
        resolved = ensemble_engine_cfg(asked)
        # the done-mask is armed, and engine, pump_k, exchange are as given
        assert resolved.ensemble
        assert dataclasses.replace(resolved, ensemble=False) == asked


def test_run_ensemble_until_rejects_single_state():
    cfg, model, tables, st0 = _phold_world()
    with pytest.raises(ValueError, match="ensemble state"):
        run_ensemble_until(st0, 10 * NS_PER_MS, model, tables, cfg)


def test_state_to_host_roundtrips_ensemble():
    cfg, model, tables, _ = _phold_world()
    ens = init_ensemble_state(cfg, model, 2, 1)
    host = state_to_host(ens)
    assert host.now.shape == (2,)
    assert host.queue.time.shape[-1] == cfg.queue_capacity
