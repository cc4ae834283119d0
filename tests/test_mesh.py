"""2-D mesh plane (engine/mesh.py, runtime/mesh.py): replicas x
host-shards on one Mesh(replica, hosts) device grid, with EXACT
per-replica independence at sharded scale.

Contracts pinned here, on the virtual 8-device CPU mesh:

  * slice r of a 2x4 mesh run is leaf-identical to the single-device
    run seeded seed + r*stride — phold and tgen, plain and pump
    engines, tracker leaves included — modulo ONLY the established
    sharded-execution deviations: the per-shard iteration diagnostics
    (iters_done / lanes_live / exch_hwm / land_hwm / land_passes / flush_cols,
    excluded by every engine-equivalence test — engine/state.py; the
    last three accumulate on each shard's local row 0, so their
    placement depends on the grid layout, and a shard's landing loop
    runs to its own busiest destination) and residual garbage in DEAD queue slots (live
    slots are compared bit-exact IN PLACE; the sharded exchange lays
    tombstone payloads differently, the same deviation
    tests/test_sharded.py accepts by comparing canonical pop order);
  * a checkpoint tapped at a mesh chunk boundary resumes to the
    bit-identical final [R, ...] batch (full leaf exactness — mesh
    resumes mesh, so even tombstones must agree);
  * a (replica, shard) capacity blowup names BOTH coordinates plus the
    saturated counter, and rollback-and-regrow regrows the whole mesh
    batch to a final state leaf-exact vs starting bigger;
  * a 4-job sweep with `mesh: 2x4` packs into ONE mesh batch, pays
    exactly one XLA compile, and each job's sim-stats.json is
    standalone-identical (the acceptance pin).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu.engine.mesh import (
    MeshPlan,
    init_mesh_state,
    mesh_engine_cfg,
    parse_mesh,
    replica_seeds,
    replica_slice,
    run_mesh_until,
)
from shadow_tpu.engine.round import CapacityError, bootstrap, run_until
from shadow_tpu.engine.state import init_state
from shadow_tpu.netstack import bw_bits_per_sec_to_refill
from shadow_tpu.simtime import NS_PER_MS, TIME_MAX


def _canon_queue(q, h):
    """Host h's live queue content in canonical (time, tie) pop order,
    every recorded field included (debug_sorted_events plus the aux
    channel). Slot ASSIGNMENT inside the queue grid is the one queue
    fact the sharded exchange lays out differently (same-time deliveries
    can land in swapped slots; tombstone payloads differ) — pop order is
    key-driven, so content-in-pop-order is the semantic contract, the
    same one tests/test_sharded.py pins."""
    time = np.asarray(q.time[h])
    tie = np.asarray(q.tie[h])
    kind = np.asarray(q.kind[h])
    data = np.asarray(q.data[h])
    aux = np.asarray(q.aux[h])
    items = sorted(
        (int(time[i]), int(tie[i]), int(kind[i]),
         tuple(int(x) for x in data[i]), int(aux[i]))
        for i in range(time.shape[0])
        if time[i] != TIME_MAX
    )
    assert len(items) == int(q.count[h])
    return items


PER_SHARD_LEAVES = (
    "iters_done", "lanes_live", "exch_hwm", "land_hwm", "land_passes", "flush_cols",
)


def per_shard_leaf(keystr: str) -> bool:
    """The leaves a comparison ACROSS planes leaves out, and nothing else:
    each shard keeps them for itself (the iteration diagnostics per row of
    its own loop, the exchange's four counts on its row 0), so a sharded
    state holds other values there than the one-chip state, tracker on or
    off. Across chunkings on one plane they are equal and stay compared."""
    return any(name in keystr for name in PER_SHARD_LEAVES)


def _assert_mesh_slice_exact(sl, single, what=""):
    """Leaf-exact comparison modulo the two sharded-execution
    deviations (module docstring): per-shard iteration diagnostics are
    skipped, and the queue grids compare as live content in canonical
    pop order (plus exact count/overflow/head_time) instead of raw slot
    layout."""
    fa = jax.tree_util.tree_leaves_with_path(sl)
    fb = jax.tree_util.tree_leaves_with_path(single)
    assert len(fa) == len(fb)
    grid_leaves = (".queue.time", ".queue.tie", ".queue.kind",
                   ".queue.data", ".queue.aux")
    for (path, la), (_, lb) in zip(fa, fb):
        ks = jax.tree_util.keystr(path)
        if per_shard_leaf(ks) or ks in grid_leaves:
            continue
        assert jnp.array_equal(la, lb), f"mismatch{what} at {ks}"
    for h in range(single.queue.num_hosts):
        assert _canon_queue(sl.queue, h) == _canon_queue(single.queue, h), (
            f"queue content mismatch{what} at host {h}"
        )


def _single_run(cfg, model, tables, seed, end, rounds_per_chunk, bw=None):
    rcfg = dataclasses.replace(cfg, seed=seed)
    st = init_state(
        rcfg, model.init(), tx_bytes_per_interval=bw, rx_bytes_per_interval=bw
    )
    st = bootstrap(st, model, rcfg)
    return run_until(st, end, model, tables, rcfg, rounds_per_chunk=rounds_per_chunk)


def test_mesh_slice_matches_single_phold_plain():
    """The tentpole pin: every replica slice of a 2x4 Mesh(replica,
    hosts) phold run equals its single-device run, tracker leaves
    included."""
    assert jax.device_count() == 8
    cfg, model, tables, _ = _phold_world(num_hosts=8)
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 40 * NS_PER_MS
    stride = 7
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    ens0 = init_mesh_state(cfg, model, plan, stride)
    ens = run_mesh_until(ens0, end, model, tables, cfg, plan, rounds_per_chunk=4)
    totals = set()
    for r, seed in enumerate(replica_seeds(cfg, 2, stride)):
        single = _single_run(cfg, model, tables, seed, end, 4)
        _assert_mesh_slice_exact(replica_slice(ens, r), single, f" (replica {r})")
        totals.add(int(single.events_handled.sum()))
    assert len(totals) > 1  # seeds actually diverged the trajectories


def test_mesh_slice_matches_single_tgen_pump():
    """The full simulated stack (TCP + netstack shaping, pump engine,
    deliver-lanes exchange grid) through a 2x4 mesh carrying FOUR
    replicas (two vmapped per mesh row) — every slice standalone-exact."""
    assert jax.device_count() == 8
    cfg0, model, tables, _ = _tgen_world(8, 0.02, 20_000_000, seed=3)
    cfg = dataclasses.replace(cfg0, tracker=True, engine="pump", pump_k=3)
    bw = bw_bits_per_sec_to_refill(20_000_000)
    end = 30 * NS_PER_MS
    plan = MeshPlan(replicas=4, shards=4, rows=2)
    assert plan.local_replicas == 2
    ens0 = init_mesh_state(
        cfg, model, plan, 3, tx_bytes_per_interval=bw, rx_bytes_per_interval=bw
    )
    ens = run_mesh_until(ens0, end, model, tables, cfg, plan, rounds_per_chunk=8)
    for r, seed in enumerate(replica_seeds(cfg, 4, 3)):
        single = _single_run(cfg, model, tables, seed, end, 8, bw=bw)
        _assert_mesh_slice_exact(replica_slice(ens, r), single, f" (replica {r})")


def test_mesh_checkpoint_resume_exact(tmp_path):
    """A checkpoint tapped at a mesh chunk boundary resumes to the
    bit-identical final batch — FULL leaf exactness here (mesh resumes
    mesh: even tombstone garbage is deterministic), through the same
    CheckpointManager/StateTap machinery every other plane uses."""
    from shadow_tpu.runtime.checkpoint import (
        CheckpointManager,
        StateTap,
        load_checkpoint,
    )

    cfg, model, tables, _ = _phold_world(num_hosts=8, seed=29)
    cfg = dataclasses.replace(cfg, tracker=True)
    end = 40 * NS_PER_MS
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    ens0 = init_mesh_state(cfg, model, plan, 1)

    straight = run_mesh_until(ens0, end, model, tables, cfg, plan, rounds_per_chunk=4)

    ckpt = CheckpointManager(str(tmp_path), 10 * NS_PER_MS, "fp-mesh")
    tap = StateTap(checkpoints=ckpt)
    run_mesh_until(
        ens0, end, model, tables, cfg, plan, rounds_per_chunk=4, on_state=tap
    )
    assert ckpt.written, "the cadence must have written a checkpoint"
    restored, meta = load_checkpoint(ckpt.written[-1], ens0, "fp-mesh")
    assert meta["queue_capacity"] == cfg.queue_capacity
    resumed = run_mesh_until(
        restored, end, model, tables, cfg, plan, rounds_per_chunk=4
    )
    for (path, la), lb in zip(
        jax.tree_util.tree_leaves_with_path(straight), jax.tree.leaves(resumed)
    ):
        assert jnp.array_equal(la, lb), (
            f"resume mismatch at {jax.tree_util.keystr(path)}"
        )


def test_mesh_capacity_error_names_replica_and_shard():
    """A saturated cell names BOTH mesh coordinates — (replica, shard)
    plus the saturated counter split — not just whichever plane raised
    first."""
    cfg, model, tables, _ = _phold_world(num_hosts=8, queue_capacity=2)
    cfg = dataclasses.replace(cfg, outbox_capacity=1)
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    ens0 = init_mesh_state(cfg, model, plan, 1)
    with pytest.raises(CapacityError, match=r"\(replica \d, shard \d\)") as ei:
        run_mesh_until(
            ens0, 40 * NS_PER_MS, model, tables, cfg, plan, rounds_per_chunk=4
        )
    err = ei.value
    assert err.replica is not None and 0 <= err.replica < 2
    assert err.shard is not None and 0 <= err.shard < 4
    assert err.queue_overflow or err.outbox_overflow  # the counter split
    assert err.mesh_cells and all(
        {"replica", "shard", "queue_overflow", "outbox_overflow"}
        <= set(c) for c in err.mesh_cells
    )


def test_mesh_recovery_regrows_whole_batch():
    """One cell's overflow rolls the WHOLE mesh batch back, every
    replica's buffers widen together, and the recovered final state is
    leaf-exact vs a mesh run that started at the larger capacity."""
    from shadow_tpu.runtime.mesh import grow_mesh_state
    from shadow_tpu.runtime.recovery import RecoveryPolicy, run_until_recovering

    cfg_small, model, tables, _ = _phold_world(num_hosts=8, queue_capacity=2)
    end = 60 * NS_PER_MS
    plan = MeshPlan(replicas=2, shards=4, rows=2)

    def factory(run_cfg):
        def run(st, on_state=None):
            return run_mesh_until(
                st, end, model, tables, run_cfg, plan,
                rounds_per_chunk=4, on_state=on_state,
            )

        return run

    ens_small = init_mesh_state(cfg_small, model, plan, 1)
    final, recoveries = run_until_recovering(
        ens_small,
        end,
        cfg=cfg_small,
        policy=RecoveryPolicy(max_recoveries=4, snapshot_interval_chunks=2),
        runner_factory=factory,
        grow_fn=grow_mesh_state,
    )
    assert recoveries, "the tiny queue must have overflowed at least once"
    assert "replica" in recoveries[0]
    grown_cap = recoveries[-1]["queue_capacity"]
    assert grown_cap > cfg_small.queue_capacity

    cfg_big = dataclasses.replace(cfg_small, queue_capacity=grown_cap)
    ens_big = run_mesh_until(
        init_mesh_state(cfg_big, model, plan, 1),
        end, model, tables, cfg_big, plan, rounds_per_chunk=4,
    )
    for (path, la), lb in zip(
        jax.tree_util.tree_leaves_with_path(final), jax.tree.leaves(ens_big)
    ):
        assert jnp.array_equal(la, lb), (
            f"regrow mismatch at {jax.tree_util.keystr(path)}"
        )


def test_mesh_plan_and_spec_validation():
    assert parse_mesh("2x4") == (2, 4)
    assert parse_mesh("1X8") == (1, 8)
    assert parse_mesh("2×4") == (2, 4)
    with pytest.raises(ValueError, match="RxS"):
        parse_mesh("2x")
    with pytest.raises(ValueError, match=">= 1"):
        parse_mesh("0x4")
    with pytest.raises(ValueError, match="multiple"):
        MeshPlan(replicas=3, shards=4, rows=2)
    # for_batch degrades rows to the largest divisor of the batch size
    assert MeshPlan.for_batch(1, 2, 4).rows == 1
    assert MeshPlan.for_batch(6, 4, 2).rows == 3
    assert MeshPlan.for_batch(8, 2, 4).local_replicas == 4
    # host-count divisibility is loud
    cfg, model, tables, _ = _phold_world(num_hosts=6)
    with pytest.raises(ValueError, match="divide evenly"):
        init_mesh_state(cfg, model, MeshPlan(replicas=2, shards=4, rows=2))
    # the exchange pin: mesh cfgs trace the all_gather exchange
    # (all_to_all has no vmap batching rule)
    assert cfg.exchange == "all_to_all"
    assert mesh_engine_cfg(cfg).exchange == "all_gather"
    assert mesh_engine_cfg(cfg).ensemble


def test_mesh_rejects_mismatched_state():
    cfg, model, tables, st0 = _phold_world(num_hosts=8)
    plan = MeshPlan(replicas=2, shards=4, rows=2)
    with pytest.raises(ValueError, match="ensemble state"):
        run_mesh_until(st0, 10 * NS_PER_MS, model, tables, cfg, plan)
    ens3 = init_mesh_state(cfg, model, MeshPlan(replicas=3, shards=4, rows=3))
    with pytest.raises(ValueError, match="plan expects"):
        run_mesh_until(ens3, 10 * NS_PER_MS, model, tables, cfg, plan)


def test_cli_sweep_mesh_four_jobs_one_compile(tmp_path):
    """The acceptance pin: a 4-job sweep with `mesh: 2x4` packs into ONE
    2x4 mesh batch, pays exactly one XLA compile, and each job's
    sim-stats.json is standalone-identical to `shadow-tpu run` of that
    seed (modulo wall-clock and execution-shape counters — the
    test_sweep_cli.py comparison idiom)."""
    import json
    import pathlib

    from shadow_tpu.runtime.cli_run import run_from_config, run_sweep

    base = tmp_path / "base.yaml"
    base.write_text(
        """
general:
  stop_time: 60 ms
  heartbeat_interval: null
  tracker: true
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
    )
    out = tmp_path / "out"
    spec = tmp_path / "sweep.yaml"
    spec.write_text(
        f"""
sweep:
  base: base.yaml
  output_dir: {out}
  capacity: 4
  mesh: 2x4
  jobs:
    - name: ph
      seed_range: [0, 4]
"""
    )
    assert run_sweep(str(spec)) == 0
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["mesh"] == "2x4"
    assert m["jobs_done"] == 4
    assert len(m["batches"]) == 1 and m["batches"][0]["replicas"] == 4
    assert m["compile_cache"]["compiles"] == 1

    def _stats(path):
        s = json.loads(pathlib.Path(path).read_text())
        s.pop("wall_seconds")
        # memory prices the run's own plane (batch row vs standalone
        # shard): execution shape, not trajectory
        s.pop("memory", None)
        s.pop("device", None)  # likewise: where it ran, not what it computed
        if "tracker" in s:
            s["tracker"].pop("phases", None)
            for k in ("iters", "lanes_live", "occupancy", "land_passes"):
                s["tracker"].get("window", {}).pop(k, None)
            # the most entries ONE SHARD staged in a round: the plane's, like
            # land_passes (counted with the tracker on or off since PR 36)
            s["tracker"].get("high_water", {}).pop("exchange", None)
        return s

    # one standalone comparison in the quick tier (each run_from_config
    # pays real device time on the 870s tier-1 budget); every job's
    # stats carry trajectory counters, so the cross-seed divergence
    # check below still guards against aliased replicas
    for seed in (3,):
        d = tmp_path / f"alone-s{seed}"
        cfg = tmp_path / f"alone-s{seed}.yaml"
        cfg.write_text(
            base.read_text().replace(
                "general:",
                f"general:\n  seed: {seed}\n  data_directory: {d}",
            )
        )
        assert run_from_config(str(cfg)) == 0
        job = _stats(out / "jobs" / f"ph-s{seed}" / "sim-stats.json")
        assert job == _stats(d / "sim-stats.json")
    events = [
        json.loads(
            (out / "jobs" / f"ph-s{s}" / "sim-stats.json").read_text()
        )["events_handled"]
        for s in range(4)
    ]
    assert all(e > 0 for e in events) and len(set(events)) > 1
