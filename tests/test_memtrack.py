"""Memory observatory (docs/observability.md; runtime/memtrack.py).

The contracts under test:

  * **exact static pricing** — price_state's total equals the literal
    sum of leaf nbytes (typed PRNG keys priced as their raw key words)
    on all three planes (single, ensemble [R], mesh — which shares the
    ensemble pytree), and abstract jax.eval_shape pytrees price
    identically to concrete ones, so `shadow-tpu mem` never allocates;
  * **exact regrow projection** — price_regrow matches what grow_state
    actually allocates, and max_hosts_for_budget is monotone;
  * **zero extra device syncs** — the flight recorder's device-memory
    sampling is a pure host call: not one `jax.device_get`, and a
    backend without memory_stats (CPU) disables itself after one probe;
  * **priced failures** — a CapacityError carries the saturated
    buffer's current/post-regrow bytes, and a capacity recovery record
    carries the full state's priced current/post-regrow bytes.
"""

import json
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_pipeline import _phold_world  # noqa: E402

from shadow_tpu.engine.state import (  # noqa: E402
    fmt_bytes,
    grow_state,
    init_state,
    leaf_nbytes,
    tree_nbytes,
)
from shadow_tpu.runtime import memtrack  # noqa: E402
from shadow_tpu.simtime import NS_PER_MS  # noqa: E402

pytestmark = pytest.mark.metrics


def _manual_nbytes(tree) -> int:
    """The reference total: literal leaf nbytes, typed PRNG key leaves
    measured as their raw key words (independent of leaf_nbytes)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        try:
            total += leaf.nbytes
        except Exception:  # typed PRNG key arrays
            total += jax.random.key_data(leaf).nbytes
    return int(total)


# ---- static pricing exactness -------------------------------------------


def test_price_state_exact_single_plane():
    cfg, _model, _tables, st0 = _phold_world()
    report = memtrack.price_state(st0)
    assert report["total_bytes"] == _manual_nbytes(st0) == tree_nbytes(st0)
    assert report["num_hosts"] == cfg.num_hosts
    assert report["replicas"] == 1
    # group totals partition the state: nothing dropped, nothing counted
    # twice
    assert sum(g["bytes"] for g in report["groups"].values()) == report[
        "total_bytes"
    ]
    # the dominant grid on any phold world is the queue's [H, C] rows
    assert report["dominant"]["name"].startswith("queue.")


def test_price_state_exact_ensemble_and_mesh_planes():
    from shadow_tpu.engine.ensemble import init_ensemble_state
    from shadow_tpu.engine.mesh import MeshPlan, init_mesh_state

    cfg, model, _tables, _st0 = _phold_world(num_hosts=4)
    ens = init_ensemble_state(cfg, model, 3, 1)
    rep = memtrack.price_state(ens)
    assert rep["total_bytes"] == _manual_nbytes(ens)
    assert rep["replicas"] == 3
    assert rep["num_hosts"] == 4

    # the mesh plane is BY CONSTRUCTION the ensemble pytree (mesh.py
    # init_mesh_state), so its pricing is the same exactness claim
    msh = init_mesh_state(cfg, model, MeshPlan(replicas=2, shards=2, rows=1))
    rep = memtrack.price_state(msh)
    assert rep["total_bytes"] == _manual_nbytes(msh)
    assert rep["replicas"] == 2


def test_price_state_abstract_equals_concrete():
    """`shadow-tpu mem` prices under jax.eval_shape: the abstract pytree
    must price byte-identical to the allocated one."""
    cfg, model, _tables, _st0 = _phold_world(num_hosts=4)
    concrete = init_state(cfg, model.init())
    abstract = jax.eval_shape(lambda: init_state(cfg, model.init()))
    assert (
        memtrack.price_state(abstract)["total_bytes"]
        == memtrack.price_state(concrete)["total_bytes"]
        == _manual_nbytes(concrete)
    )


def test_price_regrow_matches_grow_state():
    cfg, _model, _tables, st0 = _phold_world(num_hosts=4)
    q2, ob2 = cfg.queue_capacity * 2, 16
    projected = memtrack.price_regrow(st0, queue_capacity=q2,
                                      outbox_capacity=ob2)
    grown = grow_state(st0, queue_capacity=q2, outbox_capacity=ob2)
    assert projected == _manual_nbytes(grown)
    assert projected > tree_nbytes(st0)
    # a no-op regrow projects the current total
    assert memtrack.price_regrow(st0) == tree_nbytes(st0)


def test_max_hosts_for_budget_monotone():
    cfg, _model, _tables, st0 = _phold_world()
    report = memtrack.price_state(st0)
    budgets = [2**20, 2**24, 2**28, 2**32]
    fits = [memtrack.max_hosts_for_budget(report, b) for b in budgets]
    assert fits == sorted(fits)
    assert fits[-1] > fits[0] > 0
    assert memtrack.max_hosts_for_budget(report, 0) == 0


def test_render_report_table():
    cfg, _model, _tables, st0 = _phold_world()
    report = memtrack.price_state(st0)
    text = memtrack.render_report(report, hbm_gb=16)
    assert "dominant grid:" in text
    assert "queue" in text and "outbox" in text
    assert fmt_bytes(report["total_bytes"]) in text
    assert "16 GiB" in text  # the projection line


def test_leaf_nbytes_prices_key_leaves():
    key = jax.random.key(0)
    assert leaf_nbytes(key) == jax.random.key_data(key).nbytes
    abstract = jax.eval_shape(lambda: jax.random.key(0))
    assert leaf_nbytes(abstract) == leaf_nbytes(key)


# ---- live sampling: zero syncs, backend-tolerant ------------------------


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def _probe(**kw):
    import dataclasses

    from shadow_tpu.engine.round import ChunkProbe

    fields = {f.name: 0 for f in dataclasses.fields(ChunkProbe)}
    fields.update(kw)
    return ChunkProbe(**fields)


def test_device_memory_sampling_zero_fetches_and_fields(monkeypatch):
    """With a backend that reports memory_stats, every sample carries
    bytes_in_use summed across devices and peak maxed per device — and
    the sampling path performs not one jax.device_get."""
    from shadow_tpu.runtime.flightrec import FlightRecorder

    fetches = {"n": 0}
    real = jax.device_get

    def counting(x):
        fetches["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    monkeypatch.setattr(
        jax, "local_devices",
        lambda: [
            _FakeDevice({"bytes_in_use": 100, "peak_bytes_in_use": 300,
                         "bytes_limit": 1000}),
            _FakeDevice({"bytes_in_use": 50, "peak_bytes_in_use": 700,
                         "bytes_limit": 1000}),
        ],
    )
    rec = FlightRecorder(num_hosts=8)
    for i in range(3):
        sample = rec.observe(_probe(now=(i + 1) * 1000))
    assert sample["device_bytes_in_use"] == 150  # summed
    assert sample["device_peak_bytes"] == 700  # maxed
    assert fetches["n"] == 0
    # memtrack's aggregate view sums/maxes the same way
    dm = memtrack.device_memory(devices=jax.local_devices())
    assert dm["bytes_in_use"] == 150
    assert dm["peak_bytes_in_use"] == 700
    assert dm["bytes_limit"] == 2000


def test_device_memory_sampling_disables_on_cpu(monkeypatch):
    """A backend whose devices report no memory_stats (CPU returns None)
    disables sampling after ONE probe: samples carry no device fields
    and the device list is resolved exactly once."""
    from shadow_tpu.runtime.flightrec import FlightRecorder

    calls = {"n": 0}

    def tracked():
        calls["n"] += 1
        return [_FakeDevice(None)]

    monkeypatch.setattr(jax, "local_devices", tracked)
    rec = FlightRecorder(num_hosts=8)
    for i in range(3):
        sample = rec.observe(_probe(now=(i + 1) * 1000))
    assert "device_bytes_in_use" not in sample
    assert calls["n"] == 1
    assert memtrack.device_memory(devices=[_FakeDevice(None)]) is None


def test_write_prom_carries_device_gauges(tmp_path, monkeypatch):
    from shadow_tpu.runtime.flightrec import FlightRecorder

    monkeypatch.setattr(
        jax, "local_devices",
        lambda: [_FakeDevice({"bytes_in_use": 42, "peak_bytes_in_use": 99})],
    )
    rec = FlightRecorder(num_hosts=8)
    rec.observe(_probe(now=1000))
    pp = tmp_path / "m.prom"
    assert rec.write_prom(path=str(pp)) == str(pp)
    prom = pp.read_text()
    assert "shadow_tpu_device_bytes_in_use 42" in prom
    assert "shadow_tpu_device_peak_bytes 99" in prom


# ---- priced failures ----------------------------------------------------


def test_capacity_error_carries_priced_bytes():
    from shadow_tpu.engine.round import CapacityError, attach_capacity_bytes

    _cfg, _model, _tables, st0 = _phold_world(num_hosts=4)
    err = CapacityError("saturated")
    err.queue_overflow, err.outbox_overflow = 3, 0
    attach_capacity_bytes(err, st0)
    assert err.bytes_current > 0
    # only the queue was saturated: its x2 regrow doubles the capacity-
    # axis grids but not the per-host counters, so strictly between 1x
    # and 2x
    assert err.bytes_current < err.bytes_regrown < 2 * err.bytes_current
    assert "saturated buffer bytes" in str(err)
    assert fmt_bytes(err.bytes_current) in str(err)


def test_capacity_recovery_record_carries_priced_bytes():
    """The rollback-and-regrow record prices the full state before and
    after the double it applied — the headroom figures sim-stats and the
    recovery log line publish. Reuses the queue_capacity=2 world
    test_robustness compiles."""
    from shadow_tpu.runtime.recovery import (
        RecoveryPolicy,
        run_until_recovering,
    )

    cfg, model, tables, st0 = _phold_world(queue_capacity=2)
    _final, recoveries = run_until_recovering(
        st0, 60 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4,
        policy=RecoveryPolicy(max_recoveries=4, snapshot_interval_chunks=2),
    )
    assert recoveries
    rec = recoveries[0]
    assert rec["kind"] == "capacity"
    assert rec["bytes_current"] > 0
    assert rec["bytes_regrown"] > rec["bytes_current"]
    # the projection priced BEFORE growing matches the regrown shapes:
    # recompute it from a fresh world of the same seed capacity
    projected = memtrack.price_regrow(
        st0,
        queue_capacity=rec["queue_capacity"],
        outbox_capacity=rec["outbox_capacity"],
    )
    assert rec["bytes_regrown"] == projected


# ---- CLI + sim-stats surfaces -------------------------------------------

CONFIG = """
general:
  stop_time: 60 ms
  seed: 1
  data_directory: {data_dir}
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
    # 12 hosts matches test_metrics_cli / test_checkpoint_cli exactly,
    # so the run-backed smoke below reuses their compiled chunk program
    # from the process-wide jit cache
    quantity: 12
    processes:
      - path: phold
        args:
          min_delay: "2 ms"
          max_delay: "12 ms"
"""


def _write(tmp_path) -> pathlib.Path:
    d = tmp_path / "mem"
    d.mkdir()
    cfg = d / "shadow.yaml"
    cfg.write_text(CONFIG.format(data_dir=d / "data"))
    return cfg


def test_cli_mem_prices_without_compiling(tmp_path, capsys):
    """`shadow-tpu mem` prints the table (dominant grid line included)
    and the --json report's total matches the exact leaf pricing of the
    state the run would allocate."""
    from shadow_tpu.cli import main as cli_main

    cfg_path = _write(tmp_path)
    assert cli_main(["mem", str(cfg_path), "--hbm-gb", "16"]) == 0
    out = capsys.readouterr().out
    assert "memory: 12 hosts" in out
    assert "dominant grid:" in out
    assert "hosts fit in 16 GiB HBM" in out

    assert cli_main(["mem", str(cfg_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["num_hosts"] == 12
    assert report["total_bytes"] == sum(
        g["bytes"] for g in report["groups"].values()
    )
    # the ensemble plane prices [R] rows of the same world
    assert cli_main(["mem", str(cfg_path), "--replicas", "3", "--json"]) == 0
    rep3 = json.loads(capsys.readouterr().out)
    assert rep3["replicas"] == 3
    assert rep3["total_bytes"] > report["total_bytes"]

    # user mistakes stay one-line errors, never tracebacks
    assert cli_main(["mem", str(tmp_path / "nope.yaml")]) == 1
    assert "shadow-tpu: error:" in capsys.readouterr().err


def test_sim_stats_carries_memory_section(tmp_path):
    """A completed run's sim-stats.json prices its final state: the
    memory block's total is the exact leaf pricing, grouped by
    subsystem, with the dominant grid named."""
    from shadow_tpu.runtime.cli_run import run_from_config

    cfg_path = _write(tmp_path)
    assert run_from_config(str(cfg_path)) == 0
    stats = json.loads(
        (tmp_path / "mem" / "data" / "sim-stats.json").read_text()
    )
    mem = stats["memory"]
    assert mem["num_hosts"] == 12
    assert mem["total_bytes"] == sum(mem["groups"].values())
    assert mem["dominant"]["name"].startswith("queue.")
    assert mem["bytes_per_host"] > 0
