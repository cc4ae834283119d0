"""Tier-1 CLI smoke for the sweep scheduler service (docs/service.md):

* a 3-job, two-priority sweep runs end to end with ONE mid-run
  preemption — the low-priority batch checkpoints when the
  high-priority job arrives on the service clock, the high-priority job
  runs, the batch resumes — and every job's published sim-stats.json is
  leaf-identical to running that seed standalone through `shadow-tpu
  run` (modulo wall-clock fields), preempted-then-resumed jobs
  included;
* an 8-job seed sweep (identical shapes) pays exactly ONE XLA compile
  (the compile-cache counter published in sweep-manifest.json);
* --show-plan prints the packing decision without running;
* spec mistakes surface as one-line CliUserErrors.
"""

import json
import pathlib

import pytest

from shadow_tpu.runtime.cli_run import CliUserError, run_from_config, run_sweep

BASE = """
general:
  stop_time: {stop}
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


def _write_base(tmp_path, stop="120 ms") -> pathlib.Path:
    p = tmp_path / "base.yaml"
    p.write_text(BASE.format(stop=stop))
    return p


def _stats(path) -> dict:
    """sim-stats.json modulo the wall-clock fields (the established
    comparison idiom — tests/test_checkpoint_cli.py does the same) and
    the execution-shape counters: a standalone run shards across this
    box's 8 XLA host devices (per-shard drain loops, psum'd iters_done)
    while a sweep job runs inside a single-device ensemble batch (joint
    iterations across hosts), so drain-iteration counts and the
    occupancy derived from them legitimately differ — like `phases`,
    they describe HOW the trajectory was executed, not the trajectory.
    The window-width facts (win_ns_sum / mean_ns) are mesh-uniform and
    stay compared."""
    s = json.loads(pathlib.Path(path).read_text())
    s.pop("wall_seconds")
    # the memory section prices the run's OWN device footprint (sharded
    # single state vs ensemble batch row): execution shape, not trajectory
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


def _standalone(tmp_path, base: pathlib.Path, seed: int, stop="120 ms") -> dict:
    d = tmp_path / f"alone-s{seed}"
    cfg = tmp_path / f"alone-s{seed}.yaml"
    cfg.write_text(
        base.read_text().replace(
            "general:",
            f"general:\n  seed: {seed}\n  data_directory: {d}",
        )
    )
    assert run_from_config(str(cfg)) == 0
    return _stats(d / "sim-stats.json")


def test_cli_sweep_preempt_resume_matches_standalone(tmp_path):
    """The acceptance pin: a preempted-then-resumed job's sim-stats.json
    is identical to its uninterrupted standalone run (modulo wall), and
    the resume reuses the cached executable instead of recompiling."""
    base = _write_base(tmp_path)
    out = tmp_path / "out"
    spec = tmp_path / "sweep.yaml"
    spec.write_text(
        f"""
sweep:
  name: preempt
  base: base.yaml
  output_dir: {out}
  jobs:
    - name: lo
      seeds: [0, 1]
      priority: 0
    - name: hi
      seeds: [7]
      priority: 10
      arrival: 40 ms
"""
    )
    assert run_sweep(str(spec)) == 0
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["jobs_done"] == 3 and m["jobs_failed"] == 0
    # the hi job arrived at 40 ms on the service clock, mid-lo-batch:
    # exactly one preemption, through a verified final checkpoint
    assert m["preemptions"] == 1
    lo_batch = next(b for b in m["batches"] if "lo-s0" in b["jobs"])
    assert lo_batch["preemptions"] == 1 and lo_batch["status"] == "done"
    assert sorted(lo_batch["jobs"]) == ["lo-s0", "lo-s1"]  # packed R=2
    ckpts = list((out / "batches").glob("b*/ckpts/ckpt-*.npz"))
    assert ckpts, "preemption must checkpoint through CheckpointManager"
    # compile accounting: two distinct programs (R=2 and R=1) and one
    # cache hit — the preempted batch's resume reuses its executable
    cache = m["compile_cache"]
    assert cache["compiles"] == 2 and cache["hits"] == 1

    # per-job outputs: leaf-identical to standalone runs, preempted or not
    for name, seed in (("lo-s0", 0), ("hi-s7", 7)):
        job = _stats(out / "jobs" / name / "sim-stats.json")
        assert job == _standalone(tmp_path, base, seed)
    # per-job progress streamed from the probe rows (sync-free)
    for rec in m["jobs"]:
        assert rec["progress"]["now_ns"] >= 120_000_000
        assert rec["progress"]["events"] > 0


def test_cli_sweep_eight_jobs_one_compile(tmp_path):
    """The acceptance pin: 8 same-shape jobs (seeds 0-7) pack into one
    ensemble batch and pay exactly one XLA compile."""
    _write_base(tmp_path, stop="60 ms")
    out = tmp_path / "out8"
    spec = tmp_path / "sweep8.yaml"
    spec.write_text(
        f"""
sweep:
  base: base.yaml
  output_dir: {out}
  capacity: 8
  jobs:
    - name: ph
      seed_range: [0, 8]
"""
    )
    assert run_sweep(str(spec)) == 0
    m = json.loads((out / "sweep-manifest.json").read_text())
    assert m["jobs_done"] == 8
    assert len(m["batches"]) == 1 and m["batches"][0]["replicas"] == 8
    assert m["compile_cache"]["compiles"] == 1
    # every job published its own standalone-format stats + config
    for seed in range(8):
        d = out / "jobs" / f"ph-s{seed}"
        stats = json.loads((d / "sim-stats.json").read_text())
        assert stats["scheduler"] == "tpu" and stats["events_handled"] > 0
        cfgd = json.loads((d / "processed-config.json").read_text())
        assert cfgd["general"]["seed"] == seed
    # cross-job aggregate table in the manifest
    agg = m["aggregate"]["ph"]["events_handled"]
    assert agg["min"] <= agg["mean"] <= agg["max"]


def test_cli_sweep_show_plan_packs_without_running(tmp_path, capsys):
    _write_base(tmp_path)
    spec = tmp_path / "plan.yaml"
    spec.write_text(
        f"""
sweep:
  base: base.yaml
  output_dir: {tmp_path / "never"}
  capacity: 3
  jobs:
    - name: ph
      seeds: [0, 1, 2, 3, 5, 7]
"""
    )
    assert run_sweep(str(spec), show_plan=True) == 0
    plan = json.loads(capsys.readouterr().out)
    got = [(b["base_seed"], b["replicas"], b["seed_stride"]) for b in plan["batches"]]
    # 0,1,2 fold (cap 3); 3,5,7 fold as a stride-2 progression
    assert got == [(0, 3, 1), (3, 3, 2)]
    assert not (tmp_path / "never").exists()


def test_cli_sweep_bad_specs(tmp_path):
    base = _write_base(tmp_path)
    bad = tmp_path / "bad.yaml"
    bad.write_text("sweep:\n  base: base.yaml\n")
    with pytest.raises(CliUserError, match="jobs"):
        run_sweep(str(bad))
    bad.write_text(
        "sweep:\n  base: missing.yaml\n  jobs:\n    - name: a\n      seeds: [0]\n"
    )
    with pytest.raises(CliUserError, match="invalid sweep spec"):
        run_sweep(str(bad))
    bad.write_text(
        f"""
sweep:
  base: {base.name}
  jobs:
    - name: a
      seeds: [0]
      overrides:
        general: {{replicas: 4}}
"""
    )
    with pytest.raises(CliUserError, match="replicas"):
        run_sweep(str(bad))
    # managed-executable scenarios cannot batch on device: a clean
    # one-line refusal at validation, never an internal error mid-run
    (tmp_path / "managed-base.yaml").write_text(
        """
general: {stop_time: 1 s}
hosts:
  h:
    network_node_id: 0
    processes:
      - path: /bin/true
"""
    )
    bad.write_text(
        "sweep:\n  base: managed-base.yaml\n  jobs:\n"
        "    - name: a\n      seeds: [0]\n"
    )
    with pytest.raises(CliUserError, match="scripted-model"):
        run_sweep(str(bad))
