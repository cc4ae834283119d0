"""Tier-1 CLI smoke for the elastic mesh (ISSUE 15 acceptance):

  * a checkpoint written mid-run on a 2x4 mesh resumes `--mesh 1x2`
    AND single-device (pure ensemble), each publishing sim-stats.json
    identical to the uninterrupted 2x4 run's modulo wall- and
    execution-shape fields — execution geometry is an implementation
    detail;
  * an injected `device-loss` fault mid-run completes on a degraded
    grid, leaf-exact vs fault-free, with the reshape visible in the
    `recovery` and `mesh` sections.
"""

import json
import pathlib
import shutil

import pytest

from shadow_tpu.runtime.cli_run import CliUserError, run_from_config

CONFIG = """
general:
  stop_time: 160 ms
  seed: 5
  data_directory: {data_dir}
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


def _write(tmp_path, name) -> pathlib.Path:
    d = tmp_path / name
    d.mkdir()
    cfg = d / "shadow.yaml"
    cfg.write_text(CONFIG.format(data_dir=d / "data"))
    return cfg


def _stats(cfg_path: pathlib.Path) -> dict:
    """sim-stats.json minus wall-clock and execution-shape fields: the
    grid/scheduler/wall facts legitimately differ across layouts; every
    simulated-world fact must not."""
    stats = json.loads(
        (cfg_path.parent / "data" / "sim-stats.json").read_text()
    )
    for k in ("wall_seconds", "scheduler", "mesh", "recovery", "degraded",
              "chaos", "metrics", "autotune", "memory", "device"):
        stats.pop(k, None)
    ens = stats.get("ensemble")
    if ens:
        for k in ("wall_seconds", "wall_seconds_per_replica",
                  "sim_sec_per_wall_sec_per_replica"):
            ens.pop(k, None)
        (ens.get("aggregate") or {}).pop("events_per_wall_second", None)
    return stats


def test_cli_mesh_checkpoint_resumes_on_any_grid(tmp_path, monkeypatch):
    """The acceptance smoke: write a 2x4 checkpoint mid-run, resume it
    on 1x2 and on a single device, and get the uninterrupted run's
    stats each time."""
    # uninterrupted 2x4 reference
    ref_cfg = _write(tmp_path, "ref")
    assert run_from_config(str(ref_cfg), mesh="2x4") == 0
    ref = _stats(ref_cfg)
    assert ref["events_handled"] > 0
    assert len(ref["ensemble"]["per_replica"]) == 2

    # interrupted 2x4 run leaves a mid-run checkpoint behind
    run_cfg = _write(tmp_path, "run")
    ckpt_dir = tmp_path / "ckpts"
    monkeypatch.setenv("SHADOW_TPU_TEST_INTERRUPT_AT_NS", str(80_000_000))
    rc = run_from_config(
        str(run_cfg), mesh="2x4",
        checkpoint_dir=str(ckpt_dir), checkpoint_interval="40 ms",
    )
    assert rc == 130
    monkeypatch.delenv("SHADOW_TPU_TEST_INTERRUPT_AT_NS")
    written = sorted(ckpt_dir.glob("ckpt-*.npz"))
    assert written, "interrupt must leave a checkpoint behind"
    meta = json.loads(__import__("numpy").load(written[-1])["__meta__"][()])
    assert meta["mesh"] == "2x4"  # layout metadata, not part of the hash

    # resume the SAME snapshot on two other grids (each from its own
    # copy of the dir — a completed resume writes newer checkpoints)
    for name, kwargs in (
        ("r1x2", dict(mesh="1x2", replicas=2)),
        ("rsingle", dict(replicas=2)),  # single device, pure ensemble
    ):
        cdir = tmp_path / f"ckpts-{name}"
        shutil.copytree(ckpt_dir, cdir)
        cfg = _write(tmp_path, name)
        rc = run_from_config(
            str(cfg), checkpoint_dir=str(cdir), resume=True, **kwargs
        )
        assert rc == 0, name
        assert _stats(cfg) == ref, (
            f"resume on {kwargs} must reproduce the 2x4 run's stats"
        )

    # a genuinely different world still refuses, naming the key
    bad = _write(tmp_path, "bad")
    with pytest.raises(CliUserError, match=r"general\.replicas: 2 != 4"):
        run_from_config(
            str(bad), checkpoint_dir=str(ckpt_dir), resume=True,
            mesh="1x2", replicas=4,
        )


def test_cli_device_loss_completes_on_degraded_grid(tmp_path):
    """Acceptance: an injected device-loss mid-run finishes the run on
    a degraded grid with fault-free results, visibly degraded in
    sim-stats.json."""
    ref_cfg = _write(tmp_path, "clean")
    assert run_from_config(str(ref_cfg), mesh="2x4") == 0
    ref = _stats(ref_cfg)

    cfg = _write(tmp_path, "lossy")
    rc = run_from_config(
        str(cfg), mesh="2x4",
        chaos_faults=["device-loss@1:target=3"],
    )
    assert rc == 0
    raw = json.loads((cfg.parent / "data" / "sim-stats.json").read_text())
    mesh = raw["mesh"]
    assert mesh["requested"] == "2x4"
    assert mesh["effective"] != "2x4"
    assert mesh["degradations"][0]["grid_from"] == "2x4"
    rec = raw["recovery"]["events"][0]
    assert rec["kind"] == "device-loss" and rec["injected"]
    assert rec["device"] == 3 and rec["grid_to"] == mesh["effective"]
    assert raw["chaos"]["fired"] == [
        {"kind": "device-loss", "at": 1, "target": "3"}
    ]
    assert _stats(cfg) == ref, "degraded results must equal fault-free"
