"""The front door's chip-facing seams (PR 22): what `engine: auto` means,
where JAX's persistent compilation cache lives, the `device` block a run
publishes, fail-fast under --no-recover, and chip_smoke.py's rehearsal."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from shadow_tpu.engine import EngineConfig
from shadow_tpu.engine.round import EngineCompileError, effective_engine
from shadow_tpu.runtime import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHOLD = """
general:
  stop_time: "60 ms"
  seed: 3
  parallelism: 1
  data_directory: {data_dir}
network:
  graph:
    type: 1_gbit_switch
experimental:
  rounds_per_chunk: 8
  {experimental}
hosts:
  peer:
    network_node_id: 0
    quantity: 8
    processes:
      - path: phold
        args: {{ min_delay: "2 ms", max_delay: "12 ms" }}
{chaos}
"""


# --- what `auto` means --------------------------------------------------


def test_auto_on_a_tpu_is_an_engine_the_chip_compiles(monkeypatch):
    """On a backend that calls itself `tpu`, auto is pump (pump_k > 0) or
    plain — the two whose chunk programs the chip's compiler accepted
    (tools/compile_for_chip.py)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = EngineConfig(num_hosts=8)
    assert effective_engine(cfg) == "plain"
    assert effective_engine(dataclasses.replace(cfg, pump_k=4)) == "pump"
    assert effective_engine(dataclasses.replace(cfg, ensemble=True, pump_k=4)) == "pump"
    # explicit names always win
    assert effective_engine(dataclasses.replace(cfg, engine="plain", pump_k=4)) == "plain"


def test_unknown_backend_gets_the_same_engines(monkeypatch):
    """A platform with another name gets the same XLA engines from auto:
    the rule reads the config, never the backend's name."""
    monkeypatch.setattr(jax, "default_backend", lambda: "quux")
    cfg = EngineConfig(num_hosts=8, pump_k=8)
    assert effective_engine(cfg) == "pump"
    assert effective_engine(dataclasses.replace(cfg, pump_k=0)) == "plain"


# --- one compile cache, placeable from outside --------------------------


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_cache_dir_from_the_environment_is_not_set_in_code(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.place_persistent_cache() is None
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.place_persistent_cache()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # git would not commit it
    ignored = open(os.path.join(REPO_ROOT, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_cache_dir_is_the_same_in_two_processes():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    code = (
        "from shadow_tpu.runtime.compile_cache import place_persistent_cache as p; "
        "import jax; print(p()); print(jax.config.jax_compilation_cache_dir)"
    )
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=dict(env, PYTHONPATH=REPO_ROOT),
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        for cwd in (REPO_ROOT, os.path.join(REPO_ROOT, "tests"))
    ]
    assert outs[0] == outs[1] == [os.path.join(REPO_ROOT, ".jax_cache")] * 2


# --- what a run publishes, and how it fails -----------------------------


def _run(tmp_path, name, experimental="", chaos="", **flags):
    from shadow_tpu.runtime.cli_run import CliUserError, run_from_config

    cfg = tmp_path / f"{name}.yaml"
    cfg.write_text(PHOLD.format(
        data_dir=tmp_path / name, experimental=experimental, chaos=chaos
    ))
    try:
        rc = run_from_config(str(cfg), **flags)
    except CliUserError as e:
        return str(e), None
    return rc, json.loads((tmp_path / name / "sim-stats.json").read_text())


def test_sim_stats_device_block(tmp_path):
    rc, s = _run(tmp_path, "plain")
    assert rc == 0
    dev = jax.devices()[0]
    assert s["device"] == {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "ids": [dev.id], "engine": "plain",
        # one host group: the routing lookup reads a host's node from one run
        "route": "runs", "route_runs": 1,
    }
    assert "degraded" not in s
    for k, per_host in s["per_host"].items():
        assert len(per_host) == 8 and sum(per_host) == s[k]


def test_no_recover_fails_fast_on_an_engine_compile_error(tmp_path):
    """With recovery on, a compile failure of the selected engine walks
    the ladder and the run is marked degraded; with --no-recover it
    propagates and the CLI exits non-zero."""
    fault = 'chaos:\n  faults: [{kind: compile, target: pump}]'
    pump = "engine: pump\n  pump_k: 4"
    rc, s = _run(tmp_path, "ladder", experimental=pump, chaos=fault)
    assert rc == 0 and s["device"]["engine"] == "plain"
    assert [(f["from"], f["to"]) for f in s["degraded"]["engine_fallbacks"]] == [("pump", "plain")]

    err, s = _run(tmp_path, "failfast", experimental=pump, chaos=fault, no_recover=True)
    assert s is None and "pump engine failed to compile" in err

    from shadow_tpu.cli import main

    cfg = tmp_path / "failfast.yaml"
    assert main(["run", str(cfg), "--no-recover"]) == 1
    assert issubclass(EngineCompileError, RuntimeError)


def test_compile_seam_leaves_runtime_errors_alone():
    """Only tracing and compiling happen inside the seam: what the device
    raises when the program runs is not relabelled a compile failure."""
    from shadow_tpu.engine.round import _launch_chunk0

    def oom(_):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        _launch_chunk0(oom, None, None, "plain", compile_chunk=lambda st: None)
    with pytest.raises(EngineCompileError, match="plain engine failed to compile"):
        _launch_chunk0(lambda st: st, None, None, "plain", compile_chunk=oom)


# --- chip_smoke.py ------------------------------------------------------


def test_chip_smoke_rehearses_on_cpu_and_refuses_without_a_chip(tmp_path):
    smoke = [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")]
    r = subprocess.run(smoke, capture_output=True, text=True, timeout=120)
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert r.returncode != 0 and last["ok"] is False

    r = subprocess.run(
        smoke + ["--rehearse", "--out", str(tmp_path / "smoke")],
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())},
    }
    out = "\n".join(lines)
    assert "parity-device == parity-oracle" in out and "cold == warm" in out
    assert "engine plain on cpu" in out
    stats = json.loads((tmp_path / "smoke" / "full.data" / "sim-stats.json").read_text())
    assert "degraded" not in stats and "recovery" not in stats


def test_committed_deployment_is_the_generator_output():
    """examples/tgen-10k/shadow.yaml is what its seeded generator writes;
    smaller worlds come only from chip_smoke.py's cut of that file."""
    example = os.path.join(REPO_ROOT, "examples", "tgen-10k")
    r = subprocess.run(
        [sys.executable, os.path.join(example, "gen_tgen10k.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    with open(os.path.join(example, "shadow.yaml")) as f:
        assert r.stdout == f.read()
