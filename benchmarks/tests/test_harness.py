"""Tests of the benchmark's own harness. Run them by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Every run of the harness is a child process at the cells' rehearsal size
(64 hosts) on the CPU; nothing here loads libtpu and nothing is built into
a shared path (the reference binary goes into each run's own temporary
directory). The repo's tier-1 command collects `tests/` only, so these do
not count there.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELLS = ("tgen-10k.fetch", "phold-10k.steady")


def env_cpu(devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def harness(root: str, *argv, devices: int = 1, timeout: int = 900):
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *argv],
        cwd=root, env=env_cpu(devices), capture_output=True, text=True, timeout=timeout,
    )
    lines = r.stdout.strip().splitlines()
    return r, (json.loads(lines[-1]) if r.returncode == 0 and lines else None)


@pytest.fixture(scope="module")
def copy_of_repo(tmp_path_factory):
    """The program and the benchmark in a directory of the test's own, so
    that files can be added beside the committed ones."""
    dst = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "shadow_tpu"), dst / "shadow_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(BENCH, dst / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    return str(dst)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", (0, 1))
def test_rehearsal_end_to_end(cell, trace):
    """The whole run at 64 hosts: the last line parses, every unit's totals
    equal the untimed unit's, nothing compiled in the window, and every
    per-host counter equals the plain reference's."""
    r, out = harness(ROOT, "--workload", cell, "--seed", str(2**31 + 12345),
                     "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["rehearsal"] is True and out["device"]["platform"] == "cpu"
    assert all(v["value"] == 0 == v["limit"] for v in out["check"].values())
    assert list(out)[-1] == "check"  # the numbers compared come last
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[group] if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) <= names
    timed = [m for m in out["metrics"].values() if m["value"] is not None]
    if trace:  # a rehearsal reports counts only
        assert out["metrics"]["drain.iters_per_unit"]["value"] > 0 and len(timed) == 1
    else:
        assert set(out["metrics"]) == names and not timed
    assert "check hosts_differing: 0 (limit 0)" in r.stderr


def test_no_accelerator_is_refused_quickly():
    r, out = harness(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0", timeout=120)
    assert r.returncode != 0 and out is None
    assert not r.stdout.strip().startswith("{")


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    r, out = harness(str(tmp_path), "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0", "--rehearse", timeout=120)
    assert r.returncode != 0 and out is None and "{" not in r.stdout


def test_new_cell_config_and_metric_arrive_as_files(copy_of_repo):
    """A later PR adds a configuration, a cell and a per-layer metric by
    adding files and BENCHMARK.json entries; no file that is there changes."""
    root = copy_of_repo
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    cfg = json.load(open(os.path.join(root, "benchmarks/configs/phold-10k.json")))
    for spec in cfg["hosts"].values():
        spec["processes"][0]["args"]["max_delay"] = "20 ms"
    json.dump(cfg, open(os.path.join(root, "benchmarks/configs/phold-fast.json"), "w"))
    json.dump({"config": "phold-fast", "chips": 1, "warm_sim_ms": 20, "unit_sim_ms": 6,
               "rehearse": {"hosts": 64}, "why": "a test's cell"},
              open(os.path.join(root, "benchmarks/cells/phold-fast.short.json"), "w"))
    with open(os.path.join(root, "benchmarks/layer_metrics/driver.events_per_unit.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.events_per_unit\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "phold-fast", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmarks/configs/phold-fast.json"})
    bench["workloads"].append({"name": "phold-fast.short", "config": "phold-fast",
                               "traffic": "short", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "driver.events_per_unit", "unit": "events", "better": "lower",
                               "source": "program_counter", "layer": "driver",
                               "moves": "sim_s_per_wall_s", "workloads": ["phold-fast.short"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    r, out = harness(root, "--workload", "phold-fast.short", "--seed", "9", "--seconds", "1",
                     "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True
    # only the metric that lists the new cell is asked of it
    assert set(out["metrics"]) == {"driver.events_per_unit"}
    assert out["metrics"]["driver.events_per_unit"]["value"] > 0
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


def test_four_virtual_chips_equal_one(copy_of_repo):
    """`chips: 4` in a cell means general.parallelism 4 and nothing else:
    the sharded plane gives the reference's per-host counters too."""
    root = copy_of_repo
    json.dump({"config": "tgen-10k", "chips": 4, "warm_sim_ms": 50, "unit_sim_ms": 10,
               "rehearse": {"hosts": 64}, "why": "a test's cell"},
              open(os.path.join(root, "benchmarks/cells/tgen-10k.fetch-x4.json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tgen-10k.fetch-x4", "config": "tgen-10k",
                               "traffic": "fetch-x4", "chips": 4, "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("tgen-10k.fetch-x4")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    r, out = harness(root, "--workload", "tgen-10k.fetch-x4", "--seed", "77", "--seconds", "1",
                     "--trace", "1", "--rehearse", devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["device"]["count"] == 4
    assert out["check"]["hosts_differing"] == {"value": 0, "limit": 0}
    # the one-chip pieces do not apply on the sharded plane: left out, not 0
    assert "drain.iter_ms" not in out["metrics"]
    # with one visible device the cell is refused, not run on fewer chips
    r1, out1 = harness(root, "--workload", "tgen-10k.fetch-x4", "--seed", "77", "--seconds", "1",
                       "--trace", "0", "--rehearse", devices=1, timeout=120)
    assert r1.returncode != 0 and out1 is None
