"""The deployment that fills a chip (PR 32): `benchmarks/configs/phold-512k.json`
and its cell `phold-512k.steady`.

On the CPU only what needs no chip: the document is `phold-10k`'s world at
16,384 hosts a group and nothing else; the front door sizes it without
building it; the benchmark's cell runs end to end at its rehearsal size (64
hosts) and agrees with the plain reference; `chip_smoke.py` knows it.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
CONFIGS = ROOT / "benchmarks" / "configs"
HOSTS = 524_288


def _doc(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_the_document_is_phold_10k_at_16384_hosts_a_group():
    big, small = _doc("phold-512k"), _doc("phold-10k")
    assert sum(g["quantity"] for g in big["hosts"].values()) == HOSTS == 32 * 2**14
    assert {g["quantity"] for g in big["hosts"].values()} == {16_384}
    assert big["general"].pop("stop_time") == "50 ms"
    small["general"].pop("stop_time")
    for g in list(big["hosts"].values()) + list(small["hosts"].values()):
        g.pop("quantity")
    head, head10 = big.pop("x-benchmark"), small.pop("x-benchmark")
    assert big == small  # graph, groups, processes, arguments, experimental: phold-10k's
    assert head["reduced"] == [] and head["guarantees"] == head10["guarantees"]
    assert head["assumed"]["hosts"] == HOSTS
    assert (head["assumed"]["queue_capacity"], head["assumed"]["outbox_capacity"]) == (64, 16)


def test_benchmark_json_names_the_configuration_and_its_cell_last():
    """Last when PR 32 added them: the fourth configuration, the fifth cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][3]["name"] == "phold-512k" and bench["configs"][3]["reduced"] == []
    cell = bench["workloads"][4]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "phold-512k.steady", "phold-512k", "steady", 1)
    params = json.loads((ROOT / "benchmarks" / "cells" / "phold-512k.steady.json").read_text())
    assert (params["warm_sim_ms"], params["unit_sim_ms"], params["rehearse"]["hosts"]) == (30, 10, 64)
    by = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("exchange.count_ms_per_unit")  # later PRs append after the pair
    assert names[at:at + 2] == ["exchange.count_ms_per_unit", "exchange.land_roofline"]
    assert by["exchange.land_roofline"] == ["phold-512k.steady"]
    assert by["exchange.count_ms_per_unit"][:5] == [w["name"] for w in bench["workloads"][:5]]
    listed = sorted(n for n, cells in by.items() if "phold-512k.steady" in cells)
    # 16 with the cell, the landing loop's own since PR 33, rounds and occupancy since
    # PR 34, the pop's since PR 35: a floor, later PRs append theirs
    assert len(listed) >= 20 and {"exchange.pull_ms_per_unit", "drain.pop_ms_per_unit"} <= set(listed)
    assert not {"drain.iter_ms", "exchange.flush_ms",
                "exchange.flush_roofline", "driver.unit_p95_ms"} & set(listed)
    for cells in by.values():  # a cell is only ever appended: after the four that were there
        if "phold-512k.steady" in cells:
            assert set(cells[:cells.index("phold-512k.steady")]) <= {
                w["name"] for w in bench["workloads"][:4]}


def test_the_front_door_sizes_the_world_without_building_it(tmp_path):
    """`shadow-tpu mem` on the full document: state by shapes, 4.65 KiB a
    host (4.64 until the landing's two tracker leaves, PR 33; the flush's
    `flush_cols` leaf, PR 37, is the 2 MiB that rounds 2.32 GiB to 2.33), and in its
    projection the ratio the chip measured in a run."""
    r = subprocess.run(
        [sys.executable, "-m", "shadow_tpu.cli", "mem", str(CONFIGS / "phold-512k.json"),
         "--hbm-gb", "16"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "524288" in r.stdout or "524,288" in r.stdout
    assert "2.33 GiB" in r.stdout and "4.65 KiB/host" in r.stdout
    # the projection is by state alone, and says what the chip measured on top
    from shadow_tpu.runtime.memtrack import DEVICE_OVER_STATE

    line = [l for l in r.stdout.splitlines() if "projection" in l][0]
    fits = int(line.split("projection:")[1].split()[0])
    assert f"held {DEVICE_OVER_STATE:g}x the state" in line
    assert f"about {int(fits / DEVICE_OVER_STATE)} hosts" in line and fits > 3_000_000


def test_the_cell_rehearses_end_to_end():
    """`benchmarks/run.py --workload phold-512k.steady --rehearse`: 64
    hosts on the CPU, every unit's totals equal the untimed unit's, every
    per-host counter equals the plain reference's; counts only."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", "phold-512k.steady",
         "--seed", str(2**31 + 32032), "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True and out["failed"] == 0
    assert all(v["value"] == 0 == v["limit"] for v in out["check"].values())
    assert "64 hosts, 1 chip(s)" in r.stdout
    assert out["metrics"]["drain.iters_per_unit"]["value"] > 0
    # no time, rate or share from a CPU run: the two new readers among them;
    # the counts keep their values (rounds and occupancy since PR 34, the
    # exchange's four since PR 36, the flush's width since PR 37)
    timed = {k for k, m in out["metrics"].items() if m["value"] is not None}
    assert timed == {
        "drain.iters_per_unit", "drain.rounds_per_unit", "drain.occupancy_pct",
        "exchange.passes_per_unit", "exchange.fill_pct", "exchange.land_hwm", "exchange.staged_hwm",
        "exchange.flat_pct",
    }
    # five flushes of one or two blocks of 2 columns each: a busiest row of
    # 64 hosts stages 1-4 of its 16 slots
    assert out["metrics"]["exchange.flat_pct"]["value"] in {100.0 * c / (5 * 16) for c in range(10, 41, 2)}
    assert out["metrics"]["drain.rounds_per_unit"]["value"] == 5  # 10 ms of a 2 ms lookahead
    # one ball a host: no destination of 64 takes more than one pass a round
    assert out["metrics"]["exchange.passes_per_unit"]["value"] == 5
    assert 1 <= out["metrics"]["exchange.land_hwm"]["value"] <= 4


def test_chip_smoke_knows_the_deployment():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    doc, _parity_stop, rehearse_hosts, _stop = chip_smoke.DEPLOYMENTS["phold-512k"]
    assert (ROOT / doc) == CONFIGS / "phold-512k.json" and rehearse_hosts == 64
    assert len(chip_smoke.DEPLOYMENTS) == 4
