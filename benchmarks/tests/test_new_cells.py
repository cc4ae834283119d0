"""Tests of the cells and readers that arrived after the first benchmark:
`tgen-10k.fetch-x4` (four chips), `fattree-10k.saturate` (the k=16 fat-tree
whose unit is 5 live rounds of bursts) and the four per-scope readers
`drain.netstack_ms_per_unit`, `drain.tcp_ms_per_unit`,
`exchange.collective_ms_per_unit`, `drain.stage_ms_per_unit`. By hand, with the harness's others:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run  # noqa: E402
import scope_account  # noqa: E402
import trace_reduce  # noqa: E402

from test_harness import harness  # noqa: E402

# cell -> (virtual devices, rehearsal hosts)
NEW_CELLS = {"tgen-10k.fetch-x4": (4, 64), "fattree-10k.saturate": (1, 128)}
SCOPE_OF = {
    "drain.netstack_ms_per_unit": "drain/handle/netstack",
    "drain.tcp_ms_per_unit": "drain/handle/tcp",
    "exchange.collective_ms_per_unit": "exchange/collective",
    "drain.stage_ms_per_unit": "drain/handle/stage",
}
IN_THE_HANDLER = [m for m, s in SCOPE_OF.items() if s.startswith("drain/handle/")]


@pytest.mark.parametrize("cell", NEW_CELLS)
@pytest.mark.parametrize("trace", (0, 1))
def test_rehearsal_end_to_end(cell, trace):
    """The whole run at the cell's rehearsal size (the fat-tree keeps all
    its 320 nodes and 128 groups, one host each): every unit's totals equal
    the untimed unit's, nothing compiled in the window, every per-host
    counter equals the plain reference's."""
    devices, hosts = NEW_CELLS[cell]
    r, out = harness(ROOT, "--workload", cell, "--seed", str(2**31 + 54321),
                     "--seconds", "2", "--trace", str(trace), "--rehearse", devices=devices)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["rehearsal"] is True and out["device"]["count"] == devices
    assert all(v["value"] == 0 == v["limit"] for v in out["check"].values())
    assert f"{hosts} hosts, {devices} chip(s)" in r.stdout
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[group] if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) <= names
    if trace:  # a rehearsal reports counts only
        assert out["metrics"]["drain.iters_per_unit"]["value"] > 0
        assert all(out["metrics"].get(n, {"value": None})["value"] is None for n in SCOPE_OF)
    else:
        assert set(out["metrics"]) == names


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A Context over the trace recorded on the chip (three phold units)."""
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "phold_units.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = trace_reduce.reduce_file(str(path))

    def ctx():
        c = run.Context()
        c.trace, c.unit_s = trace, [0.03, 0.03, 0.03]
        return c

    return ctx


def test_new_readers_on_the_recorded_trace(recorded, monkeypatch):
    """With a table that books four of the recorded trace's operations to
    the four scopes, each reader gives that operation's time per unit;
    with a table that lacks the scope (the parent's program), None."""
    ops = [(op, s) for op, s in recorded().trace["device_ops"]
           if not scope_account.is_wrapper(scope_account.split(op)[0])]
    table = {scope_account.split(op)[0]: (scope_account.split(op)[1], "drain/handle", "drain")
             for op, _s in ops}
    parent = dict(table)
    picked = {}
    for (op, seconds), (metric, scope) in zip(ops, SCOPE_OF.items()):
        name, shape = scope_account.split(op)
        table[name] = (shape, scope, scope.split("/")[0])
        picked[metric] = seconds
    monkeypatch.setattr(scope_account, "chunk_table", lambda: table)
    ctx = recorded()
    for metric in SCOPE_OF:
        got = run.load_reader(metric)(ctx)
        assert got == pytest.approx(picked[metric] * 1e3 / scope_account.TRACED_UNITS) and got > 0
    # the handler's own time still holds what its named parts hold
    assert run.load_reader("drain.device_ms_per_unit")(ctx) >= sum(
        run.load_reader(m)(ctx) for m in IN_THE_HANDLER)
    monkeypatch.setattr(scope_account, "chunk_table", lambda: parent)
    ctx = recorded()
    assert all(run.load_reader(metric)(ctx) is None for metric in SCOPE_OF)
    monkeypatch.setattr(scope_account, "chunk_table", lambda: None)
    assert all(run.load_reader(metric)(recorded()) is None for metric in SCOPE_OF)


def test_new_entries_come_last_and_name_their_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [w["name"] for w in bench["workloads"]][2:] == list(NEW_CELLS)
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 4, 1]
    assert [c["name"] for c in bench["configs"]] == ["tgen-10k", "phold-10k", "fattree-10k"]
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(SCOPE_OF)
    by = {m["name"]: m for m in bench["per_layer"]}
    assert by["exchange.collective_ms_per_unit"]["workloads"] == ["tgen-10k.fetch-x4"]
    assert by["driver.unit_p95_ms"]["workloads"] == ["phold-10k.steady", "tgen-10k.fetch-x4"]
    # the fat-tree cell says what it is: 5 live rounds, not the 200 its lookahead allows
    assert "5 live rounds" in bench["workloads"][3]["why"]
    for m in bench["per_layer"]:
        # a cell is only ever appended to a metric's list
        old = [c for c in m["workloads"] if c not in NEW_CELLS]
        assert m["workloads"][:len(old)] == old
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    cell = json.load(open(os.path.join(BENCH, "cells", "fattree-10k.saturate.json")))
    assert (cell["warm_sim_ms"], cell["unit_sim_ms"], cell["chips"]) == (4, 1, 1)
    doc = json.load(open(os.path.join(ROOT, bench["configs"][2]["file"])))
    assert sum(g["quantity"] for g in doc["hosts"].values()) == 10240
    assert doc["x-benchmark"]["reduced"] == bench["configs"][2]["reduced"]
