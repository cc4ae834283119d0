"""Tests of the per-scope readers (benchmarks/scope_account.py and the seven
`layer_metrics/` files that read it). By hand, with the harness's others:

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

from test_harness import CELLS, harness  # noqa: E402

ACCEPTED = [
    "setup.build_world_s", "setup.compile_load_s", "setup.warmup_s", "driver.us_per_event",
    "driver.unit_p95_ms", "drain.iters_per_unit", "drain.iter_ms", "exchange.flush_ms",
    "exchange.flush_roofline", "device.idle_pct",
]
NEW = [
    "drain.window_ms_per_unit", "drain.device_ms_per_unit", "exchange.device_ms_per_unit",
    "exchange.land_ms_per_unit", "device.unscoped_pct", "device.idle_in_program_pct",
    "driver.host_ms_per_unit",
]

TABLE = {
    # instruction: (result shape, innermost scope, outermost scope)
    "fusion.16": ("s32[655360,15]", "exchange/land", "exchange"),
    "select_select_fusion.7": ("s32[10240,64,8]", "exchange/land/push_self", "exchange"),
    "fusion.40": ("pred[163840]", "exchange", "exchange"),
    "fusion.900": ("s32[10240,8]", "drain/handle", "drain"),
    "fusion.901": ("s32[10240,64,8]", "drain/handle/push_self", "drain"),
    "reduce.3": ("u32[]", "window", "window"),
    "fusion.77": ("s64[23]", "probe", "probe"),
    "copy.1": ("u32[10240,16]", "", ""),
    "copy.9": ("s32[]", None, None),
    "conditional.11": ("", "", ""),
    "while.114": ("u32[]", "", ""),
    "while.5": ("s32[10240]", "drain", "drain"),
}
OPS = [
    ["fusion.16 s32[655360,15]", 0.525],
    ["select_select_fusion.7 s32[10240,64,8]", 0.100],
    ["fusion.40 pred[163840]", 0.050],
    ["fusion.900 s32[10240,8]", 0.200],
    ["fusion.901 s32[10240,64,8]", 0.060],
    ["reduce.3 u32[]", 0.010],
    ["fusion.77 s64[23]", 0.002],
    ["copy.1 u32[10240,16]", 0.004],  # the chunk's own, under no scope
    ["copy.9 s32[]", 0.001],  # the compiler's own, in a body no scope encloses
    ["copy.1 s32[10240,384,8]", 0.030],  # another program's copy.1: the shape differs
    ["convert_element_type.2 s64[]", 0.001],  # no such instruction in the chunk
    ["while.114 u32[]", 0.007],
    ["while.5 s32[10240]", 0.002],
    ["%conditional.11 = ((u32[]{:T(128)}, u32[]{:T(128)}), (u32[10240,64]{0,1:T(8,128)}", 0.003],
]


class Ctx:
    """What run.py's Context gives a reader, with a trace of one's own."""

    def __init__(self, device_ops, spans=(), units=6, busy_s=None):
        busy = busy_s if busy_s is not None else sum(
            t for n, t in device_ops if not scope_account.is_wrapper(scope_account.split(n)[0]))
        self.trace = {"device_ops": device_ops, "busy_s": busy, "window_s": busy * 1.25,
                      "idle_gaps": []}
        self.spans = list(spans)
        self.unit_s = [0.4] * units


def test_fold_matches_by_name_and_shape():
    folded = scope_account.fold(OPS, TABLE)
    by = folded["by_scope"]
    assert by["exchange/land"] == pytest.approx(0.525)
    assert by["exchange/land/push_self"] == pytest.approx(0.100)
    assert by["drain/handle/push_self"] == pytest.approx(0.060)
    assert by[scope_account.UNSCOPED] == pytest.approx(0.005)
    assert by[scope_account.OTHER] == pytest.approx(0.031)
    assert folded["wrappers"] == pytest.approx(0.012)
    assert scope_account.under(folded, "exchange") == pytest.approx(0.675)
    assert scope_account.under(folded, "exchange/land") == pytest.approx(0.625)
    assert scope_account.under(folded, "drain") == pytest.approx(0.260)
    # the account closes: every leaf second is booked once, wrappers never
    leaves = sum(t for n, t in OPS) - 0.012
    assert sum(by.values()) == pytest.approx(leaves)


def test_readers_on_a_synthetic_trace(monkeypatch, capsys):
    monkeypatch.setattr(scope_account, "chunk_table", lambda: TABLE)
    spans = []
    for u in range(8):  # two warm-up entries, then the window's six
        t = 10.0 * u
        spans += [("run", t, t + 0.5), ("donate_copy", t + 0.01, t + 0.03),
                  ("probe_fetch", t + 0.05, t + 0.25), ("probe_fetch", t + 0.26, t + 0.46 + 0.001 * u)]
    ctx = Ctx(OPS, spans=spans, units=6)
    got = {name: run.load_reader(name)(ctx) for name in NEW}
    assert got["drain.window_ms_per_unit"] == pytest.approx(10.0 / 3)
    assert got["drain.device_ms_per_unit"] == pytest.approx(260.0 / 3)
    assert got["exchange.device_ms_per_unit"] == pytest.approx(675.0 / 3)
    assert got["exchange.land_ms_per_unit"] == pytest.approx(625.0 / 3)
    assert got["device.unscoped_pct"] == pytest.approx(100 * 0.036 / ctx.trace["busy_s"])
    assert got["device.idle_in_program_pct"] == pytest.approx(100 * 0.012 / ctx.trace["window_s"])
    # median of the last six runs: 500 ms less 200 ms less (200 + u) ms, u = 2..7
    assert got["driver.host_ms_per_unit"] == pytest.approx(100.0 - 4.5)
    out = capsys.readouterr().out
    assert out.count("scope account over 3 traced units") == 1  # folded and said once
    assert "(+0.000 %)" in out


def test_readers_find_nothing_without_table_scope_or_trace(monkeypatch):
    for table in (None, {}):
        monkeypatch.setattr(scope_account, "chunk_table", lambda table=table: table)
        assert all(run.load_reader(name)(Ctx(OPS)) is None for name in NEW)
    monkeypatch.setattr(scope_account, "chunk_table", lambda: TABLE)
    no_trace = Ctx(OPS)
    no_trace.trace = None
    assert all(run.load_reader(name)(no_trace) is None for name in NEW)
    # a scope that names no operation of this trace reports nothing, not 0
    ctx = Ctx([op for op in OPS if not op[0].startswith("reduce.3")])
    assert run.load_reader("drain.window_ms_per_unit")(ctx) is None
    assert run.load_reader("drain.device_ms_per_unit")(ctx) > 0


def test_readers_return_none_on_the_recorded_chip_trace(tmp_path):
    """The trace recorded on the chip before the scopes existed: the
    process has compiled no chunk, so there is no table, and no `run`
    span either."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "phold_units.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ctx = run.Context()
    ctx.trace = trace_reduce.reduce_file(str(path))
    ctx.unit_s = [0.03, 0.03, 0.03]
    ctx.spans = [("donate_copy", 0.0, 0.01), ("probe_fetch", 0.02, 0.03)]
    assert ctx.trace["device_ops"]
    assert all(run.load_reader(name)(ctx) is None for name in NEW)


def test_a_rehearsal_gives_the_new_metrics_no_value():
    r, out = harness(ROOT, "--workload", CELLS[1], "--seed", str(2**31 + 777),
                     "--seconds", "1", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True
    for name in NEW:
        assert out["metrics"].get(name, {"value": None})["value"] is None
    assert out["metrics"]["drain.iters_per_unit"]["value"] > 0


def test_the_seven_arrive_as_files_and_entries_only():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED  # nothing put first or in the middle
    assert names[len(ACCEPTED):len(ACCEPTED) + len(NEW)] == NEW
    for m in bench["per_layer"][len(ACCEPTED):len(ACCEPTED) + len(NEW)]:
        assert m["workloads"] == list(CELLS) and m["better"] == "lower"
        assert m["moves"] == "sim_s_per_wall_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    # the harness learns of them from BENCHMARK.json alone
    for existing in ("run.py", "trace_reduce.py", "pieces.py", "roofline.py"):
        text = open(os.path.join(BENCH, existing)).read()
        assert "scope_account" not in text and not any(n in text for n in NEW)
