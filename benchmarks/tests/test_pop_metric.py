"""Test of the reader that arrived with the pop's scope (PR 35):
`drain.pop_ms_per_unit` reads the operations under `drain/handle/pop`, is part
of `drain.device_ms_per_unit`, and reads nothing, raising nothing, against a
program without the scope. By hand, with the harness's others:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run  # noqa: E402
import scope_account  # noqa: E402

# a canned trace (seconds over the three traced units) and the chunk's scope
# table {instruction: (result shape, innermost scope, outermost scope)}
OPS = [
    ("fusion.7 s32[10240,8]", 0.030),  # the pop's payload gather
    ("reduce_fusion.2 (u32[10240], u32[10240], s32[10240])", 0.012),  # slot and tie
    ("select_fusion.9 s32[10240,64]", 0.021),  # the handler's own
    ("fusion.11 s32[10240,64,8]", 0.009),  # the push
    ("while.3 (s32[], s32[10240])", 0.5),  # a wrapper: no leaf time
]
TABLE = {
    "fusion.7": ("s32[10240,8]", "drain/handle/pop", "drain"),
    "reduce_fusion.2": ("(u32[10240], u32[10240], s32[10240])", "drain/handle/pop", "drain"),
    "select_fusion.9": ("s32[10240,64]", "drain/handle", "drain"),
    "fusion.11": ("s32[10240,64,8]", "drain/handle/push_self", "drain"),
}


def _ctx():
    ctx = run.Context()
    ctx.trace = {"device_ops": OPS, "busy_s": 0.072, "window_s": 0.6}
    ctx.unit_s = [0.2, 0.2, 0.2]
    return ctx


def test_pop_reader_on_a_canned_scope_table(monkeypatch):
    read = run.load_reader("drain.pop_ms_per_unit")
    monkeypatch.setattr(scope_account, "chunk_table", lambda: TABLE)
    assert read(_ctx()) == pytest.approx((0.030 + 0.012) * 1e3 / scope_account.TRACED_UNITS)
    # the pop is part of the drain: the drain's reader holds it
    assert run.load_reader("drain.device_ms_per_unit")(_ctx()) == pytest.approx(
        (0.030 + 0.012 + 0.021 + 0.009) * 1e3 / scope_account.TRACED_UNITS)
    # the pump's pop is another path, and no cell runs the pump
    pumped = {k: (s, i.replace("drain/handle", "drain/pump"), o) for k, (s, i, o) in TABLE.items()}
    monkeypatch.setattr(scope_account, "chunk_table", lambda: pumped)
    assert read(_ctx()) is None


def test_pop_reader_reads_nothing_from_a_program_without_the_scope(monkeypatch):
    read = run.load_reader("drain.pop_ms_per_unit")
    parent = {k: (s, "drain/handle" if i.endswith("/pop") else i, o) for k, (s, i, o) in TABLE.items()}
    for table in (parent, {}, None):  # the parent's program; an empty table; no chunk kept
        monkeypatch.setattr(scope_account, "chunk_table", lambda table=table: table)
        assert read(_ctx()) is None
    monkeypatch.setattr(scope_account, "chunk_table", lambda: TABLE)
    untraced = run.Context()
    untraced.trace, untraced.unit_s = None, [0.2]
    assert read(untraced) is None


def test_the_entry_names_the_six_cells_it_arrived_for():
    """Pinned by name and by prefix: later PRs append entries and cells."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {m["name"]: m for m in bench["per_layer"]}["drain.pop_ms_per_unit"]
    cells = entry.pop("workloads")
    assert entry == {
        "name": "drain.pop_ms_per_unit", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "drain", "moves": "sim_s_per_wall_s",
    }
    assert cells[:6] == [w["name"] for w in bench["workloads"]][:6]
