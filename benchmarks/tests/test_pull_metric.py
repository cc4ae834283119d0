"""Test of the reader that arrived with the landing's loop (PR 33):
`exchange.pull_ms_per_unit` reads the operations under `exchange/land/pull`,
is part of `exchange.land_ms_per_unit`, and reads nothing, raising nothing,
against a program without the scope. By hand, with the harness's others:

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

from test_fill_cell import _tables  # noqa: E402
from test_new_cells import recorded  # noqa: E402,F401 — the trace recorded on the chip


def test_pull_reader_on_the_recorded_trace(recorded, monkeypatch):  # noqa: F811
    read = run.load_reader("exchange.pull_ms_per_unit")
    parent, change, first_s, total_s = _tables(recorded())
    # the change's table with its first landing operation under the loop's scope
    pulled = {name: (shape, "exchange/land/pull", outer) if inner == "exchange/land/count"
              else (shape, inner, outer) for name, (shape, inner, outer) in change.items()}
    monkeypatch.setattr(scope_account, "chunk_table", lambda: pulled)
    ctx = recorded()
    assert read(ctx) == pytest.approx(first_s * 1e3 / scope_account.TRACED_UNITS)
    assert run.load_reader("exchange.land_ms_per_unit")(ctx) == pytest.approx(
        total_s * 1e3 / scope_account.TRACED_UNITS)
    assert run.load_reader("exchange.count_ms_per_unit")(ctx) is None
    for table in (parent, None):  # the parent's program; no chunk kept
        monkeypatch.setattr(scope_account, "chunk_table", lambda table=table: table)
        assert read(recorded()) is None


def test_the_entry_names_the_five_cells_it_arrived_for():
    """Pinned by name and by prefix: later PRs append entries and cells."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {m["name"]: m for m in bench["per_layer"]}["exchange.pull_ms_per_unit"]
    cells = entry.pop("workloads")
    assert entry == {
        "name": "exchange.pull_ms_per_unit", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "sim_s_per_wall_s",
    }
    assert cells[:5] == [w["name"] for w in bench["workloads"]][:5]
