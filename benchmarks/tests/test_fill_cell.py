"""Tests of what arrived with the cell that fills a chip (`phold-512k.steady`):
the readers `exchange.count_ms_per_unit` and `exchange.land_roofline`, and the
landing's byte count beside `roofline.py` (`land_bytes.py`). By hand, with the
harness's others:

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

import land_bytes  # noqa: E402
import run  # noqa: E402
import scope_account  # noqa: E402

from test_new_cells import recorded  # noqa: E402,F401 — the trace recorded on the chip

CELL = "phold-512k.steady"


def test_land_bytes_at_phold_10k_by_hand():
    """10,240 hosts x 64 slots, 163,840 staged entries, 14 words of 4 bytes:
    queue read and written 2 x 36,700,160, the sorted words 9,175,040, the
    pulled [14, H, Q] 36,700,160."""
    doc = land_bytes.config_doc({"config": "phold-10k"})
    assert land_bytes.shapes(doc) == (10_240, 64, 163_840)
    slot_bytes = 8 + 8 + 4 + 4 + 8 * 4  # time, tie, kind, aux, payload lanes
    assert slot_bytes == land_bytes.WORDS * 4
    assert land_bytes.land_min_bytes(10_240, 64, 163_840) == (
        2 * 36_700_160 + 9_175_040 + 36_700_160) == 119_275_520
    # 51.2 times the hosts, 51.2 times the bytes: nothing in it is quadratic
    big = land_bytes.shapes(land_bytes.config_doc({"config": "phold-512k"}))
    assert big == (524_288, 64, 8_388_608)
    assert land_bytes.land_min_bytes(*big) * 10 == 119_275_520 * 512


def test_rounds_per_unit_is_the_unit_over_the_lookahead():
    doc = land_bytes.config_doc({"config": "phold-512k"})
    assert land_bytes.rounds_per_unit(doc, 10) == 5  # 2 ms self-loops
    assert land_bytes.rounds_per_unit(doc, 50) == 25  # phold-10k.steady's unit
    fat = land_bytes.config_doc({"config": "fattree-10k"})
    assert land_bytes.rounds_per_unit(fat, 1) == 200  # 5 us: the most a unit could hold


def _tables(ctx):
    """(the parent's table: every recorded operation under `exchange/land`;
    the change's: the first of them under `exchange/land/count`)."""
    ops = [(op, s) for op, s in ctx.trace["device_ops"]
           if not scope_account.is_wrapper(scope_account.split(op)[0])]
    parent = {scope_account.split(op)[0]: (scope_account.split(op)[1], "exchange/land", "exchange")
              for op, _s in ops}
    change = dict(parent)
    name, shape = scope_account.split(ops[0][0])
    change[name] = (shape, "exchange/land/count", "exchange")
    # (a name the trace holds at two shapes is in the table at one: the other is "other programs")
    landed = scope_account.under(scope_account.fold(ctx.trace["device_ops"], parent), "exchange/land")
    return parent, change, ops[0][1], landed


def test_count_reader_on_the_recorded_trace(recorded, monkeypatch):  # noqa: F811
    read = run.load_reader("exchange.count_ms_per_unit")
    parent, change, first_s, total_s = _tables(recorded())
    monkeypatch.setattr(scope_account, "chunk_table", lambda: change)
    ctx = recorded()
    assert read(ctx) == pytest.approx(first_s * 1e3 / scope_account.TRACED_UNITS)
    # the count is part of the landing: the landing's reader holds it
    assert run.load_reader("exchange.land_ms_per_unit")(ctx) == pytest.approx(
        total_s * 1e3 / scope_account.TRACED_UNITS)
    # a program without the scope (the parent): nothing, and nothing raised
    monkeypatch.setattr(scope_account, "chunk_table", lambda: parent)
    assert read(recorded()) is None
    monkeypatch.setattr(scope_account, "chunk_table", lambda: None)
    assert read(recorded()) is None


def test_land_roofline_on_the_recorded_trace(recorded, monkeypatch):  # noqa: F811
    read = run.load_reader("exchange.land_roofline")
    parent, _change, _first, total_s = _tables(recorded())
    monkeypatch.setattr(scope_account, "chunk_table", lambda: parent)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ctx = recorded()
    ctx.cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    ctx.params = json.load(open(os.path.join(BENCH, "cells", CELL + ".json")))
    ctx.peaks = {"hbm_bytes_per_s": 819e9}
    land_ms = total_s * 1e3 / scope_account.TRACED_UNITS
    least_ms = 5 * 6_106_906_624 / 819e9 * 1e3  # 5 rounds of 6.1 GB: 37.28 ms a unit
    assert least_ms == pytest.approx(37.2827, rel=1e-4)
    assert read(ctx) == pytest.approx(100.0 * least_ms / land_ms)
    # no peaks (a rehearsal), several chips, or no trace: nothing
    ctx.peaks = {}
    assert read(ctx) is None
    ctx.peaks, ctx.chips = {"hbm_bytes_per_s": 819e9}, 4
    assert read(ctx) is None
    monkeypatch.setattr(scope_account, "chunk_table", lambda: None)
    assert read(recorded()) is None


def test_the_new_entries_are_appended_and_name_the_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == "phold-512k"
    by = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "exchange.count_ms_per_unit", "exchange.land_roofline"]
    assert by["exchange.land_roofline"]["workloads"] == [CELL]
    assert by["exchange.land_roofline"]["unit"] == "%"
    assert by["exchange.count_ms_per_unit"]["workloads"][-1] == CELL
    for m in bench["per_layer"]:
        assert CELL not in m["workloads"][:-1]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for gone in ("drain.iter_ms", "exchange.flush_ms", "exchange.flush_roofline",
                 "driver.unit_p95_ms"):
        assert CELL not in by[gone]["workloads"]
