"""Tests of what arrived with the exchange's always-on counters and its three
scopes (PR 36): seven per-layer entries, a reader file each, and
`benchmarks/exchange_counts.py`, which reads the exchange's probe lanes of a
unit and gives None against a program that keeps no `outbox_slots` (the
parent, whose three lanes read 0 with the tracker off). Entries are pinned by
NAME, never by position: later PRs append. By hand, with the harness's others:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import exchange_counts  # noqa: E402
import run  # noqa: E402
import scope_account  # noqa: E402

from test_harness import harness  # noqa: E402

COUNTS = ("exchange.passes_per_unit", "exchange.fill_pct", "exchange.land_hwm",
          "exchange.staged_hwm")
X4 = "tgen-10k.fetch-x4"
# name -> (unit, better, source, layer, listed for every cell of its day)
ENTRIES = {
    "exchange.passes_per_unit": ("passes", "lower", "program_counter", "kernels", True),
    "exchange.fill_pct": ("%", "higher", "program_counter", "exchange", True),
    "exchange.land_hwm": ("arrivals", "lower", "program_counter", "kernels", True),
    "exchange.staged_hwm": ("entries", "lower", "program_counter", "exchange", True),
    "exchange.sort_ms_per_unit": ("ms", "lower", "device_trace", "kernels", True),
    "exchange.pack_ms_per_unit": ("ms", "lower", "device_trace", "kernels", True),
    "exchange.bucket_ms_per_unit": ("ms", "lower", "device_trace", "exchange", False),
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_has_its_fields_its_cells_and_a_reader(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = dict({m["name"]: m for m in bench["per_layer"]}[name])
    cells = entry.pop("workloads")
    unit, better, source, layer, everywhere = ENTRIES[name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "sim_s_per_wall_s"}
    six = [w["name"] for w in bench["workloads"]][:6]
    assert cells[:6] == six if everywhere else cells == [X4]
    assert callable(run.load_reader(name))


def _probe(**lanes):
    return types.SimpleNamespace(**lanes)


def _kept(**more):
    entry = _probe(rounds_live=7, iters=60, lanes_live=900, land_passes=11, packets_sent=500,
                   land_hwm=9, exch_hwm=300)
    chunk = _probe(rounds_live=12, iters=100, lanes_live=980, land_passes=40, packets_sent=1_780,
                   land_hwm=36, exch_hwm=420)
    return types.SimpleNamespace(hosts=100, entry=entry, chunk=chunk, **more)


def _ctx(iters=40):
    ctx = run.Context()
    ctx.iters_per_unit = iters
    return ctx


def test_counts_of_a_unit_and_none_under_the_guards(monkeypatch):
    from shadow_tpu import scopes

    monkeypatch.setattr(scopes, "last_probes", _kept(outbox_slots=6_400))
    assert exchange_counts.per_unit(_ctx()) == {
        "land_passes": 29, "packets_sent": 1_280, "rounds_live": 5,
        "land_hwm": 36, "exch_hwm": 420, "outbox_slots": 6_400,
    }
    read = {name: run.load_reader(name) for name in COUNTS}
    assert read["exchange.passes_per_unit"](_ctx()) == 29
    assert read["exchange.fill_pct"](_ctx()) == pytest.approx(100 * 1_280 / (5 * 6_400))
    assert read["exchange.land_hwm"](_ctx()) == 36  # the mark, not 36 - 9
    assert read["exchange.staged_hwm"](_ctx()) == 420
    # another entry than the window's last unit
    assert exchange_counts.per_unit(_ctx(iters=41)) is None
    # the parent: probes kept, but no outbox_slots beside them
    monkeypatch.setattr(scopes, "last_probes", _kept())
    assert exchange_counts.per_unit(_ctx()) is None
    assert all(r(_ctx()) is None for r in read.values())
    # no chunk fetched; no probes kept; a program from before the probes
    monkeypatch.setattr(scopes, "last_probes",
                        types.SimpleNamespace(hosts=100, outbox_slots=6_400, entry=_probe(), chunk=None))
    assert exchange_counts.per_unit(_ctx()) is None
    monkeypatch.setattr(scopes, "last_probes", None)
    assert exchange_counts.per_unit(_ctx()) is None
    monkeypatch.delattr(scopes, "last_probes")
    assert all(r(_ctx()) is None for r in read.values())


# a canned trace (seconds over the three traced units) and the sharded
# chunk's scope table {instruction: (result shape, innermost, outermost)}
OPS = [
    ("sort.3 (s32[655360], s32[655360])", 0.060),
    ("fusion.40 s32[14,655360]", 0.015),
    ("fusion.24 u32[655360]", 0.150),  # the bucketing's argsort
    ("scatter.5 s32[4,163840,8]", 0.090),
    ("all-to-all.1 s32[4,163840,8]", 0.009),
    ("fusion.77 s32[2560,384]", 0.012),  # the free-slot ranks: the landing's own
    ("fusion.80 s32[2560,64]", 0.006),  # the flush's own: the outbox cleared
]
TABLE = {
    "sort.3": ("(s32[655360], s32[655360])", "exchange/land/sort", "exchange"),
    "fusion.40": ("s32[14,655360]", "exchange/land/pack", "exchange"),
    "fusion.24": ("u32[655360]", "exchange/bucket", "exchange"),
    "scatter.5": ("s32[4,163840,8]", "exchange/bucket", "exchange"),
    "all-to-all.1": ("s32[4,163840,8]", "exchange/collective", "exchange"),
    "fusion.77": ("s32[2560,384]", "exchange/land", "exchange"),
    "fusion.80": ("s32[2560,64]", "exchange", "exchange"),
}


def _traced():
    ctx = run.Context()
    ctx.trace = {"device_ops": OPS, "busy_s": 0.342, "window_s": 0.6}
    ctx.unit_s = [0.2, 0.2, 0.2]
    return ctx


def test_the_three_scopes_are_read_apart_and_stay_part_of_what_held_them(monkeypatch):
    per = 1e3 / scope_account.TRACED_UNITS
    monkeypatch.setattr(scope_account, "chunk_table", lambda: TABLE)
    assert run.load_reader("exchange.sort_ms_per_unit")(_traced()) == pytest.approx(0.060 * per)
    assert run.load_reader("exchange.pack_ms_per_unit")(_traced()) == pytest.approx(0.015 * per)
    assert run.load_reader("exchange.bucket_ms_per_unit")(_traced()) == pytest.approx(0.240 * per)
    # by prefix the standing readers read what they read: the landing holds
    # sort and pack, the exchange holds all; the collective is not the bucket's
    assert run.load_reader("exchange.land_ms_per_unit")(_traced()) == pytest.approx(0.087 * per)
    assert run.load_reader("exchange.device_ms_per_unit")(_traced()) == pytest.approx(0.342 * per)
    assert run.load_reader("exchange.collective_ms_per_unit")(_traced()) == pytest.approx(0.009 * per)
    # the parent's program: the same operations under the scopes it had
    parent = {k: (s, {"exchange/land/sort": "exchange/land", "exchange/land/pack": "exchange/land",
                      "exchange/bucket": "exchange"}.get(i, i), o) for k, (s, i, o) in TABLE.items()}
    for table in (parent, {}, None):
        monkeypatch.setattr(scope_account, "chunk_table", lambda table=table: table)
        for name in ("exchange.sort_ms_per_unit", "exchange.pack_ms_per_unit",
                     "exchange.bucket_ms_per_unit"):
            assert run.load_reader(name)(_traced()) is None
    monkeypatch.setattr(scope_account, "chunk_table", lambda: parent)
    assert run.load_reader("exchange.land_ms_per_unit")(_traced()) == pytest.approx(0.087 * per)
    assert run.load_reader("exchange.device_ms_per_unit")(_traced()) == pytest.approx(0.342 * per)


@pytest.mark.parametrize("cell", ("phold-10k.steady", "tgen-10k.fetch"))
def test_a_rehearsal_keeps_the_four_counts_and_repeats_them(cell):
    seen = []
    for _ in range(2):
        r, out = harness(ROOT, "--workload", cell, "--seed", str(2**31 + 3636),
                         "--seconds", "1", "--trace", "1", "--rehearse")
        assert r.returncode == 0, r.stderr[-2000:]
        assert out["correct"] is True
        seen.append({name: out["metrics"][name]["value"] for name in COUNTS})
        assert all(v is not None and v > 0 for v in seen[-1].values()), seen[-1]
        # a CPU run gives no time: the three scopes' readers keep no number
        assert all(out["metrics"].get(n, {"value": None})["value"] is None for n in
                   ("exchange.sort_ms_per_unit", "exchange.pack_ms_per_unit"))
    assert seen[0] == seen[1]
