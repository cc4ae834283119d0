"""`correct` has to be able to come out false: the control (the reference
with one stated guarantee broken, put in the program's place) and the timed
path broken underneath a run. Also the pieces the comparison rests on: the
reference's own routing against hand-worked values, the trace reduction
against a recorded trace, and the roofline bytes against the program's own
pricing of its state.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from reference import world as refworld  # noqa: E402
import trace_reduce  # noqa: E402

CELLS = {"tgen-10k.fetch": ("tgen-10k", 60), "phold-10k.steady": ("phold-10k", 80)}


def small_config(name: str, hosts: int = 64) -> dict:
    raw = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    for spec in raw["hosts"].values():
        spec["quantity"] = hosts // len(raw["hosts"])
    return raw


@pytest.fixture(scope="module")
def binary(tmp_path_factory):
    return refworld.build_reference(str(tmp_path_factory.mktemp("ref")))


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("seed", (3, 2**31 + 5, 123456789))
def test_control_is_not_correct(cell, seed, binary, tmp_path):
    """The reference without the graph's loss draws, compared as if it were
    the program: some host has to differ, and the reference against itself
    has to agree."""
    config, end_ms = CELLS[cell]
    w = refworld.World(small_config(config, 256), seed)
    want = refworld.run_reference(binary, w, end_ms * 1_000_000, str(tmp_path))
    again = refworld.run_reference(binary, w, end_ms * 1_000_000, str(tmp_path))
    control = refworld.run_reference(binary, w, end_ms * 1_000_000, str(tmp_path), lossless=True)
    assert all(v == 0 for v in refworld.compare(again, want).values())
    numbers = refworld.compare(control, want)
    assert numbers["hosts_differing"] > 0 and numbers["total_gap.packets_dropped"] > 0


@pytest.mark.parametrize("fault", ("state_unchanged", "answer_altered"))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_timed_path_is_not_correct(cell, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run.py"), fault, "--workload", cell,
         "--seed", "41", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["check"]["hosts_differing"]["value"] > 0
    assert "check hosts_differing:" in r.stderr


def test_routing_by_hand():
    """A 4-node line 0-1-2-3 with a shortcut 0-3 of equal latency: the
    direct edge is kept (a candidate wins only when strictly shorter)."""
    t = refworld.TIME_MAX
    lat = np.array([[2, 3, t, 9], [3, 2, 3, t], [t, 3, 2, 3], [9, t, 3, 2]], np.int64)
    rel = np.where(lat < t, np.float32(0.99), np.float32(0)).astype(np.float32)
    rel[np.arange(4), np.arange(4)] = 1.0
    out_lat, out_rel = refworld.routing(lat, rel)
    assert out_lat.tolist() == [[2, 3, 6, 9], [3, 2, 3, 6], [6, 3, 2, 3], [9, 6, 3, 2]]
    assert out_rel[0, 3] == np.float32(0.99)  # the direct edge, not the 3-hop path
    assert out_rel[0, 2] == np.float32(0.99) * np.float32(0.99)


def test_trace_reduction_on_synthetic_events():
    """Busy union, nesting, window and gap naming, on events made by hand."""

    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [
            Plane("/device:TPU:0", [Line("XLA Ops", [
                Ev("while.1", 1000, 6000),   # wraps the two fusions below
                Ev("fusion.1", 1000, 2000),
                Ev("fusion.2", 4000, 2000),
                Ev("copy.3", 8000, 1000),
            ]), Line("XLA Modules", [Ev("jit_chunk", 1000, 8000)])]),
            Plane("/host:CPU", [Line("main", [
                Ev("bench:unit", 0, 10000),
                Ev("bench:probe_fetch", 2500, 2000),
                Ev("python stuff", 0, 10000),
            ])]),
        ]

    out = trace_reduce.reduce(Profile)
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx(5000e-9)  # leaves only: 2000 + 2000 + 1000
    ops = dict(map(tuple, out["device_ops"]))
    assert ops["while.1"] == pytest.approx(2000e-9)  # self time: 6000 - 4000
    assert ops["fusion.1"] == pytest.approx(2000e-9)
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["probe_fetch"] == pytest.approx(1000e-9)  # 3000-4000, inside the span
    assert gaps["unit"] == pytest.approx(4000e-9)  # 0-1000, 6000-8000, 9000-10000
    assert trace_reduce.reduce(type("P", (), {"planes": Profile.planes[1:]})) is None


def test_trace_reduction_on_the_recorded_trace(tmp_path):
    """A short trace recorded on a TPU v5e (three replayed units of
    phold-10k.steady cut to 1,024 hosts; my chip run, PR 25), committed
    beside the expected reduction."""
    gz = os.path.join(HERE, "data", "phold_units.xplane.pb.gz")
    path = tmp_path / "t.xplane.pb"
    with gzip.open(gz, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys, json; sys.path.insert(0, %r); import trace_reduce; "
            "print(json.dumps(trace_reduce.reduce_file(%r)))" % (BENCH, str(path)))
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    want = json.load(open(os.path.join(HERE, "data", "phold_units.expected.json")))
    assert out["devices"] == 1 and out["op_events"] == want["op_events"]
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["busy_s"] == pytest.approx(want["busy_s"]) and out["window_s"] == pytest.approx(want["window_s"])
    assert [n for n, _ in out["device_ops"][:5]] == [n for n, _ in want["device_ops"][:5]]
    assert {n for n, _ in out["idle_gaps"]} == {n for n, _ in want["idle_gaps"]}
    assert sum(t for _, t in out["idle_gaps"]) == pytest.approx(out["window_s"] - out["busy_s"])


@pytest.mark.parametrize("config", ("tgen-10k", "phold-10k"))
def test_flush_bytes_against_the_programs_pricing(config):
    """roofline.flush_min_bytes on the configuration's full shapes (abstract,
    nothing allocated) equals outbox + 2 x queue of memtrack.price_state."""
    code = f"""
import json, sys
sys.path.insert(0, {ROOT!r}); sys.path.insert(0, {BENCH!r})
import shadow_tpu, jax, roofline
from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.runtime.manager import Manager
from shadow_tpu.runtime.memtrack import price_state
from shadow_tpu.runtime.scheduler import make_scheduler
raw = json.load(open({os.path.join(BENCH, 'configs', config + '.json')!r}))
w = Manager(ConfigOptions.from_dict(raw)).build_world()
s = make_scheduler("tpu", w.model, w.tables, w.ecfg, w.host_node, parallelism=1,
                   tx_bytes_per_interval=w.tx_refill, rx_bytes_per_interval=w.rx_refill)
st = jax.eval_shape(s.initial_state)
g = price_state(st)["groups"]
print(json.dumps([roofline.flush_min_bytes(st.outbox, st.queue), g["outbox"]["bytes"], g["queue"]["bytes"]]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    got, outbox, queue = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == outbox + 2 * queue > 0
