"""The tracker plane (engine/state.py TrackerState + utils/tracker.py).

Contracts pinned here:

  * counters are leaf-exact identical across the plain and pump
    engines (classification is by event kind / wire size /
    flow-table delta — properties of the event sequence, which the
    engines already reproduce bit-identically);
  * tracker ON vs OFF leaves the SimState trajectory leaf-exact
    unchanged (tracker leaves are write-only);
  * the pipelined driver stays leaf-exact vs the synchronous driver
    with the tracker enabled (the quiescent-extra-chunk path restores
    the round counters from the probe, like `now`);
  * heartbeat lines and sim-stats.json keep a golden shape on phold and
    tgen, and the per-host lines stay parseable by tools/parse_shadow.py;
  * the Chrome trace is valid JSON with well-nested spans;
  * `--tracker --trace-file` runs end-to-end from the CLI on CPU (the
    tier-1 tooling smoke) and the CapacityError names the saturated
    counter.
"""

import contextlib
import dataclasses
import io
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu.engine.round import (
    CapacityError,
    check_capacity,
    host_stats,
    run_until,
)
from shadow_tpu.simtime import NS_PER_MS
from shadow_tpu.utils.tracker import Tracker


def _assert_leaves_exact(a, b, skip=None):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        key = jax.tree_util.keystr(path)
        if skip and skip in key:
            continue
        assert jnp.array_equal(la, lb), f"mismatch at {key}"


TRACKER_LEAVES = (
    "ev_local", "ev_tcp", "bytes_ctrl", "bytes_data", "retrans_segs",
    "queue_hwm", "outbox_hwm", "rounds_idle",
)


# --- cross-engine / on-off equivalence ----------------------------------


def test_tracker_counters_cross_engine_pump_tgen():
    """Tier-1 tentpole pin: with the tracker on, a full tgen run under
    shaping+loss is leaf-exact identical (including every TrackerState
    leaf) between the plain engine and the pump microscan."""
    cfg0, model, tables, st0 = _tgen_world(8, 0.02, 20_000_000, seed=3)
    end = 30 * NS_PER_MS
    plain = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg0, engine="plain", tracker=True),
        rounds_per_chunk=8,
    )
    pump = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg0, engine="pump", pump_k=3, tracker=True),
        rounds_per_chunk=8,
    )
    tr = plain.tracker
    # the world actually exercised the counters
    assert int(tr.ev_tcp.sum()) > 0 or int(tr.ev_local.sum()) > 0
    assert int(tr.bytes_data.sum()) > 0
    assert int(tr.queue_hwm.max()) > 0
    for name in TRACKER_LEAVES:
        assert jnp.array_equal(
            getattr(plain.tracker, name), getattr(pump.tracker, name)
        ), name


def test_tracker_on_off_trajectory_unchanged_phold():
    """cfg.tracker must be write-only observability: every non-tracker
    leaf of the final state is identical with the plane on or off (and
    off leaves the tracker leaves at zero — it costs nothing)."""
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    off = run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    on = run_until(
        st0, end, model, tables,
        dataclasses.replace(cfg, tracker=True),
        rounds_per_chunk=4,
    )
    _assert_leaves_exact(off, on, skip=".tracker")
    for name in TRACKER_LEAVES:
        assert int(jnp.sum(getattr(off.tracker, name))) == 0, name
    # the live rounds are counted on the state itself, tracker on or off
    assert int(on.rounds_live) == int(off.rounds_live) > 0
    assert int(jnp.sum(on.tracker.ev_local)) > 0


def test_tracker_pipelined_matches_sync():
    """The depth-2 pipeline stays leaf-exact with the tracker enabled:
    the quiescent extra chunk's idle-round counts are restored from the
    probe exactly like `now`."""
    cfg0, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg0, tracker=True)
    end = 40 * NS_PER_MS
    sync = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=False
    )
    piped = run_until(
        st0, end, model, tables, cfg, rounds_per_chunk=4, pipeline=True
    )
    _assert_leaves_exact(sync, piped)


@pytest.mark.slow
def test_tracker_sharded_matches_single_device():
    """Sharded over the 8-virtual-device mesh, the tracker leaves come
    back identical to the single-device run (probe lanes psum/pmax over
    the mesh; per-host rows exchange-invariant)."""
    import numpy as np
    from jax.sharding import Mesh

    from test_sharded import _setup

    from shadow_tpu.engine import ShardedRunner
    from shadow_tpu.engine.sharded import AXIS

    cfg0, model, tables, st0 = _setup(num_hosts=16)
    cfg = dataclasses.replace(cfg0, tracker=True)
    end = 50 * NS_PER_MS
    single = run_until(st0, end, model, tables, cfg, rounds_per_chunk=16)
    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=16)
    sharded = runner.run_until(st0, end)
    for name in TRACKER_LEAVES:
        np.testing.assert_array_equal(
            np.asarray(getattr(single.tracker, name)),
            np.asarray(getattr(sharded.tracker, name)),
            err_msg=name,
        )


# --- probe / heartbeat / stats shapes -----------------------------------


def test_probe_tracker_lanes_consistent():
    """The widened probe's tracker lanes agree with the final state's
    counters, and ev_packet derives correctly."""
    cfg0, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg0, tracker=True)
    probes = []
    st = run_until(
        st0, 20 * NS_PER_MS, model, tables, cfg,
        rounds_per_chunk=4, on_chunk=probes.append,
    )
    p = probes[-1]
    assert p.events_handled == int(st.events_handled.sum())
    assert p.ev_local == int(st.tracker.ev_local.sum())
    assert p.ev_tcp == int(st.tracker.ev_tcp.sum())
    assert p.ev_packet == p.events_handled - p.ev_local - p.ev_tcp
    assert p.drop_loss == int(st.packets_dropped.sum())
    assert p.queue_hwm == int(st.tracker.queue_hwm.max())
    assert p.outbox_hwm == int(st.tracker.outbox_hwm.max())
    assert p.rounds_live == int(st.rounds_live)
    assert p.rounds_live > 0
    assert p.queue_overflow == 0 and p.outbox_overflow == 0


def _staged(st, dst_by_row):
    """`st` with one staged packet per listed outbox row: row r sends to
    dst_by_row[r]."""
    ob, n = st.outbox, len(dst_by_row)
    return st.replace(
        outbox=ob.replace(
            valid=ob.valid.at[:n, 0].set(True),
            dst=ob.dst.at[:n, 0].set(jnp.asarray(dst_by_row, jnp.int32)),
            time=ob.time.at[:n, 0].set(7 * NS_PER_MS),
            tie=ob.tie.at[:n, 0].set(jnp.arange(n) + 1),
            fill=ob.fill.at[:n].set(1),
        )
    )


@pytest.mark.parametrize("tracker", [True, False], ids=["on", "off"])
def test_land_counters_book_the_landings_loop(tracker):
    """land_hwm is the most arrivals one destination landed in one round,
    land_passes the sum over the flushes of ceil(that round's mark / K);
    both on row 0, and both counted whatever cfg.tracker says."""
    from shadow_tpu import equeue
    from shadow_tpu.engine.round import ChunkProbe, flush_outbox, state_probe
    from shadow_tpu.engine.state import EngineConfig, init_state

    k = equeue.LAND_LANES
    hosts = 3 * k + 4
    cfg = EngineConfig(
        num_hosts=hosts, queue_capacity=4 * k, outbox_capacity=2,
        runahead_ns=NS_PER_MS, tracker=tracker,
    )
    st = init_state(cfg, model_state=())
    fan_ins = [1, k, k + 1, 3 * k + 1, 2]
    for n in fan_ins:  # n rows send to host 5, one more to host 0
        st = flush_outbox(_staged(st, [5] * n + [0]), None, cfg)
        st = st.replace(queue=equeue.create(hosts, 4 * k))
    want_hwm = max(fan_ins)
    want_passes = sum(-(-n // k) for n in fan_ins)
    assert st.tracker.land_hwm.tolist() == [want_hwm] + [0] * (hosts - 1)
    assert st.tracker.land_passes.tolist() == [want_passes] + [0] * (hosts - 1)
    probe = ChunkProbe.from_array(state_probe(st))
    assert (probe.land_hwm, probe.land_passes) == (want_hwm, want_passes)
    assert probe.exch_hwm == 0  # run_round's own sample, not the flush's


def test_ensemble_flatten_pairs_window_numerator_and_denominator():
    """mean_ns = win_ns_sum / live must take BOTH terms from the same
    population: the ensemble flatten sums win_ns_sum across replicas and
    ships the summed live-round denominator as win_rounds_live — maxing
    each independently would divide replica A's width sum by replica B's
    round count and publish a mean no replica actually had."""
    import numpy as np

    from shadow_tpu.runtime.ensemble import flatten_host_stats

    hs = {
        "rounds_live": np.array([10, 20]),
        "rounds_idle": np.array([1, 2]),
        "win_ns_sum": np.array([100_000_000, 60_000_000]),
        "lanes_live": np.ones((2, 3), np.int64),
    }
    out = flatten_host_stats(hs)
    assert out["win_ns_sum"] == 160_000_000
    assert out["win_rounds_live"] == 30  # -> weighted mean ~5.33e6, exact
    assert out["rounds_live"] == 20  # the rounds block keeps its max
    assert out["lanes_live"].shape == (6,)


def test_window_occupancy_scales_by_iteration_planes():
    """The occupancy denominator must shrink by the iteration-plane
    count: iters_done sums PER-PLANE drain-loop counts (one per shard's
    row 0, or per replica after the ensemble flatten) while each such
    iteration scans only H/planes lanes — without the correction a
    sharded fold under-reports occupancy by exactly the shard factor."""
    cfg0, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg0, tracker=True)
    names = [f"h{i}" for i in range(cfg.num_hosts)]
    st = run_until(st0, 40 * NS_PER_MS, model, tables, cfg, rounds_per_chunk=4)
    tr1 = Tracker(host_names=names)
    tr1.finalize(host_stats(st))
    occ1 = tr1.stats_dict()["window"]["occupancy"]
    # a plane count that divides H, like the scheduler enforces for shards
    planes = 2
    assert cfg.num_hosts % planes == 0
    tr2 = Tracker(host_names=names)
    tr2.num_shards = planes
    tr2.finalize(host_stats(st))
    occ2 = tr2.stats_dict()["window"]["occupancy"]
    assert occ1 > 0
    assert occ2 == pytest.approx(occ1 * planes, rel=0.05)


def test_heartbeat_lines_and_stats_fold_phold():
    """Driving with a Tracker attached renders per-host heartbeat lines
    in the format tools/parse_shadow.py parses, and the end-of-run fold
    has the golden sim-stats shape."""
    import re
    import sys

    from shadow_tpu.utils import shadow_log

    cfg0, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg0, tracker=True)
    names = [f"h{i}" for i in range(cfg.num_hosts)]
    tracker = Tracker(host_names=names, heartbeat_ns=10 * NS_PER_MS)
    sink = io.StringIO()
    shadow_log.set_sink(sink)
    try:
        st = run_until(
            st0, 40 * NS_PER_MS, model, tables, cfg,
            rounds_per_chunk=4, tracker=tracker,
        )
    finally:
        shadow_log.flush()
        shadow_log.set_sink(None)
    out = sink.getvalue()
    lines = [ln for ln in out.splitlines() if "tracker: " in ln]
    assert lines, out
    # the leading fields stay parse_shadow-compatible
    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))
    try:
        from parse_shadow import TRACKER

        assert TRACKER.search(lines[0]), lines[0]
    finally:
        sys.path.pop(0)
    pat = re.compile(
        r"tracker: bytes_sent=\d+ bytes_recv=\d+ packets_sent=\d+ "
        r"packets_dropped=\d+ events=\d+ ev_local=\d+ ev_tcp=\d+ "
        r"ev_packet=\d+ drop_codel=\d+ drop_unroutable=\d+ bytes_ctrl=\d+ "
        r"bytes_data=\d+ retrans=\d+ queue_hwm=\d+ outbox_hwm=\d+"
    )
    for ln in lines:
        assert pat.search(ln), ln

    tracker.finalize(host_stats(st))
    stats = tracker.stats_dict()
    assert set(stats["events_by_kind"]) == {"local", "tcp", "packet"}
    assert set(stats["drops"]) == {"loss", "codel", "unroutable"}
    assert set(stats["bytes"]) == {"ctrl", "data", "retrans_segments"}
    assert set(stats["high_water"]) == {"queue", "outbox", "exchange", "landing"}
    assert set(stats["rounds"]) == {"live", "idle"}
    total = sum(stats["events_by_kind"].values())
    assert total == int(st.events_handled.sum())
    assert stats["rounds"]["live"] > 0
    assert "probe_fetch" in stats["phases"]
    assert stats["phases"]["probe_fetch"]["count"] >= 3


@pytest.mark.slow
def test_heartbeat_and_stats_fold_tgen():
    """The tgen golden-shape check: TCP traffic populates the byte
    classes and the tcp event kind; heartbeat lines render for every
    host."""
    from shadow_tpu.utils import shadow_log

    cfg0, model, tables, st0 = _tgen_world(8, 0.02, 20_000_000, seed=3)
    cfg = dataclasses.replace(cfg0, tracker=True)
    names = [f"host{i}" for i in range(cfg.num_hosts)]
    tracker = Tracker(host_names=names, heartbeat_ns=5 * NS_PER_MS)
    sink = io.StringIO()
    shadow_log.set_sink(sink)
    try:
        st = run_until(
            st0, 30 * NS_PER_MS, model, tables, cfg,
            rounds_per_chunk=4, tracker=tracker,
        )
    finally:
        shadow_log.flush()
        shadow_log.set_sink(None)
    lines = [ln for ln in sink.getvalue().splitlines() if "tracker: " in ln]
    assert len(lines) >= cfg.num_hosts
    tracker.finalize(host_stats(st))
    stats = tracker.stats_dict()
    assert stats["events_by_kind"]["tcp"] > 0
    assert stats["bytes"]["data"] > 0
    assert stats["bytes"]["ctrl"] > 0
    assert stats["high_water"]["queue"] > 0


# --- chrome trace -------------------------------------------------------


def test_chrome_trace_valid_and_well_nested(tmp_path):
    """A 3-chunk CPU run emits a Perfetto-loadable trace: valid JSON,
    every complete-span has numeric ts/dur, and spans on one thread are
    well-nested (disjoint or contained — never partially overlapping)."""
    cfg0, model, tables, st0 = _phold_world()
    cfg = dataclasses.replace(cfg0, tracker=True)
    path = tmp_path / "trace.json"
    tracker = Tracker(trace_path=str(path))
    probes = []
    run_until(
        st0, 20 * NS_PER_MS, model, tables, cfg,
        rounds_per_chunk=4, on_chunk=probes.append, tracker=tracker,
    )
    assert len(probes) >= 3  # at least 3 chunks dispatched
    assert tracker.write_trace() == str(path)
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"compile+launch", "chunk_launch", "probe_fetch", "donate_copy"} <= names
    for e in spans:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # well-nested per thread
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    eps = 1e-3  # float-us rounding slack
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        for i, a in enumerate(evs):
            for b in evs[i + 1 :]:
                a0, a1 = a["ts"], a["ts"] + a["dur"]
                b0, b1 = b["ts"], b["ts"] + b["dur"]
                disjoint = b0 >= a1 - eps
                contained = b1 <= a1 + eps
                assert disjoint or contained, (a, b)


# --- the entry's probe and its newest chunk's, kept for readers ------------


@pytest.mark.parametrize("driver", ("single", "sharded"))
def test_an_entry_keeps_its_probe_and_its_newest_chunks(driver):
    """A driver entry fetches the probe of the state it starts from (its
    `next_time` lane is the quiescence test) and keeps it, with the newest
    chunk's, as `scopes.last_probes`: a warm state's counters are not
    zero, so what the entry did is the difference. Tracker off: the live
    rounds and the mean window width are counted all the same."""
    from shadow_tpu import scopes

    cfg, model, tables, st0 = _phold_world(64)
    if driver == "sharded":
        import numpy as np
        from jax.sharding import Mesh

        from shadow_tpu.engine.sharded import AXIS, ShardedRunner

        mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
        runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)

        def run(st, end, **kw):
            return runner.run_until(st, end, **kw)

    else:

        def run(st, end, **kw):
            return run_until(st, end, model, tables, cfg, rounds_per_chunk=4, **kw)

    warm = run(st0, 20 * NS_PER_MS)
    probes = []
    out = run(warm, 40 * NS_PER_MS, on_chunk=probes.append)
    kept = scopes.last_probes
    assert kept.hosts == 64 and kept.chunk == probes[-1]
    # rows x outbox capacity over all shards: what a round's flushes flatten
    assert kept.outbox_slots == 64 * cfg.outbox_capacity == out.outbox.valid.size
    assert kept.entry.rounds_live == int(warm.rounds_live) > 0
    assert kept.entry.now == int(warm.now)
    assert kept.entry.events_handled == int(warm.events_handled.sum())
    assert kept.entry.iters == int(warm.iters_done.sum())
    did = kept.chunk.rounds_live - kept.entry.rounds_live
    assert did == int(out.rounds_live) - int(warm.rounds_live) > 0
    assert int(out.tracker.rounds_idle) == 0  # the tracker is off
    assert probes[-1].window_ns_mean == int(out.win_ns_sum) / int(out.rounds_live) > 0
    # an entry that finds nothing to do keeps its probe and no chunk's
    assert run(out, 40 * NS_PER_MS) is not None
    assert scopes.last_probes.chunk is None
    assert scopes.last_probes.entry.rounds_live == int(out.rounds_live)


# --- the span tree: ids, parents, one `run` root per driver entry ---------


DRIVER_SPANS = {
    "run", "validate_runahead", "peek_next_time", "put_end_time", "donate_copy",
    "compile+launch", "chunk_compile", "chunk_launch", "probe_fetch",
    "probe_decide", "quiescent_restore",
}


def _driver_spans(sharded):
    # the sharded runner validates the runahead once, when it is built
    return DRIVER_SPANS - {"validate_runahead"} | {"shard_state"} if sharded else DRIVER_SPANS


def _drive_thrice(driver):
    """Three entries of one driver under one Tracker (the first compiles)."""
    cfg, model, tables, st0 = _phold_world(64)
    tracker = Tracker()
    if driver == "sharded":
        import numpy as np
        from jax.sharding import Mesh

        from shadow_tpu.engine.sharded import AXIS, ShardedRunner

        mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
        runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=16)

        def run():
            return runner.run_until(st0, 40 * NS_PER_MS, tracker=tracker)

    else:

        def run():
            return run_until(
                st0, 40 * NS_PER_MS, model, tables, cfg,
                rounds_per_chunk=16, tracker=tracker,
            )

    for _ in range(3):
        run()
    return tracker.spans()


@pytest.mark.parametrize("driver", ["single", "sharded"])
def test_spans_form_one_tree_per_run(driver):
    """Every span carries an id, its parent and its run; a driver entry is
    one `run` root with every other span of the entry below it; and what a
    `run` spends outside all of its children is under 5 % of it (in the
    entry that compiles, and in the quieter of the two that do not: what
    is left there is the tracker's own bookkeeping between spans)."""
    spans = _drive_thrice(driver)
    by_id = {e["args"]["id"]: e for e in spans}
    assert len(by_id) == len(spans)  # ids are unique
    roots = [e for e in spans if e["args"]["parent"] is None]
    assert [e["name"] for e in roots] == ["run"] * 3
    assert [e["args"]["run"] for e in roots] == [1, 2, 3]
    want = _driver_spans(sharded=driver == "sharded")
    outside = []
    for root in roots:
        mine = [e for e in spans if e["args"]["run"] == root["args"]["run"]]
        assert {e["name"] for e in mine} == want
        for e in mine:
            if e is root:
                continue
            parent = by_id[e["args"]["parent"]]
            assert parent["args"]["run"] == root["args"]["run"]
            # a child lies inside its parent, on the tracker's one clock
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
        by_name = {e["name"]: e for e in mine}
        assert by_name["chunk_compile"]["args"]["parent"] == by_name["compile+launch"]["args"]["id"]
        children = [e for e in mine if e["args"]["parent"] == root["args"]["id"]]
        outside.append((root["dur"] - sum(e["dur"] for e in children)) / root["dur"])
    assert 0 <= outside[0] < 0.05 and 0 <= min(outside[1:]) < 0.05, outside


def test_span_tree_is_per_thread_and_add_span_joins_it():
    """A span opened on another thread is no child of this thread's open
    span, and an already-measured interval (add_span) is booked under the
    span that is open where it is recorded."""
    import threading
    import time

    tracker = Tracker()

    def elsewhere():
        with tracker.span("elsewhere"):
            pass

    with tracker.span("run"):
        with tracker.span("outer"):
            t = threading.Thread(target=elsewhere)
            t.start()
            t.join()
            t0 = time.perf_counter()
            tracker.add_span("measured", t0, t0 + 0.001, worker=3)
    with tracker.span("after"):
        pass
    spans = {e["name"]: e["args"] for e in tracker.spans()}
    assert spans["outer"]["parent"] == spans["run"]["id"]
    assert spans["elsewhere"]["parent"] is None and spans["elsewhere"]["run"] is None
    assert spans["measured"]["parent"] == spans["outer"]["id"]
    assert spans["measured"]["run"] == 1 and spans["measured"]["worker"] == 3
    assert spans["after"] == {"id": spans["after"]["id"], "parent": None, "run": None}


class _SeamTracker:
    """What benchmarks/run.py hands the driver: `span` and
    `host_heartbeat_due`, and nothing else."""

    def __init__(self):
        self.names = []

    @contextlib.contextmanager
    def span(self, name, **_args):
        self.names.append(name)
        yield

    def host_heartbeat_due(self, _now):
        return False


@pytest.mark.parametrize("devices", [1, 4])
def test_duck_typed_tracker_drives_scheduler_run(devices):
    """The tracker seam stays duck-typed: an object with only `span` and
    `host_heartbeat_due` takes `TpuScheduler.run` to the end, one device
    or sharded, and sees every span of the driver."""
    from shadow_tpu.runtime.scheduler import TpuScheduler

    cfg, model, tables, st0 = _phold_world(8)
    sched = TpuScheduler(model, tables, cfg, parallelism=devices, rounds_per_chunk=4)
    assert sched.num_devices == devices
    seam = _SeamTracker()
    probes = []
    out = sched.run(20 * NS_PER_MS, on_chunk=probes.append, tracker=seam, start_state=st0)
    assert probes and int(out.events_handled.sum()) == probes[-1].events_handled > 0
    want = _driver_spans(sharded=devices > 1)
    assert set(seam.names) == want
    assert seam.names[0] == "run"


# --- CLI / manager end-to-end (the tier-1 tooling smoke) ----------------


CLI_YAML = """
general:
  stop_time: "120 ms"
  seed: 5
  heartbeat_interval: "50 ms"
  data_directory: {data_dir}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 ]
        node [ id 1 ]
        edge [ source 0 target 0 latency "1 ms" ]
        edge [ source 1 target 1 latency "1 ms" ]
        edge [ source 0 target 1 latency "5 ms" packet_loss 0.02 ]
      ]
experimental:
  queue_capacity: 32
hosts:
  node:
    network_node_id: 0
    quantity: 4
    processes:
      - path: phold
        args: {{ min_delay: "1 ms", max_delay: "8 ms" }}
"""


def test_cli_tracker_trace_end_to_end(tmp_path):
    """`shadow-tpu run --tracker --trace-file` on CPU produces a
    Perfetto-loadable trace and a sim-stats.json carrying per-kind event
    counts, drop reasons, and high-water marks."""
    from shadow_tpu.cli import main

    data = tmp_path / "data"
    conf = tmp_path / "c.yaml"
    conf.write_text(CLI_YAML.format(data_dir=data))
    trace = tmp_path / "trace.json"
    assert main(["run", str(conf), "--tracker", "--trace-file", str(trace)]) == 0
    stats = json.loads((data / "sim-stats.json").read_text())
    tr = stats["tracker"]
    assert sum(tr["events_by_kind"].values()) == stats["events_handled"]
    assert set(tr["drops"]) == {"loss", "codel", "unroutable"}
    assert tr["high_water"]["queue"] > 0
    assert tr["rounds"]["live"] > 0
    assert "compile+launch" in tr["phases"]
    doc = json.loads(trace.read_text())
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


# --- satellites ---------------------------------------------------------


def test_capacity_error_names_saturated_counter():
    """The capacity check names WHICH counter saturated (queue vs
    outbox) instead of only the total."""
    cfg, model, tables, st0 = _phold_world()
    bad = st0.replace(
        queue=st0.queue.replace(overflow=st0.queue.overflow.at[0].add(3))
    )
    with pytest.raises(CapacityError, match=r"queue\.overflow=3") as ei:
        check_capacity(bad)
    assert "saturated: queue" in str(ei.value)
    bad2 = st0.replace(
        outbox=st0.outbox.replace(overflow=st0.outbox.overflow.at[0].add(2))
    )
    with pytest.raises(CapacityError, match=r"outbox\.overflow=2") as ei:
        check_capacity(bad2)
    assert "saturated: outbox/exchange" in str(ei.value)
    # the chunk driver raises the same enriched error from the probe lanes
    with pytest.raises(CapacityError, match=r"queue\.overflow=3"):
        run_until(
            bad, 400 * NS_PER_MS, model, tables, cfg,
            rounds_per_chunk=4,
        )


def test_progress_line_renders_rates(capsys):
    """The status line shows sync-free events/sec and sim-sec/wall-sec
    once it has two probe samples."""
    from shadow_tpu.utils.progress import ProgressLine

    p = ProgressLine(enabled=True)
    p.update(100_000_000, 1_000_000_000, events=1000)
    p._last = 0.0  # bypass the 0.5 s render throttle
    p.update(300_000_000, 1_000_000_000, events=51_000)
    err = capsys.readouterr().err
    assert "ev/s" in err and "sim-s/s" in err
    p.finish(1_000_000_000)
