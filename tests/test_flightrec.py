"""Flight recorder + metrics plane (docs/observability.md;
runtime/flightrec.py).

The contracts under test:

  * **black box on every failure path** — a chaos-injected capacity
    fault and a chaos-injected watchdog stall each leave a readable
    `flight-recorder.json` whose last sample matches the failing (resp.
    last successfully fetched) chunk's probe from a fault-free run of
    the same world — the drivers record the probe BEFORE raising;
  * **zero extra device syncs** — enabling the metrics stream adds not
    one `jax.device_get` over a plain run (the recorder reads only the
    probes the driver fetched anyway);
  * survivable degradations (engine fallback, sweep quarantine) also
    dump, and the unit surfaces (ring bound, deltas, prom snapshot,
    summary renderer) hold shape.
"""

import json
import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_pipeline import _phold_world  # noqa: E402

from shadow_tpu.engine.round import (  # noqa: E402
    CapacityError,
    ChunkProbe,
    WatchdogExpired,
    run_until,
)
from shadow_tpu.runtime import chaos, flightrec  # noqa: E402
from shadow_tpu.runtime.chaos import FaultPlan, run_with_engine_ladder  # noqa: E402
from shadow_tpu.runtime.flightrec import (  # noqa: E402
    FlightRecorder,
    failure_record,
    load_series,
    render_summary,
    render_summary_file,
)
from shadow_tpu.runtime.recovery import (  # noqa: E402
    RecoveryPolicy,
    run_until_recovering,
)
from shadow_tpu.simtime import NS_PER_MS  # noqa: E402

pytestmark = pytest.mark.metrics


def _probe(**kw) -> ChunkProbe:
    """A ChunkProbe with every cumulative lane defaulted to 0."""
    import dataclasses

    fields = {f.name: 0 for f in dataclasses.fields(ChunkProbe)}
    fields.update(kw)
    return ChunkProbe(**fields)


# ---- unit surfaces ------------------------------------------------------


def test_metrics_stream_rotates_at_size_cap(tmp_path):
    """Satellite (ISSUE 11): the JSONL stream rotates at
    general.metrics_max_mb keeping metrics_keep numbered segments, so a
    week-long daemon soak cannot fill the disk — and the live path
    always holds the newest samples."""
    import os

    mf = tmp_path / "m.jsonl"
    rec = FlightRecorder(
        num_hosts=8, metrics_path=str(mf),
        metrics_max_bytes=2_000, metrics_keep=2,
    )
    for i in range(120):
        rec.observe(_probe(now=(i + 1) * 1000, events_handled=(i + 1) * 10))
    rec.close()
    assert rec.rotations >= 2
    # keep=2: live file + .1 + .2 and nothing older
    assert mf.exists() and (tmp_path / "m.jsonl.1").exists()
    assert (tmp_path / "m.jsonl.2").exists()
    assert not (tmp_path / "m.jsonl.3").exists()
    # every segment stays under cap + one line of slack
    for p in (mf, tmp_path / "m.jsonl.1", tmp_path / "m.jsonl.2"):
        assert os.path.getsize(p) < 2_600
    # every segment parses; the newest sample lives in the newest
    # segment that has samples (the live file may hold only the
    # rotation marker when the cap fired on the final line)
    def _samples(p):
        return [
            json.loads(ln) for ln in p.read_text().splitlines()
            if json.loads(ln).get("type") == "sample"
        ]

    live, older = _samples(mf), _samples(tmp_path / "m.jsonl.1")
    newest = (live or older)[-1]["chunk"]
    assert newest == 119
    if live and older:
        assert older[-1]["chunk"] < live[0]["chunk"]  # segments ordered


def test_ring_bound_and_sample_deltas(tmp_path):
    rec = FlightRecorder(num_hosts=8, ring=4,
                         metrics_path=str(tmp_path / "m.jsonl"))
    for i in range(10):
        rec.observe(
            _probe(
                now=(i + 1) * 1000,
                events_handled=(i + 1) * 10,
                packets_sent=(i + 1) * 2,
                iters=(i + 1) * 4,
                lanes_live=(i + 1) * 16,
                rounds_live=(i + 1) * 2,
                win_ns_sum=(i + 1) * 500,
                land_passes=(i + 1) * 3,
                flush_cols=(i + 1) * 24,
                land_hwm=i,
            )
        )
    rec.close()
    assert len(rec.samples) == 4  # bounded ring
    last = rec.samples[-1]
    assert last["chunk"] == 9
    # per-chunk deltas of the cumulative lanes
    assert last["dt_ns"] == 1000 and last["events"] == 10
    assert last["win_ns_mean"] == 250.0  # 500 ns over 2 live rounds
    # the exchange's series: the chunk's landing passes and flattened
    # columns as differences, the marks as they stand
    assert (last["land_passes"], last["flush_cols"], last["land_hwm"]) == (3, 24, 9)
    assert {"land_passes", "flush_cols", "land_hwm"} <= set(flightrec.SUMMARY_FIELDS)
    # occupancy: 16 live lanes over 4 iterations of 8 lanes each
    assert last["occupancy"] == 0.5
    # cumulative totals ride every sample (the black-box matcher's key)
    assert last["events_total"] == 100
    # the stream kept ALL 10 samples even though the ring holds 4
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().splitlines()]
    assert sum(1 for l in lines if l["type"] == "sample") == 10


def test_events_counters_and_prom_snapshot(tmp_path):
    rec = FlightRecorder(num_hosts=4, prom_path=str(tmp_path / "m.prom"))
    rec.observe(_probe(now=5000, events_handled=7, packets_sent=3))
    rec.event("recovery", kind_detail="capacity")
    rec.event("engine_fallback", to="plain")
    rec.event("compile_cache", hit=True)
    rec.event("compile_cache", hit=False, wall_s=1.5)
    rec.event("checkpoint", wall_s=0.1)
    assert rec.counters["recoveries"] == 1
    assert rec.counters["engine_fallbacks"] == 1
    assert rec.counters["cache_hits"] == 1 and rec.counters["cache_misses"] == 1
    assert rec.counters["checkpoints"] == 1
    # the next sample carries the cumulative counters
    s = rec.observe(_probe(now=6000, events_handled=9, packets_sent=3))
    assert s["recoveries"] == 1 and s["engine_fallbacks"] == 1
    assert rec.write_prom(extra_gauges={"shadow_tpu_sweep_queue_depth": 3})
    prom = (tmp_path / "m.prom").read_text()
    assert "shadow_tpu_events_total 9" in prom
    assert "shadow_tpu_recoveries_total 1" in prom
    assert "shadow_tpu_compile_cache_hits_total 1" in prom
    assert "shadow_tpu_sweep_queue_depth 3" in prom
    assert "# TYPE shadow_tpu_events_total gauge" in prom


def test_failure_record_maps_exception_classes():
    err = CapacityError("boom")
    err.queue_overflow, err.injected = 5, True
    rec = failure_record(err)
    assert rec["kind"] == "capacity" and rec["queue_overflow"] == 5
    assert rec["injected"] is True
    w = failure_record(WatchdogExpired(3, 0.5))
    assert w["kind"] == "watchdog" and w["chunk"] == 3
    assert w["deadline_s"] == 0.5
    assert failure_record(ValueError("x"))["kind"] == "ValueError"


def test_summary_renderer_has_percentile_rows(tmp_path):
    rec = FlightRecorder(num_hosts=8, metrics_path=str(tmp_path / "m.jsonl"))
    for i in range(12):
        rec.observe(_probe(now=(i + 1) * 1000, events_handled=(i + 1) * 5,
                           iters=i + 1, lanes_live=(i + 1) * 2))
    rec.event("recovery", note="x")
    rec.close()
    samples, events, meta = load_series(str(tmp_path / "m.jsonl"))
    assert len(samples) == 12 and len(events) == 1
    out = render_summary(samples, events, meta)
    for token in ("p50", "p90", "p99", "12 samples", "dt_ns", "recovery"):
        assert token in out, out


# ---- black-box dumps on the chaos failure matrix ------------------------


@pytest.fixture(scope="module")
def fault_free():
    """One shared fault-free reference run: (world, per-chunk probes).
    Module-scoped — the capacity and watchdog black-box tests compare
    against the same deterministic probe series."""
    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    probes = []
    run_until(st0, end, model, tables, cfg,
              rounds_per_chunk=4, on_chunk=probes.append)
    return cfg, model, tables, st0, end, probes


def test_capacity_fault_blackbox_last_sample_is_failing_chunk(
    tmp_path, fault_free
):
    """An injected CapacityError (fail-fast: no recovery budget) leaves a
    valid flight-recorder.json whose LAST sample is the failing chunk's
    probe — the driver records the probe before raising, so the black
    box sees the chunk that died, byte-for-byte equal to the fault-free
    run's probe at that chunk."""
    cfg, model, tables, st0, end, probes = fault_free
    box = tmp_path / "flight-recorder.json"
    rec = FlightRecorder(num_hosts=cfg.num_hosts, blackbox_path=str(box))
    plan = FaultPlan(faults=[{"kind": "capacity", "at": 2}])
    with chaos.installed(plan), flightrec.installed(rec):
        with pytest.raises(CapacityError):
            run_until_recovering(
                st0, end, model, tables, cfg, rounds_per_chunk=4,
                policy=RecoveryPolicy(max_recoveries=0),
            )
    doc = json.loads(box.read_text())
    assert doc["format"] == "shadow-tpu-flight-recorder-v1"
    assert doc["failure"]["kind"] == "capacity"
    assert doc["failure"]["injected"] is True
    last = doc["samples"][-1]
    assert last is doc["samples"][-1] and last == doc["last_sample"]
    ref = probes[2]  # the fault fires at chunk 2: its probe is healthy
    assert last["chunk"] == 2
    assert last["now_ns"] == ref.now
    assert last["events_total"] == ref.events_handled
    assert last["packets_total"] == ref.packets_sent
    # the summary renderer reads the black box directly
    out = render_summary_file(str(box))
    assert "FAILURE: kind=capacity" in out and "p50" in out


def test_watchdog_stall_blackbox_dump(tmp_path, fault_free):
    """A chaos stall blowing the watchdog past its recovery budget
    leaves a black box: failure kind `watchdog` naming the chunk, the
    survived recovery counted, and the last sample matching the last
    successfully fetched chunk of a fault-free run (the stalled chunk's
    probe never arrived — that is what a stall IS)."""
    cfg, model, tables, st0, end, probes = fault_free
    box = tmp_path / "flight-recorder.json"
    rec = FlightRecorder(num_hosts=cfg.num_hosts, blackbox_path=str(box))
    plan = FaultPlan(
        faults=[{"kind": "stall", "at": 1, "stall_s": 0.3, "count": -1}]
    )
    with chaos.installed(plan), flightrec.installed(rec):
        with pytest.raises(WatchdogExpired):
            run_until_recovering(
                st0, end, model, tables, cfg, rounds_per_chunk=4,
                policy=RecoveryPolicy(max_recoveries=1),
                watchdog_s=0.05,
            )
    doc = json.loads(box.read_text())
    assert doc["failure"]["kind"] == "watchdog"
    assert doc["failure"]["chunk"] == 1
    assert doc["failure"]["deadline_s"] == 0.05
    assert doc["counters"]["recoveries"] == 1
    # chunk 0 fetched cleanly (twice: once per attempt); chunk 1 stalled
    last = doc["samples"][-1]
    assert last["chunk"] == 0
    assert last["now_ns"] == probes[0].now
    assert last["events_total"] == probes[0].events_handled
    # the survived recovery is in the event log
    kinds = [e["kind"] for e in doc["events"]]
    assert "recovery" in kinds


def test_engine_fallback_writes_blackbox(tmp_path):
    """The engine ladder's fallback is a survivable degradation: the run
    completes, but a black box records the moment the ladder acted."""
    import dataclasses

    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    box = tmp_path / "flight-recorder.json"
    rec = FlightRecorder(num_hosts=cfg.num_hosts, blackbox_path=str(box))
    pump_cfg = dataclasses.replace(cfg, engine="pump", pump_k=3)
    plan = FaultPlan(faults=[{"kind": "compile", "target": "pump"}])
    with chaos.installed(plan), flightrec.installed(rec):
        final, fallbacks = run_with_engine_ladder(
            pump_cfg,
            lambda c: run_until(st0, end, model, tables, c,
                                rounds_per_chunk=4),
        )
    assert len(fallbacks) == 1  # the run survived on plain
    doc = json.loads(box.read_text())
    assert doc["failure"]["kind"] == "engine_fallback"
    assert doc["failure"]["recovered"] is True
    assert doc["failure"]["to"] == "plain"
    assert doc["counters"]["engine_fallbacks"] == 1


def test_sweep_quarantine_writes_blackbox(tmp_path):
    """A quarantined sweep job leaves TWO black boxes: one in its own
    data directory (forensics travel with the job's outputs) and the
    service-level one."""
    from shadow_tpu.runtime.sweep import Batch, SweepService

    svc = SweepService.__new__(SweepService)
    svc.spec = types.SimpleNamespace(retry_max=0, retry_backoff_s=0.0)
    svc.clock_ns = 0
    svc.job_attempts = {}
    svc.job_records = {}
    svc.job_progress = {"j0": {"now_ns": 0, "events": 0}}
    svc.batches = []
    svc.recorder = FlightRecorder(
        blackbox_path=str(tmp_path / "flight-recorder.json")
    )
    job = types.SimpleNamespace(
        name="j0", entry="e", seed=1, priority=0, arrival_ns=0,
        group_key="g" * 16,
        config=types.SimpleNamespace(
            general=types.SimpleNamespace(
                data_directory=str(tmp_path / "jobs" / "j0")
            )
        ),
    )
    batch = Batch(jobs=[job], base_seed=1, stride=1, priority=0,
                  arrival_ns=0, group_key=job.group_key, index=0)
    err = CapacityError("saturated")
    err.queue_overflow = 3
    svc._handle_failure(batch, err, pending=[])
    assert svc.job_records["j0"]["status"] == "failed"
    for path in (tmp_path / "jobs" / "j0" / "flight-recorder.json",
                 tmp_path / "flight-recorder.json"):
        doc = json.loads(path.read_text())
        assert doc["failure"]["kind"] == "capacity"
        assert doc["failure"]["job"] == "j0"
        assert doc["failure"]["queue_overflow"] == 3
    # the batch failure is an event in the service telemetry
    assert "batch_failure" in [e["kind"] for e in svc.recorder.events]


# ---- the zero-extra-syncs pin ------------------------------------------


def test_metrics_stream_adds_zero_device_fetches(tmp_path, monkeypatch):
    """Enabling the full metrics plane (recorder + JSONL stream) costs
    ZERO additional jax.device_get calls over a plain run: every sample
    is a delta of the probe the driver fetched anyway."""
    import jax

    cfg, model, tables, st0 = _phold_world()
    end = 40 * NS_PER_MS
    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)

    run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    plain = calls["n"]
    assert plain > 0  # the probe fetches are counted

    calls["n"] = 0
    rec = FlightRecorder(num_hosts=cfg.num_hosts,
                         metrics_path=str(tmp_path / "m.jsonl"))
    with flightrec.installed(rec):
        run_until(st0, end, model, tables, cfg, rounds_per_chunk=4)
    rec.close()
    assert len(rec.samples) > 0  # the plane was actually on
    # and the exchange's series came with the probe (tracker off)
    assert sum(s["flush_cols"] for s in rec.samples) >= sum(s["land_passes"] for s in rec.samples) > 0
    assert calls["n"] == plain  # and cost zero extra fetches
