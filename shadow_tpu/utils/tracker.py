"""Host-side tracker registry: heartbeats, dispatch spans, stats folding.

The device half of the tracker plane lives in `engine/state.py`
(TrackerState, accumulated by the round engines when
EngineConfig.tracker is set; the exchange's three marks, exch_hwm /
land_hwm / land_passes, in every program) and rides the per-chunk probe
as sync-free aggregate lanes (engine/round.py PROBE_*). This module is the host half
(the analogue of the reference's per-host Tracker, src/main/host/
tracker.c:407-430, and the worker-local SimStats fold, sim_stats.rs):

  * per-host heartbeat lines — rendered at `general.heartbeat_interval`
    cadence from ONE bulk device_get of the per-host counter tensors
    (engine/round.py host_stats; the per-chunk path never fetches
    [H]-shaped state), written through shadow_log so the \r progress
    status line never interleaves. The leading four key=value fields
    keep the exact format tools/parse_shadow.py already parses for the
    managed kernel's tracker lines; the tracker plane appends its
    per-kind/per-class counters after them.

  * dispatch-pipeline spans — `span(name, **args)` context managers
    recording wall-time intervals. Spans nest by construction (a stack
    of context managers per thread), which is what makes the emitted
    Chrome trace well-formed, and every span records that nesting in
    its `args`: an `id`, its `parent` (the enclosing span of the thread,
    None at the top) and the ordinal of the `run` it belongs to (a run
    is one driver entry: one `TpuScheduler.run` without recovery
    replays). The drivers' spans, each a child of the one above it:

        run                    engine/round.py run_until and its sharded,
                               ensemble and mesh twins, entry to return
          validate_runahead
          shard_state          sharded / mesh: device_put onto the mesh
          peek_next_time       the blocking "anything to do?" round trip
                               (one chip / sharded: the entry's probe)
          put_end_time         the end time made a device scalar
          donate_copy          the caller's state copied, leaf by leaf
          entry_probe          ensemble / mesh: replicas done at entry
          compile+launch       chunk 0
            chunk_compile      its lower().compile() (cache hit or not)
          chunk_launch         every later chunk
          probe_fetch          the host blocked on a chunk's probe
          probe_decide         from a fetched probe to the next launch:
                               flight recorder, capacity, on_chunk
            host_stats_fetch   heartbeat cadence only
            state_snapshot     checkpoint cadence only
          quiescent_restore    the last chunk's `now` / idle rounds

    plus the hybrid pass/upload/drain phases and worker round-trips.
    Each span also enters `jax.profiler.TraceAnnotation("shadow:<name>")`,
    so a `--xprof-dir` capture shows the driver's spans on the host's
    line, on the clock of the device's operations.

  * a Chrome-trace JSON (`write_trace`) loadable in chrome://tracing or
    Perfetto: one "X" (complete) event per span with microsecond
    ts/dur relative to tracker construction.

  * a stats fold (`stats_dict`) for sim-stats.json: per-kind event
    counts, drop reasons, byte classes, high-water marks, round
    live/idle split, and per-phase wall-time percentiles — the
    breakdown a run's wall time is read from.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

import jax
import numpy as np

RUN_SPAN = "run"  # the root span of one driver entry

# Span-list bound: beyond this many recorded events new spans fold into
# the running per-phase totals only (the Chrome trace and percentiles
# cover the first _MAX_EVENTS spans). Keeps a million-chunk run at
# bounded memory while every progress line still shows true totals.
_MAX_EVENTS = 200_000


def _pct(sorted_ms: "list[float]", q: float) -> float:
    """Nearest-rank percentile of an ascending list (no numpy needed for
    a handful of spans)."""
    if not sorted_ms:
        return 0.0
    idx = min(len(sorted_ms) - 1, max(0, int(round(q * (len(sorted_ms) - 1)))))
    return sorted_ms[idx]


class Tracker:
    """One per run. Thread-safe for span recording (the hybrid parallel
    scheduler records worker round-trips from the parent thread while
    jax dispatch spans land from the driver)."""

    def __init__(
        self,
        host_names: "list[str] | None" = None,
        heartbeat_ns: int = 0,
        trace_path: "str | None" = None,
        clear_line=None,
        host_heartbeats: bool = True,
        counters: bool = True,
    ):
        self.host_names = list(host_names) if host_names else None
        self.heartbeat_ns = heartbeat_ns
        self.trace_path = trace_path
        self.clear_line = clear_line  # erases the \r status line first
        self.host_heartbeats = host_heartbeats
        # counters=False: span-only mode (--trace-file without --tracker):
        # the device-side TrackerState was never accumulated, so the
        # stats fold must publish phases only — zeros from an
        # unaccumulated plane would be indistinguishable from real
        # measurements
        self.counters = counters
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._ids = 0  # spans opened so far; a span's id is its ordinal
        self._runs = 0  # `run` spans opened so far
        self._open = threading.local()  # .stack: [(id, run)] of this thread
        self.events: "list[dict]" = []  # chrome-trace events, append-only
        # running per-phase wall totals (seconds), updated on every span
        # append — phase_totals() is O(phases), never O(spans), so it is
        # safe to call once per chunk inside a dispatch loop
        self._totals: "dict[str, float]" = {}
        self._next_hb = heartbeat_ns if heartbeat_ns > 0 else None
        self.last_probe = None  # latest ChunkProbe seen (aggregates)
        self._final_hosts: "dict | None" = None  # last bulk host_stats
        # independent iteration planes behind the folded host tensors:
        # iters_done sums PER-PLANE drain-loop counts (one count per
        # shard's row 0, or per replica after the ensemble flatten) while
        # each such iteration scans only H/planes lanes — the occupancy
        # denominator must shrink by the same factor or a sharded run
        # under-reports occupancy by exactly the shard count. The manager
        # sets this to num_devices (sharded) or replicas (ensemble).
        self.num_shards = 1
        # rollback-and-regrow recovery records (runtime/recovery.py):
        # folded into stats_dict and marked in the trace as instants
        self.recoveries: "list[dict]" = []
        # the autotune decision (runtime/autotune.py AutotunePlan
        # as_dict, set by the manager): the probe's measured wall and the
        # chosen rounds_per_chunk surface in stats_dict alongside the
        # `autotune_probe` span — not only in sim-stats' own block
        self.autotune: "dict | None" = None

    # --- spans -----------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _enter(self, name: str) -> dict:
        """A new span's place in the tree: its id, the span of this
        thread that encloses it, and the run that one belongs to (a
        `run` span starts the next)."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        parent, run = stack[-1] if stack else (None, None)
        with self._lock:
            self._ids += 1
            sid = self._ids
            if name == RUN_SPAN:
                self._runs += 1
                run = self._runs
        stack.append((sid, run))
        return {"id": sid, "parent": parent, "run": run}

    def _record(self, name: str, ts: float, dur: float, args: dict) -> None:
        ev = {
            "name": name,
            "cat": "dispatch",
            "ph": "X",
            "ts": ts,
            "dur": dur,
            "pid": 0,
            "tid": threading.get_ident() % (1 << 31),
            "args": args,
        }
        with self._lock:
            if len(self.events) < _MAX_EVENTS:
                self.events.append(ev)
            self._totals[name] = self._totals.get(name, 0.0) + dur / 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args):
        place = self._enter(name)
        ts = self._now_us()
        try:
            with jax.profiler.TraceAnnotation("shadow:" + name):
                yield
        finally:
            dur = self._now_us() - ts
            self._open.stack.pop()
            self._record(name, ts, dur, {**place, **args})

    def add_span(self, name: str, t_start: float, t_end: float, **args) -> None:
        """Record an already-measured interval (time.perf_counter
        timestamps) — for callers that keep their own phase clocks, like
        the parallel hybrid scheduler's phase_wall accounting."""
        place = self._enter(name)
        self._open.stack.pop()
        self._record(
            name,
            (t_start - self._t0) * 1e6,
            max(0.0, (t_end - t_start) * 1e6),
            {**place, **args},
        )

    def instant(self, name: str, **args) -> None:
        ev = {
            "name": name,
            "cat": "dispatch",
            "ph": "i",
            "ts": self._now_us(),
            "s": "g",
            "pid": 0,
            "tid": threading.get_ident() % (1 << 31),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def spans(self, name: "str | None" = None) -> "list[dict]":
        """Recorded complete-spans (optionally filtered by name), in
        record order."""
        with self._lock:
            evs = list(self.events)
        return [
            e for e in evs if e["ph"] == "X" and (name is None or e["name"] == name)
        ]

    # --- heartbeats ------------------------------------------------------

    def host_heartbeat_due(self, now_ns: int) -> bool:
        """Per-host heartbeat cadence test on the already-fetched probe
        `now` — deciding costs no device sync; only an affirmative answer
        triggers the one bulk host_stats fetch."""
        if (
            not self.host_heartbeats
            or self._next_hb is None
            or self.host_names is None
        ):
            return False
        return now_ns >= self._next_hb

    def emit_host_heartbeat(self, probe, stats: dict) -> None:
        """Render one reference-style tracker line per host from a bulk
        host_stats dict (engine/round.py). The leading four fields match
        the managed kernel's tracker lines (tools/parse_shadow.py); the
        tracker plane's per-kind/per-class counters follow."""
        from shadow_tpu.utils.shadow_log import slog

        self.record_probe(probe)
        self._final_hosts = stats
        hb = self.heartbeat_ns
        self._next_hb = (probe.now // hb + 1) * hb
        if self.clear_line is not None:
            self.clear_line()
        names = self.host_names
        n = len(stats["events_handled"])
        # run-wide adaptivity figures from the probe (the PR-9 lanes):
        # appended to every line so parse_shadow-compatible consumers see
        # the window-width/occupancy data next to the per-host counters —
        # the leading fields keep the exact parsed format (the parser
        # ignores trailing keys it does not know)
        win_mean = probe.window_ns_mean
        occ = probe.occupancy(n, self.num_shards)
        for i in range(n):
            ev = int(stats["events_handled"][i])
            evl = int(stats["ev_local"][i])
            evt = int(stats["ev_tcp"][i])
            slog(
                "info",
                probe.now,
                names[i] if names and i < len(names) else f"host{i}",
                "tracker: "
                f"bytes_sent={int(stats['bytes_sent'][i])} "
                f"bytes_recv={int(stats['bytes_recv'][i])} "
                f"packets_sent={int(stats['packets_sent'][i])} "
                f"packets_dropped={int(stats['packets_dropped'][i])} "
                f"events={ev} ev_local={evl} ev_tcp={evt} "
                f"ev_packet={ev - evl - evt} "
                f"drop_codel={int(stats['codel_dropped'][i])} "
                f"drop_unroutable={int(stats['packets_unroutable'][i])} "
                f"bytes_ctrl={int(stats['bytes_ctrl'][i])} "
                f"bytes_data={int(stats['bytes_data'][i])} "
                f"retrans={int(stats['retrans_segs'][i])} "
                f"queue_hwm={int(stats['queue_hwm'][i])} "
                f"outbox_hwm={int(stats['outbox_hwm'][i])} "
                f"lanes_live={int(stats['lanes_live'][i])} "
                f"win_mean_ns={win_mean:.0f} occupancy={occ:.4f} "
                f"land_hwm={probe.land_hwm} land_passes={probe.land_passes}",
            )

    def record_probe(self, probe) -> None:
        self.last_probe = probe

    def record_recovery(self, record: dict) -> None:
        """One rollback-and-regrow recovery happened (runtime/recovery.py):
        keep the record for the stats fold and drop an instant marker into
        the dispatch trace at the wall time it occurred."""
        self.recoveries.append(dict(record))
        self.instant("capacity_recovery", **record)

    # --- folding ---------------------------------------------------------

    def finalize(self, host_stats: "dict | None" = None, probe=None) -> None:
        """Fold the end-of-run per-host tensors (one bulk device_get,
        done by the caller via engine/round.py host_stats) and/or the
        final probe into the registry for stats_dict()."""
        if host_stats is not None:
            self._final_hosts = host_stats
        if probe is not None:
            self.last_probe = probe

    def phase_totals(self) -> dict:
        """{span name: total wall seconds} — the compact per-phase view
        of the flight recorder's post-mortem. Served from the running
        totals (O(phases), not O(spans)): emitting it once per chunk in
        a million-chunk dispatch loop costs nothing."""
        with self._lock:
            return {k: round(v, 4) for k, v in self._totals.items()}

    def phase_stats(self) -> dict:
        """{span name: {count, total_s, p50_ms, p90_ms, p99_ms, max_ms}}
        — the per-chunk timing percentiles for sim-stats.json/BENCH."""
        by_name: "dict[str, list[float]]" = {}
        for e in self.spans():
            by_name.setdefault(e["name"], []).append(e["dur"] / 1e3)
        out = {}
        for name, ms in sorted(by_name.items()):
            ms.sort()
            out[name] = {
                "count": len(ms),
                "total_s": round(sum(ms) / 1e3, 4),
                "p50_ms": round(_pct(ms, 0.50), 3),
                "p90_ms": round(_pct(ms, 0.90), 3),
                "p99_ms": round(_pct(ms, 0.99), 3),
                "max_ms": round(ms[-1], 3),
            }
        return out

    def stats_dict(self) -> dict:
        """The tracker section of sim-stats.json (reference
        sim_stats.rs:110 write_stats_to_file, with the per-kind split
        tracker.c keeps per host). Span-only trackers report only the
        phase breakdown."""
        out: dict = {"phases": self.phase_stats()}
        if self.recoveries:
            out["recoveries"] = list(self.recoveries)
        if self.autotune:
            out["autotune"] = dict(self.autotune)
        if not self.counters:
            return out
        hs = self._final_hosts
        if hs is not None:
            ev = int(sum(hs["events_handled"]))
            evl = int(sum(hs["ev_local"]))
            evt = int(sum(hs["ev_tcp"]))
            out["events_by_kind"] = {
                "local": evl,
                "tcp": evt,
                "packet": ev - evl - evt,
            }
            out["drops"] = {
                "loss": int(sum(hs["packets_dropped"])),
                "codel": int(sum(hs["codel_dropped"])),
                "unroutable": int(sum(hs["packets_unroutable"])),
            }
            out["bytes"] = {
                "ctrl": int(sum(hs["bytes_ctrl"])),
                "data": int(sum(hs["bytes_data"])),
                "retrans_segments": int(sum(hs["retrans_segs"])),
            }
            out["high_water"] = {
                "queue": int(max(hs["queue_hwm"])),
                "outbox": int(max(hs["outbox_hwm"])),
                # most entries one shard staged for one round's flush
                "exchange": int(max(hs["exch_hwm"])),
                # most arrivals one destination landed in one round
                "landing": int(max(hs["land_hwm"])),
            }
            out["rounds"] = {
                "live": int(hs["rounds_live"]),
                "idle": int(hs["rounds_idle"]),
            }
            # adaptivity: window widths + live-lane occupancy (the levers
            # of the adaptive-window/compaction round, docs/architecture.md
            # "Lookahead & compaction")
            # mean width must pair win_ns_sum with the SAME population's
            # live-round count: the ensemble flatten sums win_ns_sum
            # across replicas and supplies the summed denominator as
            # win_rounds_live (runtime/ensemble.py flatten_host_stats);
            # single runs fall back to the run's own rounds_live
            live = int(hs.get("win_rounds_live", hs["rounds_live"]))
            iters = int(np.asarray(hs["iters_done"]).sum())
            lanes = int(np.asarray(hs["lanes_live"]).sum())
            # lanes scanned per iteration: the full row count divided by
            # the iteration planes (shards / flattened replicas) whose
            # loop counts iters sums — see num_shards in __init__
            h = int(np.asarray(hs["lanes_live"]).size) // max(self.num_shards, 1)
            out["window"] = {
                "win_ns_sum": int(hs["win_ns_sum"]),
                "mean_ns": round(int(hs["win_ns_sum"]) / live, 1) if live else 0,
                "iters": iters,
                "lanes_live": lanes,
                "occupancy": round(lanes / (iters * h), 4) if iters and h else 0,
                # passes of the landing's loop (equeue.land_sorted); like
                # iters, summed over the iteration planes
                "land_passes": int(np.asarray(hs["land_passes"]).sum()),
            }
        elif self.last_probe is not None:
            p = self.last_probe
            out["events_by_kind"] = {
                "local": p.ev_local,
                "tcp": p.ev_tcp,
                "packet": p.ev_packet,
            }
            out["drops"] = {
                "loss": p.drop_loss,
                "codel": p.drop_codel,
                "unroutable": p.drop_unroutable,
            }
            out["bytes"] = {
                "ctrl": p.bytes_ctrl,
                "data": p.bytes_data,
                "retrans_segments": p.retrans_segs,
            }
            out["high_water"] = {
                "queue": p.queue_hwm, "outbox": p.outbox_hwm,
                "exchange": p.exch_hwm, "landing": p.land_hwm,
            }
            out["rounds"] = {"live": p.rounds_live, "idle": p.rounds_idle}
        return out

    # --- chrome trace ----------------------------------------------------

    def write_trace(self, path: "str | None" = None) -> "str | None":
        """Write the recorded spans as Chrome-trace JSON (the format
        chrome://tracing and Perfetto load directly). Returns the path
        written, or None when no path is configured."""
        path = path or self.trace_path
        if not path:
            return None
        with self._lock:
            events = list(self.events)
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 0,
                "args": {"name": "shadow-tpu dispatch"},
            }
        ]
        with open(path, "w") as f:
            json.dump(
                {"traceEvents": meta + events, "displayTimeUnit": "ms"}, f
            )
        return path
