#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process that owns the chip. It reads the cell from
`BENCHMARK.json`, the cell's parameters from `benchmarks/cells/<cell>.json`
and the configuration's file, builds the world through the program's front
door (`Manager.build_world` + `make_scheduler("tpu")`), advances to the
cell's warm point (all of that is `setup_s`), and then replays one fixed
unit of simulated time, `TpuScheduler.run(warm + unit, start_state=warm)`,
until `--seconds` of wall time have passed. After the window it reads the
device's peak memory, then runs the plain reference
(`benchmarks/reference/`) over the same simulated span and compares every
per-host counter of the last timed unit with it.

The last line of standard output is the result object of the contract;
everything else goes on earlier lines. `--rehearse` (tests only) runs the
cell's `rehearse` size on whatever platform JAX has and reports no time:
only counts keep their values.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NS_PER_MS = 1_000_000
TRACED_UNITS = 3
# the unit totals that have to repeat exactly from unit to unit (ChunkProbe lanes)
UNIT_TOTALS = ("now", "next_time", "events_handled", "packets_sent", "drop_loss",
               "drop_codel", "drop_unroutable", "iters", "overflow")


def say(msg: str) -> None:
    print(msg, flush=True)


class Refused(Exception):
    """The run cannot be made as asked; no result line is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_reader(metric: str):
    """The per-layer metric's own file: benchmarks/layer_metrics/<name>.py
    with one function `read(ctx)` that returns a number or None."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise Refused(f"per-layer metric {metric!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location("layer_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"BENCHMARK.json has no workload {name!r} (has: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise Refused(f"workload {name!r} names no known configuration")
    params = load_json(os.path.join(HERE, "cells", name + ".json"))
    return cell, configs[cell["config"]], params


def metrics_of(bench: dict, group: str, cell: str) -> "list[dict]":
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


class SpanTracker:
    """The program's own driver spans (`compile+launch`, `donate_copy`,
    `chunk_launch`, `probe_fetch`), taken through the `tracker=` seam of
    `TpuScheduler.run`: each is timed on the host clock and, while the
    profiler runs, written into its trace so that an idle gap on the
    device can be named by what the host was doing."""

    def __init__(self):
        self.spans = []  # (name, start_s, end_s)

    @contextlib.contextmanager
    def span(self, name, **_args):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def host_heartbeat_due(self, _now) -> bool:
        return False


class Context:
    """What a per-layer metric's reader is given."""

    def __init__(self):
        self.cell = self.params = None
        self.chips = 1
        self.device_kind = ""
        self.peaks = {}
        self.phases = {}  # set-up phase -> seconds
        self.unit_s = []  # wall seconds of every completed unit of the window
        self.window_s = 0.0
        self.events_per_unit = 0
        self.iters_per_unit = 0
        self.trace = None  # trace_reduce.reduce()'s dict, in a --trace 1 run
        self.spans = []
        self._pieces = None
        self._pieces_fn = None

    def pieces(self):
        """Blocked-call timings of the handler iteration and the flush on
        the warm state (benchmarks/pieces.py); measured once, on first use,
        and None where they do not apply (several chips)."""
        if self._pieces is None and self._pieces_fn is not None:
            self._pieces = self._pieces_fn()
            self._pieces_fn = None
        return self._pieces


def device_block(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def count_compiles():
    """A list that grows by one for every backend compile (a persistent-
    cache load included) from now on."""
    import jax.monitoring

    seen = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def probe_totals(p) -> dict:
    return {k: int(getattr(p, k)) for k in UNIT_TOTALS}


def run(args) -> int:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config_entry, params = find_cell(bench, args.workload)
    chips = int(cell["chips"])
    wanted = metrics_of(bench, "per_layer" if args.trace else "end_to_end", cell["name"])
    readers = {m["name"]: load_reader(m["name"]) for m in wanted} if args.trace else {}

    import jax

    sys.path.insert(0, ROOT)
    try:
        import shadow_tpu  # noqa: F401 — turns on x64 before any array exists
        from shadow_tpu.config.options import ConfigOptions
        from shadow_tpu.engine.round import CapacityError, host_stats
        from shadow_tpu.runtime.compile_cache import place_persistent_cache
        from shadow_tpu.runtime.manager import Manager
        from shadow_tpu.runtime.scheduler import make_scheduler
    except ImportError as e:
        raise Refused(f"the program is not beside the benchmark: {e}") from e

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        raise Refused(f"JAX found no accelerator (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"cell {cell['name']} needs {chips} chips, JAX sees {len(devices)}")
    devices = devices[:chips]

    sys.path.insert(0, HERE)
    import roofline
    import trace_reduce
    from reference import world as refworld

    ctx = Context()
    ctx.cell, ctx.params, ctx.chips = cell, params, chips
    ctx.device_kind = devices[0].device_kind
    if not args.rehearse:
        ctx.peaks = roofline.peaks_for(ctx.device_kind)

    # ---- set-up: all of it is setup_s ------------------------------------
    compiles = count_compiles()
    # None where JAX_COMPILATION_CACHE_DIR already places the cache
    cache_dir = place_persistent_cache() or os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    entries_before = cache_entries(cache_dir)
    raw = load_json(os.path.join(ROOT, config_entry["file"]))
    raw["general"]["seed"] = args.seed
    if args.rehearse:
        per, rem = divmod(int(params["rehearse"]["hosts"]), len(raw["hosts"]))
        if rem or per < 1:
            raise Refused("the rehearsal's hosts do not divide over the host groups")
        for spec in raw["hosts"].values():
            spec["quantity"] = per
    ref_config = json.loads(json.dumps(raw))  # the reference's own copy
    warm_ns = int(params["warm_sim_ms"]) * NS_PER_MS
    unit_ns = int(params["unit_sim_ms"]) * NS_PER_MS
    end_ns = warm_ns + unit_ns

    t0 = time.perf_counter()
    config = ConfigOptions.from_dict(raw)
    world = Manager(config).build_world()
    sched = make_scheduler(
        "tpu", world.model, world.tables, world.ecfg, world.host_node,
        parallelism=chips, rounds_per_chunk=config.experimental.rounds_per_chunk,
        tx_bytes_per_interval=world.tx_refill, rx_bytes_per_interval=world.rx_refill,
    )
    if sched.num_devices != chips:
        raise Refused(f"the scheduler took {sched.num_devices} devices, the cell asks for {chips}")
    s0 = sched.initial_state()
    jax.block_until_ready(s0)
    ctx.phases["build_world_s"] = time.perf_counter() - t0

    tracker = SpanTracker() if args.trace else None

    def advance(state, to_ns):
        probes = []
        out = sched.run(to_ns, start_state=state, on_chunk=probes.append, tracker=tracker)
        jax.block_until_ready(out)
        return out, probes[-1]

    t0 = time.perf_counter()
    s_warm, warm_probe = advance(s0, warm_ns)
    t1 = time.perf_counter()
    del s0
    if tracker is not None:
        cl = [e for (n, _s, e) in tracker.spans if n == "compile+launch"]
        ctx.phases["compile_load_s"] = (cl[0] if cl else t1) - t0
    # one untimed unit: proves the replay and gives the reference unit's totals
    _s, unit_probe = advance(s_warm, end_ns)
    del _s
    want_totals = probe_totals(unit_probe)
    if want_totals["overflow"]:
        raise Refused(f"the untimed unit overflowed a capacity: {want_totals}")
    ctx.events_per_unit = unit_probe.events_handled - warm_probe.events_handled
    ctx.iters_per_unit = unit_probe.iters - warm_probe.iters
    if ctx.events_per_unit <= 0:
        raise Refused("the unit handles no event")
    if tracker is not None:
        ctx.phases["warmup_s"] = time.perf_counter() - t0 - ctx.phases["compile_load_s"]
    entries_warm = cache_entries(cache_dir)
    compiles_in_setup = len(compiles)
    setup_s = time.perf_counter() - T_START

    # ---- the window --------------------------------------------------------
    work = tempfile.TemporaryDirectory(prefix="bench-")
    attempted = failed = 0
    last_state = None
    tracing = False
    excluded = 0.0  # the profiler's stop is no part of any unit
    w0 = time.perf_counter()
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(os.path.join(work.name, "trace"), profiler_options=opts)
        tracing = True
    while True:
        attempted += 1
        u0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench:unit"):
                last_state, probe = advance(s_warm, end_ns)
            ok = probe_totals(probe) == want_totals
        except CapacityError as e:
            say(f"unit {attempted}: {e}")
            ok = False
        now = time.perf_counter()
        if ok:
            ctx.unit_s.append(now - u0)
        else:
            failed += 1
        if tracing and attempted == TRACED_UNITS:
            jax.profiler.stop_trace()
            tracing = False
            excluded = time.perf_counter() - now
        if time.perf_counter() - w0 - excluded >= args.seconds:
            break
    ctx.window_s = time.perf_counter() - w0 - excluded
    if tracing:
        jax.profiler.stop_trace()
    compiles_in_window = len(compiles) - compiles_in_setup
    done = len(ctx.unit_s)

    # ---- after the window: memory first, then whatever else allocates ------
    device = device_block(devices)
    got = None
    if last_state is not None:
        hs = host_stats(last_state)
        got = {k: hs[k] for k in refworld.COUNTERS}
    del last_state
    if tracker is not None:
        ctx.spans = tracker.spans
    if args.trace:
        ctx.trace = trace_reduce.reduce_dir(os.path.join(work.name, "trace"))
        if chips == 1:
            import pieces

            ctx._pieces_fn = lambda: pieces.measure(s_warm, world, warm_probe)
    metrics = {}
    if args.trace:
        for m in wanted:
            value = readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.trace:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
    else:
        sim_rate = done * unit_ns / 1e9 / ctx.window_s if ctx.window_s else 0.0
        values = {"sim_s_per_wall_s": sim_rate,
                  "peak_hbm_gib": device["memory_peak_bytes"] / 2**30,
                  "setup_s": setup_s}
        for m in wanted:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    ctx._pieces_fn = None  # drops the closure's hold on the warm state
    del s_warm

    # ---- the comparison with the plain reference ---------------------------
    t0 = time.perf_counter()
    ref_world = refworld.World(ref_config, args.seed)
    binary = refworld.build_reference(work.name)
    want = refworld.run_reference(binary, ref_world, end_ns, work.name)
    if got is None:
        numbers = {"hosts_differing": ref_world.h}
    else:
        numbers = refworld.compare(got, want)
    numbers["units_off_the_reference_unit"] = failed
    numbers["compiles_in_window"] = compiles_in_window
    check = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
    correct = done > 0 and all(v == 0 for v in numbers.values())
    ref_s = time.perf_counter() - t0
    work.cleanup()

    rehearsal = bool(args.rehearse)
    if rehearsal:
        # a CPU run gives no time, rate or share: only counts keep a value
        counted = {m["name"] for m in wanted if m["source"] == "program_counter"}
        for name, m in metrics.items():
            if name not in counted:
                m["value"] = None
        device.pop("busy_s", None)
        device.pop("window_s", None)

    events = ctx.events_per_unit * done
    say(f"cell {cell['name']}: config {cell['config']}, {world.ecfg.num_hosts} hosts, "
        f"{chips} chip(s), engine {sched.engine}, seed {args.seed}, "
        f"warm {params['warm_sim_ms']} ms + unit {params['unit_sim_ms']} ms")
    say(f"window: {ctx.window_s:.3f} s, {attempted} units started, {done} completed, "
        f"{failed} failed; per unit {ctx.events_per_unit} events, {ctx.iters_per_unit} drain "
        f"iterations")
    if done and not rehearsal:
        say(f"events per second: {events / ctx.window_s:.1f}; unit wall median "
            f"{statistics.median(ctx.unit_s) * 1e3:.2f} ms over {done} units")
    say(f"set-up {setup_s:.2f} s; compile cache {cache_dir}: {entries_before} entries before, "
        f"{entries_warm} after warm-up, {cache_entries(cache_dir)} at the end; "
        f"{compiles_in_setup} compiles or cache loads in set-up, {compiles_in_window} inside the window")
    say(f"reference: {ref_world.h} hosts to {end_ns / 1e6:.0f} ms in {ref_s:.2f} s "
        f"(built, run and compared after the window)")
    for k, v in check.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    if args.trace and ctx.trace and not rehearsal:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"][:10],
                               "idle_gaps": ctx.trace["idle_gaps"][:10]}
    result["check"] = check
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: the cell's small size on whatever platform JAX has; yields no number")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 3


if __name__ == "__main__":
    sys.exit(main())
