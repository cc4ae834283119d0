"""Static memory pricing — layer 1 of the memory observatory
(docs/observability.md "Memory observatory").

The million-host frontier (ROADMAP item 2) is HBM-bound before it is
FLOP-bound: hosts are rows of a resident state tensor (PAPER.md §1), so
"does this world fit, and what do I shrink if not" must be answerable
BEFORE paying a compile. This module walks any plane's SimState pytree —
single, ensemble `[R, H, ...]`, mesh shard — and produces an EXACT
bytes/host table grouped by subsystem, names the dominant grid, and
projects max-hosts-that-fit for a given HBM budget. Exactness is free:
every number is the sum of leaf `nbytes` (typed PRNG keys priced as
their raw key words), and the walk accepts `jax.eval_shape` abstract
pytrees, so `shadow-tpu mem` prices a config without allocating or
compiling anything.

The other two layers share this module's best-effort readers:
`compiled_memory` extracts `compiled.memory_analysis()` at the AOT
seams (runtime/compile_cache.py, runtime/autotune.py), and
`device_memory` reads `device.memory_stats()` for live sampling
(runtime/flightrec.py) and the recovery headroom check
(runtime/recovery.py). Both return None instead of raising on backends
without support (CPU has memory_analysis but not memory_stats; TPU/GPU
have both).
"""

from __future__ import annotations

from shadow_tpu.engine.state import (
    buffer_nbytes,
    fmt_bytes,
    leaf_nbytes,
    tree_nbytes,
)

__all__ = [
    "price_state",
    "price_regrow",
    "max_hosts_for_budget",
    "render_report",
    "memory_section",
    "compiled_memory",
    "device_memory",
    "fmt_bytes",
    "leaf_nbytes",
    "tree_nbytes",
]

# top-level SimState field -> subsystem group in the table. The queue's
# dense [H, C] rows are what remains to price after PR 16 removed the
# exchange-side lane grids (ROADMAP item 2a).
_GROUP_BY_FIELD = {
    "queue": "queue",
    "outbox": "outbox",
    "net": "net",
    "model": "model",
    "tracker": "tracker",
    "rng_key": "rng",
    "rng_counter": "rng",
    "seq": "rng",
}
_GROUP_ORDER = ("queue", "outbox", "net", "model", "tracker", "rng", "counters")

# What a `shadow-tpu run` holds on the device, over the state by shapes, as
# a TPU v5e measured it on the one world that fills a chip (524,288 PHOLD
# hosts, state 2,491,940,904 B; PERF.md section 6, PR 32): two copies of
# the state at a driver entry (the caller's and the donated one;
# `memory_stats()` peaks at 5,086,441,984 B) plus what the chunk program
# reserves when it is loaded, 7.35 GiB there, which `memory_stats()` does
# not count and which held ballast showed to be real: 12.95 GB in all.
DEVICE_OVER_STATE = 5.2


def _leaf_name(path) -> str:
    """'queue.data' from a tree_flatten_with_path key path."""
    parts = []
    for k in path:
        name = getattr(k, "name", None)  # GetAttrKey
        if name is None:
            name = getattr(k, "key", None)  # DictKey
        if name is None:
            name = getattr(k, "idx", None)  # SequenceKey
        parts.append(str(k) if name is None else str(name))
    return ".".join(parts) or "<root>"


def price_state(st) -> dict:
    """Walk a SimState pytree (concrete, numpy host snapshot, or
    jax.eval_shape abstract) into the bytes/host report. The leading
    replica axis of ensemble/mesh states is detected from the scalar
    `now` leaf; `bytes_per_host` is total/(hosts) — the marginal cost of
    one more host row across all replicas, the number the max-hosts
    projection divides by."""
    import jax

    leaves_with_path = jax.tree_util.tree_flatten_with_path(st)[0]
    replicas = 1
    now = getattr(st, "now", None)
    if now is not None and len(getattr(now, "shape", ())) >= 1:
        replicas = int(now.shape[0])
    seq = getattr(st, "seq", None)
    num_hosts = int(seq.shape[-1]) if seq is not None else 0

    groups: dict = {}
    dominant = None
    total = 0
    for path, leaf in leaves_with_path:
        name = _leaf_name(path)
        top = name.split(".", 1)[0]
        group = _GROUP_BY_FIELD.get(top, "counters")
        b = leaf_nbytes(leaf)
        total += b
        g = groups.setdefault(group, {"bytes": 0, "grids": []})
        g["bytes"] += b
        g["grids"].append(
            {
                "name": name,
                "shape": [int(s) for s in leaf.shape],
                "dtype": str(leaf.dtype),
                "bytes": b,
            }
        )
        if dominant is None or b > dominant["bytes"]:
            dominant = {"group": group, **g["grids"][-1]}
    for g in groups.values():
        g["grids"].sort(key=lambda r: -r["bytes"])
        if num_hosts:
            g["bytes_per_host"] = round(g["bytes"] / num_hosts, 2)

    report = {
        "num_hosts": num_hosts,
        "replicas": replicas,
        "total_bytes": int(total),
        "bytes_per_host": round(total / num_hosts, 2) if num_hosts else 0.0,
        "groups": groups,
        "dominant": dominant,
    }
    return report


def price_regrow(st, queue_capacity=None, outbox_capacity=None) -> int:
    """Projected TOTAL bytes of `st` after grow_state/grow_ensemble_state
    to the given capacities — priced from the current shapes without
    allocating, so rollback-and-regrow can check headroom before the
    double. Exact: the capacity axis scales every [.., C(,lanes)] grid
    linearly and nothing else."""
    q, ob = st.queue, st.outbox
    total = tree_nbytes(st)
    if queue_capacity is not None:
        old = int(q.time.shape[-1])
        if queue_capacity != old:
            base = len(q.count.shape)
            total += buffer_nbytes(q, base, queue_capacity / old) - buffer_nbytes(
                q, base
            )
    if outbox_capacity is not None:
        old = int(ob.valid.shape[-1])
        if outbox_capacity != old:
            base = len(ob.fill.shape)
            total += buffer_nbytes(ob, base, outbox_capacity / old) - buffer_nbytes(
                ob, base
            )
    return int(total)


def max_hosts_for_budget(report: dict, budget_bytes: int) -> int:
    """How many hosts of THIS world (same config, same replica count)
    fit in `budget_bytes` of HBM: the per-host marginal bytes divide the
    budget after the host-independent scalars are set aside. Monotonic
    in the budget by construction."""
    per_host = report["bytes_per_host"]
    if per_host <= 0:
        return 0
    fixed = sum(
        g["bytes"]
        for r in report["groups"].values()
        for g in r["grids"]
        if not g["shape"]  # scalar leaves don't scale with hosts
    )
    return max(0, int((budget_bytes - fixed) // per_host))


def render_report(report: dict, hbm_gb: "float | None" = None) -> str:
    """The `shadow-tpu mem` table: per-subsystem bytes/host, the
    dominant grid, and the max-hosts projection."""
    h, r = report["num_hosts"], report["replicas"]
    head = f"{h} hosts" + (f" x {r} replicas" if r > 1 else "")
    lines = [
        f"memory: {head}, total {fmt_bytes(report['total_bytes'])} "
        f"({fmt_bytes(report['bytes_per_host'])}/host)",
        f"  {'subsystem':<10} {'bytes':>12} {'bytes/host':>12}  largest grid",
    ]
    for name in _GROUP_ORDER:
        g = report["groups"].get(name)
        if g is None:
            continue
        top = g["grids"][0]
        shape = "x".join(str(s) for s in top["shape"]) or "scalar"
        lines.append(
            f"  {name:<10} {fmt_bytes(g['bytes']):>12} "
            f"{fmt_bytes(g.get('bytes_per_host', 0)):>12}  "
            f"{top['name']} [{shape}] {top['dtype']}"
        )
    dom = report["dominant"]
    shape = "x".join(str(s) for s in dom["shape"]) or "scalar"
    lines.append(
        f"  dominant grid: {dom['name']} [{shape}] {dom['dtype']} = "
        f"{fmt_bytes(dom['bytes'])} "
        f"({100 * dom['bytes'] / max(report['total_bytes'], 1):.1f}% of state)"
    )
    if hbm_gb:
        budget = int(hbm_gb * 1024**3)
        fits = max_hosts_for_budget(report, budget)
        lines.append(
            f"  projection: {fits} hosts fit in {hbm_gb:g} GiB HBM "
            f"(state only; XLA temps/program come on top: a TPU v5e "
            f"held {DEVICE_OVER_STATE:g}x the state in a run, so about "
            f"{int(fits / DEVICE_OVER_STATE)} hosts at that ratio — see "
            f"compiled peak in sim-stats/autotune)"
        )
    return "\n".join(lines)


def memory_section(st, compiled: "dict | None" = None) -> dict:
    """The compact `memory` block for sim-stats.json: group totals +
    dominant grid + best-effort device/compiled numbers (the full grid
    list stays in `shadow-tpu mem`)."""
    report = price_state(st)
    out = {
        "num_hosts": report["num_hosts"],
        "replicas": report["replicas"],
        "total_bytes": report["total_bytes"],
        "bytes_per_host": report["bytes_per_host"],
        "groups": {
            name: g["bytes"] for name, g in report["groups"].items()
        },
        "dominant": report["dominant"],
    }
    dev = device_memory()
    if dev is not None:
        out["device"] = dev
    if compiled is not None:
        out["compiled"] = compiled
    return out


def compiled_memory(exe) -> "dict | None":
    """Best-effort `compiled.memory_analysis()` extraction — layer 2.
    Returns {argument,output,temp,alias,peak}_bytes or None when the
    backend (or this jax version) doesn't expose the analysis. Peak is
    XLA's own figure when present, else argument+output+temp-alias (the
    live set at execution, aliased/donated buffers counted once)."""
    try:
        fn = getattr(exe, "memory_analysis", None)
        if fn is None:
            return None
        ma = fn()
        if ma is None:
            return None
        out = {}
        for key, attr in (
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v is not None:
                out[key] = int(v)
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if peak is None and out:
            peak = (
                out.get("argument_bytes", 0)
                + out.get("output_bytes", 0)
                + out.get("temp_bytes", 0)
                - out.get("alias_bytes", 0)
            )
        if peak is None:
            return None
        out["peak_bytes"] = int(peak)
        return out
    except Exception:  # noqa: BLE001 — diagnostics, never a failure
        return None


def device_memory(devices=None) -> "dict | None":
    """Best-effort `device.memory_stats()` across the local devices —
    layer 3's source. bytes_in_use/bytes_limit sum across devices (total
    footprint vs total budget); peak_bytes_in_use is the per-device max
    (each HBM is a separate ceiling). None on backends without the
    stats (CPU), so every caller treats memory as optional."""
    try:
        import jax

        devs = devices if devices is not None else jax.local_devices()
        in_use = peak = limit = 0
        seen = False
        for d in devs:
            ms = d.memory_stats()
            if not ms:
                continue
            seen = True
            in_use += int(ms.get("bytes_in_use", 0))
            peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
            limit += int(ms.get("bytes_limit", 0) or 0)
        if not seen:
            return None
        out = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}
        if limit:
            out["bytes_limit"] = limit
        return out
    except Exception:  # noqa: BLE001 — diagnostics, never a failure
        return None
