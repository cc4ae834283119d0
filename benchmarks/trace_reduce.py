"""From a profiler trace (`*.xplane.pb`) to the numbers the benchmark reports.

Read with `jax.profiler.ProfileData` alone. What is taken from a trace:

* the traced window: from the start of the first `bench:unit` annotation
  the harness wrote to the end of the last one (the device's own extent
  where a trace has none);
* busy seconds: on each device plane (`/device:TPU:<n>`), the union of the
  intervals of the leaf operations of the `XLA Ops` line, clipped to the
  window, averaged over the device planes. A control-flow operation that
  only wraps others (`while`, `conditional`, a call) is no leaf: the time
  between the operations inside it is idle time of the device;
* the device operations that took most time: self time per operation name,
  summed over the window and averaged over the devices;
* idle gaps: each gap between busy intervals of the first device, named by
  the innermost `bench:*` annotation of the host that covers its middle
  (the program's driver spans `donate_copy`, `chunk_launch`, `probe_fetch`,
  `compile+launch` arrive there through the harness's tracker; `unit` is
  the harness's own), summed per name.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MARK = "bench:"


_HLO = re.compile(r"%?(\S+) = \(?(\w+\[[\d,]*\])")


def short_name(name: str) -> str:
    """`fusion.16 s32[3932160,15]` from the HLO instruction text the TPU's
    trace gives as an operation's name: the instruction and the shape of
    its (first) result."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """events: [(start, end, name)] of one line. Returns (leaves, self_ns):
    the leaf intervals, and per name the time not covered by children."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    self_ns, leaves, stack = {}, [], []  # stack of [start, end, name, child_ns, has_child]

    def close(top):
        s, e, name, child, has_child = top
        self_ns[name] = self_ns.get(name, 0) + max(0, (e - s) - child)
        if not has_child:
            leaves.append((s, e))
        if stack:
            stack[-1][3] += e - s
            stack[-1][4] = True

    for s, e, name in events:
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack and e > stack[-1][1]:
            e = stack[-1][1]  # a child never outlasts its parent
        stack.append([s, e, name, 0, False])
    while stack:
        close(stack.pop())
    return leaves, self_ns


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(profile) -> "dict | None":
    """`profile`: a jax.profiler.ProfileData. None where the trace has no
    device plane with operations (a CPU trace)."""
    marks, per_device = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, short_name(ev.name))
                           for ev in line.events]
                    if evs:
                        per_device.append((plane.name, evs))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        marks.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name[len(MARK):]))
    if not per_device:
        return None
    per_device.sort()
    units = [(s, e) for s, e, n in marks if n == "unit"]
    if units:
        lo, hi = min(s for s, _ in units), max(e for _, e in units)
    else:
        lo = min(ev[0] for _, evs in per_device for ev in evs)
        hi = max(ev[1] for _, evs in per_device for ev in evs)
    window_ns = hi - lo
    busy_ns, ops_ns, first_busy = [], {}, None
    for _name, evs in per_device:
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs if e > lo and s < hi]
        leaves, self_ns = _self_times(evs)
        busy = _union(_clip(leaves, lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for n, t in self_ns.items():
            ops_ns[n] = ops_ns.get(n, 0) + t
        if first_busy is None:
            first_busy = busy
    n_dev = len(per_device)
    gaps = {}
    edges = [lo] + [t for iv in first_busy for t in iv] + [hi]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        mid = (gs + ge) // 2
        cover = [(e - s, n) for s, e, n in marks if s <= mid < e]
        name = min(cover)[1] if cover else "outside every span"
        gaps[name] = gaps.get(name, 0) + (ge - gs)
    top = sorted(ops_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "devices": n_dev,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in top],
        "idle_gaps": [[n, t / 1e9] for n, t in sorted(gaps.items(), key=lambda kv: -kv[1])],
        "op_events": sum(len(evs) for _, evs in per_device),
    }


def reduce_file(path: str):
    import jax.profiler

    return reduce(jax.profiler.ProfileData.from_file(path))


def reduce_dir(trace_dir: str):
    """The one trace a `jax.profiler.start_trace(trace_dir)` session wrote."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce_file(found[-1]) if found else None
