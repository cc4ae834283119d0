"""The traced units' device time, booked to the program's own scopes.

The chunk program marks its layers with `jax.named_scope` (the one list:
`shadow_tpu/scopes.py`) and keeps the chunk executable of its newest
entry; `scopes.chunk_table()` parses that executable's text into
{instruction: (result shape, innermost scope, outermost scope)}. The
harness's reduced trace (`ctx.trace["device_ops"]`) lists every operation
of the three traced units as [`<instruction> <shape>`, self seconds]. This
file folds the second through the first, once per run, for the per-layer
readers beside it (`layer_metrics/drain.window_ms_per_unit.py` ...):

* an operation is the table's only where instruction name AND result
  shape agree, so a `copy.1` of another program (`jit_copy`,
  `jit_convert_element_type`, `_peek_next_time`) is booked to `other`;
* `while*`, `conditional*` and `call*` only wrap other operations: their
  self time is time inside a running program in which no leaf ran, and is
  no part of the device's busy seconds;
* everything else adds up to the busy seconds, and the sum is printed.

Against a program that has no scopes (the parent of the PR that brought
them), no table, no trace: every reader returns None.
"""

from __future__ import annotations

import re
import statistics

TRACED_UNITS = 3  # run.py's: the units of a --trace 1 window under the profiler
WRAPPERS = ("while", "conditional", "call")
OTHER = "other programs"
UNSCOPED = "no scope"
_RAW = re.compile(r"%?(\S+) = ")


def split(op: str) -> "tuple[str, str]":
    """("fusion.16", "s32[3932160,15]") from `fusion.16 s32[3932160,15]`;
    ("conditional.3", "") from an instruction text the harness could not
    shorten (`%conditional.3 = ((s32[...`)."""
    m = _RAW.match(op)
    if m:
        return m.group(1), ""
    name, _, shape = op.partition(" ")
    return name.lstrip("%"), shape


def is_wrapper(name: str) -> bool:
    return name.split(".")[0] in WRAPPERS


def fold(device_ops, table) -> dict:
    """{"by_scope": {innermost scope: seconds}, "wrappers": seconds}. The
    keys of by_scope are scope paths, UNSCOPED for the table's
    instructions under no scope, OTHER for operations the table does not
    know."""
    by_scope, wrappers = {}, 0.0
    for op, seconds in device_ops:
        name, shape = split(op)
        if is_wrapper(name):
            wrappers += seconds
            continue
        entry = table.get(name)
        if entry is None or entry[0] != shape:
            key = OTHER
        else:
            key = entry[1] or UNSCOPED
        by_scope[key] = by_scope.get(key, 0.0) + seconds
    return {"by_scope": by_scope, "wrappers": wrappers}


def under(folded: dict, prefix: str) -> float:
    """Seconds of every scope path that is `prefix` or lies below it."""
    return sum(s for k, s in folded["by_scope"].items()
               if k == prefix or k.startswith(prefix + "/"))


def chunk_table():
    try:
        from shadow_tpu import scopes
    except ImportError:  # a program from before the scopes
        return None
    return scopes.chunk_table()


def account(ctx) -> "dict | None":
    """The fold of this run's trace, made and printed once."""
    if hasattr(ctx, "_scope_account"):
        return ctx._scope_account
    ctx._scope_account = None
    if not ctx.trace or not ctx.trace.get("device_ops"):
        return None
    table = chunk_table()
    if not table:
        return None
    folded = fold(ctx.trace["device_ops"], table)
    busy = ctx.trace["busy_s"]
    total = sum(folded["by_scope"].values())
    parts = ", ".join(f"{k} {s:.6f}" for k, s in
                      sorted(folded["by_scope"].items(), key=lambda kv: -kv[1]))
    print(f"scope account over {TRACED_UNITS} traced units, seconds: {parts}; "
          f"sum {total:.6f} against busy_s {busy:.6f} "
          f"({100.0 * (total - busy) / busy if busy else 0.0:+.3f} %); control-flow "
          f"wrappers' self time {folded['wrappers']:.6f} of window_s "
          f"{ctx.trace['window_s']:.6f}", flush=True)
    if len(ctx.unit_s) > TRACED_UNITS:
        traced, plain = ctx.unit_s[:TRACED_UNITS], ctx.unit_s[TRACED_UNITS:]
        print(f"unit wall: traced median {statistics.median(traced) * 1e3:.3f} ms "
              f"({len(traced)} units), untraced median "
              f"{statistics.median(plain) * 1e3:.3f} ms ({len(plain)} units)", flush=True)
    ctx._scope_account = folded
    return folded


def ms_per_unit(ctx, prefix: str) -> "float | None":
    folded = account(ctx)
    seconds = under(folded, prefix) if folded else 0.0
    return seconds * 1e3 / TRACED_UNITS if seconds else None


def window_runs(ctx) -> list:
    """The `run` spans of the window's units: the last len(ctx.unit_s) of
    the process, each with the spans it contains."""
    runs = [s for s in ctx.spans if s[0] == "run"][-len(ctx.unit_s):] if ctx.unit_s else []
    return [(r, [s for s in ctx.spans if s is not r and s[1] >= r[1] and s[2] <= r[2]])
            for r in runs]
