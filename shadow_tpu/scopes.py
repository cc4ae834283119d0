"""The layers inside the chunk program, by name.

The chunk program (`engine/round.py` `_run_chunk` and its sharded,
ensemble and mesh twins) is one XLA executable; a device trace names its
operations `fusion.16`, `select_select_fusion.1423`. The engine therefore
marks its layer boundaries with `jax.named_scope`, which writes each
operation's `op_name` metadata and nothing else: the lowered program is
the same with the scopes taken out (tests/test_scopes.py). JAX's
persistent-cache key leaves metadata out, so the chunk functions carry a
digest of the scope names in their own name (`keyed`), which the key holds.

This module is the one list of those scope names, and the way back from
a trace to them: the driver keeps the chunk executable of its newest
entry here (`last_chunk`, one assignment per entry), and `chunk_table()`
parses that executable's text into instruction -> scope when somebody asks
(the benchmark's per-layer readers, an operator reading a `--xprof-dir`
capture). Nothing is parsed in a run that does not ask. Beside it the
driver keeps the probes of that entry's two ends (`last_probes`), for
readers of what a replayed unit did.

Every name is a single path component; nested scopes give paths:

    window                  next window: queue min (+ its all_gather when
                            sharded), staged-traffic test, window end,
                            the live/idle bookkeeping of a round
    drain                   run_round's while loop: cond, lanes_live,
                            compaction gather / scatter
    drain/handle            handle_one_iteration
    drain/pump              pump_stage
    drain/handle/netstack   netstack.py as the handler calls it: the down
                            relay's token bucket and CoDel at ingress,
                            the up relay's token buckets at emit time
    drain/handle/tcp        transport/tcp.py: tcp_handle on the fused slot
                            view and commit_slot's one scatter
    drain/handle/route      graph/routing.py route_lookup / node_of: the
    drain/pump/route        packet's routing lookup, which is the source's
                            and the destination's node (compares against
                            the host groups' bounds, or a gather where
                            hosts are listed singly) and ONE gather of the
                            pair's packed latency and reliability words
    drain/handle/stage      the handler's staging of surviving packets
                            into the host's own outbox row: one select
                            chain over [H, outbox] per array, the
                            payload's over [H, 8, outbox] (slots minor)
    drain/handle/push_self  equeue.push_self_lanes: the [H, queue] lane
    drain/pump/push_self    merges of the handler and the pump
    drain/handle/pop        equeue.peek_min + clear_slot, the pop: one
    drain/pump/pop          reduction over the row's keys gives the slot,
                            its tie, its kind and its aux, ONE gather of H
                            indices reads the slot's eight payload words,
                            one select pass tombstones the two key arrays
    exchange                flush_outbox: the busiest row's fill, each
                            block's flatten, the landing's buffers, the
                            clear
    exchange/bucket         sharded all_to_all only: the entries' shard,
                            the stable argsort by it, each entry's rank in
                            its peer's bucket, and per array the
                            [peers, capacity] buffer and the scatter into it
    exchange/collective     all_to_all / all_gather (sharded only)
    exchange/land           equeue.land_sorted's own: each row's free-slot
                            ranks (a cumsum over [H, Q]) and how many
                            arrivals it lands (no push_self under land)
    exchange/land/sort      the stable sort of (destination, position)
                            over one block of slot columns, rows x O/8
                            entries, once a block a flush takes, and its
                            key
    exchange/land/pack      the payload packed where it lies as [14, M]
                            32-bit words
    exchange/land/count     equeue.run_bounds: each destination's arrival
                            count and the start of its run in sorted order
    exchange/land/pull      the landing's while loop: a pass pulls
                            LAND_LANES arrival lanes of every destination
                            through the sort's permutation and selects
                            them into the rows' free slots; as many
                            passes as the busiest destination needs
    probe                   state_probe, the exchange's four counts of a
                            round (staged entries, fan-in, landing
                            passes, flattened columns: always on) and the
                            tracker plane's per-host high-water marks
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import re

WINDOW = "window"
DRAIN = "drain"
HANDLE = "handle"
PUMP = "pump"
NETSTACK = "netstack"
TCP = "tcp"
ROUTE = "route"
STAGE = "stage"
PUSH_SELF = "push_self"
POP = "pop"
EXCHANGE = "exchange"
COLLECTIVE = "collective"
LAND = "land"
COUNT = "count"
PULL = "pull"
SORT = "sort"
PACK = "pack"
BUCKET = "bucket"
PROBE = "probe"

# scope name -> layer of PERF.md / BENCHMARK.json that owns its time
SCOPES = {
    WINDOW: "drain",
    DRAIN: "drain",
    HANDLE: "drain",
    PUMP: "drain",
    NETSTACK: "drain",
    TCP: "drain",
    ROUTE: "drain",
    STAGE: "drain",
    PUSH_SELF: "kernels",
    POP: "kernels",
    EXCHANGE: "exchange",
    COLLECTIVE: "exchange",
    LAND: "kernels",
    COUNT: "kernels",
    PULL: "kernels",
    SORT: "kernels",
    PACK: "kernels",
    BUCKET: "exchange",
    PROBE: "driver",
}

# JAX's persistent-cache key leaves metadata, and so the scopes, out, but
# holds the XLA module's name, which is the jitted function's. Every chunk
# function carries this digest of the scope names in its name (`keyed`), so
# that a cache filled by a build with other scopes, or none, is not hit: its
# executable would be loaded with its old names, and a trace of it could not
# be read by layer. (A `with` moved without a rename keeps the digest: clear
# the cache by hand then.)
KEY = "s" + hashlib.sha1("/".join(SCOPES).encode()).hexdigest()[:6]


def keyed(fn):
    """`fn`, renamed `<name>_<KEY>` for the compile cache's sake."""
    fn.__name__ = f"{fn.__name__}_{KEY}"
    return fn


def scoped(name: str):
    """Decorator: the function's operations lie under scope `name`. The
    scope is entered at each call, through `jax.named_scope` as it is then."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# The chunk executable of the newest driver entry (engine/round.py
# _launch_chunk0 assigns it); None until a driver has compiled one, and
# where a driver was handed an executable compiled elsewhere.
last_chunk = None

@dataclasses.dataclass
class EntryProbes:
    """What a driver entry saw at its two ends: the ChunkProbe of the
    state it started from and of its newest chunk (None until one is
    fetched), the rows of that state and its outbox slots (rows x outbox
    capacity, over all shards: what the flushes of a round flatten, so
    staged entries over it is a fill share). A caller's warm state does
    not start its counters at zero, so what the entry did is the
    difference."""

    hosts: int
    outbox_slots: int
    entry: object
    chunk: object = None


# The newest one-chip or sharded driver entry's probes (engine/round.py:
# entry_probe assigns it at entry, _drive sets `chunk` at every probe
# fetch). None until such a driver has run; nobody reads it in a run that
# does not ask (the benchmark's readers of rounds and occupancy per unit).
last_probes = None

_memo = (None, None)  # (executable, its table)

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"(?:condition|body|true_computation|false_computation|to_apply|calls)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTROL = frozenset(("while", "conditional", "call"))
# instructions that move no data and take no time of their own
_TRIVIAL = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "iota",
    "after-all", "partition-id", "replica-id",
))


def scope_path(op_name: str) -> str:
    """`drain/handle/push_self` from
    `jit(_run_chunk)/while/body/drain/while/body/handle/push_self/select_n`:
    the components of an `op_name` that are scope names, in order (of an
    `a;b` that XLA wrote for two instructions it merged, the first's). The
    last component is the primitive's own name and no scope: a `sort`
    under `exchange/bucket` is not under a scope `sort`."""
    return "/".join(p for p in op_name.split(";")[0].split("/")[:-1] if p in SCOPES)


def parse_hlo_text(text: str) -> dict:
    """{instruction name: (result shape, innermost scope, outermost scope)}
    of every instruction a device runs as an operation of its own: those
    of the entry computation and of while bodies, conditions and branches,
    not those inside a fusion or a reducer. The result shape is the
    `s32[3932160,15]` of the instruction text (of a tuple, its first
    element's), as a device trace prints it. The innermost scope is the
    whole path (`exchange/land/push_self`), the outermost its first
    component (`exchange`). A fusion carries the `op_name` of its root;
    one whose root lost it (the chip's compiler merges the two 32-bit
    halves of a 64-bit scatter into one scatter that carries none) takes
    the deepest scope that the instructions inside it agree on. An
    instruction whose `op_name` names no scope, or that has none (the
    compiler's own: a copy, a rewritten reduction, a cumulative sum's
    helper), belongs where it runs: to the scope of the while or
    conditional whose body holds it. Where no scope encloses that body
    either, both scopes are "" for an instruction with an `op_name` and
    None for one without."""
    comp, rows = None, []  # rows: (computation, name, shape, opcode, op_name, callees)
    called_by, fused = {}, set()  # control-flow bodies; fusions, reducers
    named = {}  # computation -> the scope paths its instructions name
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else comp
            continue
        m = _INSTRUCTION.match(line)
        opcode = _OPCODE.search(line, m.end()) if m else None
        if not opcode:
            continue
        op = _OP_NAME.search(line)
        callees = _CALLEE.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        row = (comp, m.group(1), m.group(2) or "", opcode.group(1),
               op.group(1) if op else None, callees)
        if op and scope_path(op.group(1)):
            named.setdefault(comp, []).append(scope_path(op.group(1)).split("/"))
        if row[3] in _CONTROL:
            called_by.update((c, row) for c in callees)
        else:
            fused.update(callees)
        rows.append(row)

    def agreed(row):
        """The deepest scope path the instructions inside a fusion share."""
        inside = [p for c in row[5] for p in named.get(c, ())]
        shared = []
        for parts in zip(*inside):
            if len(set(parts)) > 1:
                break
            shared.append(parts[0])
        return "/".join(shared)

    def scope_of(row):
        """The row's own scope path, or the one its body inherits."""
        own = scope_path(row[4]) if row[4] else ""
        if not own and row[3] == "fusion":
            own = agreed(row)
        caller = called_by.get(row[0])
        inherited = scope_of(caller) if caller and not own else None
        return own or inherited or (None if row[4] is None else "")

    table = {}
    for row in rows:
        if row[0] in fused or row[3] in _TRIVIAL:
            continue
        path = scope_of(row)
        table[row[1]] = (row[2], path, path and path.split("/")[0])
    return table


def _loud(msg: str) -> None:
    from shadow_tpu.utils.shadow_log import slog

    slog("warning", 0, "scopes", msg)


def chunk_table(executable=None) -> "dict | None":
    """The instruction -> scope table of `executable` (default: the chunk
    the driver compiled last). None, after one loud line, where there is
    no executable or its text carries not a single scope (an executable
    from a persistent cache that an unscoped build filled): a reader then
    reports nothing rather than guess. Memoised per executable."""
    global _memo
    exe = last_chunk if executable is None else executable
    if exe is None:
        _loud("no chunk executable was kept in this process")
        return None
    if _memo[0] is not exe:
        table = parse_hlo_text(exe.as_text())
        if not any(v[1] for v in table.values()):
            _loud(
                f"NONE of the chunk executable's {len(table)} instructions "
                "names a scope (a compile-cache entry of an unscoped build? "
                "clear the cache and compile again); no per-scope number "
                "is given"
            )
            table = None
        _memo = (exe, table)
    return _memo[1]
