"""The layer scopes inside the chunk program (shadow_tpu/scopes.py).

Contracts pinned here, on the CPU at 8 hosts:

  * every scope of the one table appears in the `op_name` metadata of the
    compiled chunk, for tgen and phold, plain and pump, and — on 4 virtual
    devices — the sharded chunk has its exchange collectives under
    `exchange/collective` and the window's all_gather under `window`;
  * `jax.named_scope` writes metadata and nothing else: the lowered chunk
    is the same text with the scopes patched out, so the program, its
    trajectories and its cost are unchanged;
  * the instruction -> scope table (`scopes.parse_hlo_text`) names a scope
    for at least nine in ten of the chunk's operations that carry an
    `op_name`, and a table without a single scope is refused loudly.

Each chunk is lowered twice and compiled once per module (the fixture).
"""

import contextlib
import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu import scopes
from shadow_tpu.engine import round as rnd
from shadow_tpu.engine.sharded import AXIS, ShardedRunner, shard_state
from shadow_tpu.engine.state import trace_static_cfg

END = jnp.asarray(30_000_000, jnp.int64)
ROUNDS = 2
CASES = ("tgen-plain", "tgen-pump", "phold-plain", "phold-pump", "tgen-sharded")
EVERYWHERE = {
    "window", "drain", "drain/handle", "drain/handle/push_self", "drain/handle/pop",
    "drain/handle/stage", "drain/handle/route", "exchange", "exchange/land",
    "exchange/land/count", "exchange/land/pull", "exchange/land/sort",
    "exchange/land/pack", "probe",
}
# the tgen world shapes its hosts and speaks TCP; phold's does neither
TGEN = EVERYWHERE | {"drain/handle/netstack", "drain/handle/tcp"}
EXPECTED = {
    "tgen-plain": TGEN,
    "tgen-pump": TGEN | {"drain/pump", "drain/pump/push_self", "drain/pump/route",
                         "drain/pump/pop"},
    # phold publishes no pump_spec: every engine value takes the handler
    "phold-plain": EVERYWHERE,
    "phold-pump": EVERYWHERE,
    "tgen-sharded": TGEN | {"exchange/collective", "exchange/bucket"},
}


def _lower(case):
    """The case's chunk, traced anew (a function object of its own, so no
    trace cache answers) and lowered."""
    kind, engine = case.split("-")
    if kind == "tgen":
        cfg, model, tables, st = _tgen_world(8, 0.02, 20_000_000, seed=3)
    else:
        cfg, model, tables, st = _phold_world(8)
    cfg = dataclasses.replace(cfg, tracker=True)
    if engine == "pump":
        cfg = dataclasses.replace(cfg, engine="pump", pump_k=3)
    if engine == "sharded":
        mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
        runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=ROUNDS)
        st = shard_state(st, mesh)
        return runner._chunk_fn(st).lower(st, tables, END)

    def chunk(*args):
        return rnd._run_chunk(*args)

    chunk.__name__ = rnd._run_chunk.__name__  # the module's name, as the driver jits it
    fn = jax.jit(chunk, static_argnums=(2, 3, 5), donate_argnums=(0,))
    return fn.lower(st, END, ROUNDS, model, tables, trace_static_cfg(cfg))


@pytest.fixture(scope="module")
def chunks():
    """{case: (lowered text, lowered text without scopes, optimized HLO)}"""
    out = {}
    for case in CASES:
        lowered = _lower(case)
        with mock.patch.object(jax, "named_scope", lambda name: contextlib.nullcontext()):
            bare = _lower(case).as_text()
        out[case] = (lowered.as_text(), bare, lowered.compile().as_text())
    return out


@pytest.mark.parametrize("case", CASES)
def test_every_scope_names_operations_of_the_compiled_chunk(chunks, case):
    table = scopes.parse_hlo_text(chunks[case][2])
    found = {v[1] for v in table.values() if v[1]}
    assert EXPECTED[case] <= found, EXPECTED[case] - found
    # the landing pulls by arrival lane in a loop of its own: no lane merge
    # under it, and the loop's body books under the loop's scope
    assert "exchange/land/push_self" not in found
    pulled = [n for n, v in table.items() if v[1] == "exchange/land/pull"]
    assert any(n.startswith("while") for n in pulled) and len(pulled) > 1, pulled
    # the bucketing stands in front of the collective, not around it or
    # under it, and only the sharded all_to_all flush has one
    assert not any("bucket" in p and "collective" in p for p in found), found
    assert ("exchange/bucket" in found) == (case == "tgen-sharded")
    # the one sort of the landing is the sort scope's, whatever else sorts
    sorts = {v[1] for n, v in table.items() if n.startswith("sort")}
    assert "exchange/land/sort" in sorts
    assert sorts <= {"exchange/land/sort", "exchange/bucket"}, sorts
    # nothing outside the one list, and outermost is the path's head
    for shape, inner, outer in table.values():
        if inner:
            assert all(p in scopes.SCOPES for p in inner.split("/"))
            assert outer == inner.split("/")[0]


@pytest.mark.parametrize("case", ["tgen-plain", "tgen-pump", "tgen-sharded"])
def test_the_staged_payload_keeps_its_slots_minor(chunks, case):
    """Outbox.data is [H, 8, O] all through the chunk: under the staging
    scopes no s32[H, O, 8] result is left (a minor axis of 8 pads to the
    chip's 128 lanes: 16 times the bytes, PERF.md PR 29), and the select
    chain's result is the payload on its new axes."""
    h = 2 if case == "tgen-sharded" else 8  # 8 hosts over 4 devices
    o, w = 32, 8  # test_pump._world's outbox_capacity; PAYLOAD_LANES
    stage = "drain/pump" if case == "tgen-pump" else "drain/handle/stage"

    class Chunk:
        def as_text(self):
            return chunks[case][2]

    staged = [
        shape for shape, inner, _ in scopes.chunk_table(Chunk()).values()
        if inner == stage
    ]
    assert f"s32[{h},{w},{o}]" in staged, staged
    assert f"s32[{h},{o},{w}]" not in staged
    # nor anywhere else in the program, inside a fusion or out; at 8 hosts
    # the flush's own [8, O, H] words (hosts minor: engine/round.py
    # _payload_words) spell the same digits, and are told apart by scope
    for line in chunks[case][2].splitlines():
        if f"s32[{h},{o},{w}]" in line:
            assert h == w and "/exchange/" in line and "/stage" not in line, line
    assert f"tensor<{h}x{w}x{o}xi32>" in chunks[case][0]
    if h != w:  # the lowered text names no scope to tell the flush's words by
        assert f"tensor<{h}x{o}x{w}xi32>" not in chunks[case][0]


def _scopes_of(text, opcode):
    """The scope paths of the instructions of one opcode."""
    return {
        scopes.scope_path(op.group(1))
        for line in text.splitlines()
        if f" {opcode}(" in line and (op := scopes._OP_NAME.search(line))
    }


def test_sharded_collectives_lie_under_their_scopes(chunks):
    text = chunks["tgen-sharded"][2]
    assert _scopes_of(text, "all-to-all") == {"exchange/collective"}
    # the window's pmin (an all_gather reduced locally), and the probe's
    gathers = _scopes_of(text, "all-gather")
    assert "window" in gathers and gathers <= {"window", "probe"}
    # the staged-traffic test's psum: the window's, and the flush's own
    assert _scopes_of(text, "all-reduce") <= {"window", "exchange", "probe"}


@pytest.mark.parametrize("case", CASES)
def test_scopes_are_metadata_only(chunks, case):
    scoped, bare, _ = chunks[case]
    assert len(scoped.splitlines()) > 100
    assert scoped == bare
    # the module's name carries the scope table's digest: the compile
    # cache's key holds the name, and leaves the scopes themselves out
    assert scopes.KEY in scoped.splitlines()[0]


@pytest.mark.parametrize("case", CASES)
def test_table_covers_the_chunk(chunks, case):
    """Of the operations the program traced (those that kept an
    `op_name`), at least 90 % lie under a scope; with the compiler's own
    (copies, rewritten reductions — XLA:CPU drops their metadata) counted
    as misses, still 80 %."""
    table = scopes.parse_hlo_text(chunks[case][2])
    paths = [v[1] for v in table.values()]
    scoped = sum(1 for p in paths if p)
    traced = sum(1 for p in paths if p is not None)
    assert len(paths) > 50
    assert scoped >= 0.9 * traced, (scoped, traced)
    assert scoped >= 0.8 * len(paths), (scoped, len(paths))


def test_a_table_without_a_scope_is_refused_loudly(chunks, monkeypatch):
    """An executable whose text names no scope (what a compile-cache entry
    of an unscoped build gives): one loud line, and None — never a guess."""
    said = []
    monkeypatch.setattr(scopes, "_loud", said.append)
    bare = re.sub(r'op_name="[^"]*"', 'op_name="jit(_run_chunk)/while/body/add"',
                  chunks["phold-plain"][2])

    class Stale:
        def as_text(self):
            return bare

    assert scopes.chunk_table(Stale()) is None
    assert len(said) == 1 and "NONE" in said[0]
    monkeypatch.setattr(scopes, "last_chunk", None)
    assert scopes.chunk_table() is None and len(said) == 2

    class Fresh:
        def as_text(self):
            return chunks["phold-plain"][2]

    fresh = Fresh()
    table = scopes.chunk_table(fresh)
    assert table and scopes.chunk_table(fresh) is table  # memoised
    assert len(said) == 2


@pytest.mark.parametrize("name,layer,op,path", [
    (scopes.ROUTE, "drain",
     "jit(_run_chunk)/while/body/drain/while/body/handle/route/gather", "drain/handle/route"),
    # equeue.run_bounds, under the landing (PR 32)
    (scopes.COUNT, "kernels",
     "jit(_run_chunk)/while/body/exchange/land/count/dot_general", "exchange/land/count"),
    # equeue.land_sorted's while loop, under the landing (PR 33)
    (scopes.PULL, "kernels",
     "jit(_run_chunk)/while/body/exchange/land/pull/while/body/gather", "exchange/land/pull"),
    # equeue.peek_min + clear_slot, as the handler calls them (PR 35)
    (scopes.POP, "kernels",
     "jit(_run_chunk)/while/body/drain/while/body/handle/pop/gather", "drain/handle/pop"),
    # equeue.land_sorted's sort and packing, and the sharded flush's
    # bucketing in front of the collective (PR 36)
    (scopes.SORT, "kernels",
     "jit(_run_chunk)/while/body/exchange/cond/branch_1_fun/land/sort/sort", "exchange/land/sort"),
    (scopes.PACK, "kernels",
     "jit(_run_chunk)/while/body/exchange/cond/branch_1_fun/land/pack/concatenate",
     "exchange/land/pack"),
    (scopes.BUCKET, "exchange",
     "jit(_chunk)/while/body/exchange/cond/branch_1_fun/bucket/jit(argsort)/sort",
     "exchange/bucket"),
])
def test_a_later_scope_is_in_the_list_and_in_the_digest(name, layer, op, path):
    """The scope names its layer, and the chunk functions' names moved
    with it: a compile cache filled before it cannot answer."""
    import hashlib

    assert scopes.SCOPES[name] == layer

    def digest(names):
        return "s" + hashlib.sha1("/".join(names).encode()).hexdigest()[:6]

    assert scopes.KEY == digest(scopes.SCOPES)
    assert scopes.KEY != digest(n for n in scopes.SCOPES if n != name)
    assert scopes.scope_path(op) == path


def test_scope_path_keeps_only_the_lists_names():
    op = "jit(_run_chunk)/while/body/closed_call/cond/branch_1_fun/drain/while/body/handle/push_self/select_n"
    assert scopes.scope_path(op) == "drain/handle/push_self"
    assert scopes.scope_path("jit(_run_chunk)/while/body/add") == ""
    # a primitive whose name only holds a scope's word is no scope
    assert scopes.scope_path("jit(_run_chunk)/while/body/drain/population_count") == "drain"
    # the last component is the primitive's name: a sort outside the
    # landing's sort scope is not under it
    assert scopes.scope_path("jit(_run_chunk)/while/body/exchange/land/jit(argsort)/sort") == (
        "exchange/land")
    assert scopes.scope_path("sort") == ""
    # no scope is named like something JAX writes into the middle of an op_name
    assert not set(scopes.SCOPES) & {"cond", "body", "while", "closed_call", "jit"}


_FUSED_SCATTER = """\
HloModule jit_chunk

%region_add (a: u32[], b: u32[]) -> u32[] {
  %a = u32[] parameter(0)
  ROOT %b = u32[] parameter(1)
}

%fused_scatter (p0: u32[64], p1: s32[16], p2: u32[16]) -> u32[64] {
  %p0 = u32[64]{0} parameter(0)
  %p1 = s32[16]{0} parameter(1)
  %p2 = u32[16]{0} parameter(2)
  %reshape.1 = u32[16]{0} reshape(%p2), metadata={op_name="jit(chunk)/exchange/cond/branch_1_fun/bucket/gather"}
  ROOT %scatter.1 = u32[64]{0} scatter(%p0, %p1, %reshape.1), to_apply=%region_add
}

%fused_mixed (q0: s32[16]) -> s32[16] {
  %q0 = s32[16]{0} parameter(0)
  %add.1 = s32[16]{0} add(%q0, %q0), metadata={op_name="jit(chunk)/exchange/cond/branch_1_fun/land/sort/add"}
  ROOT %mul.1 = s32[16]{0} multiply(%add.1, %q0), metadata={op_name="jit(chunk)/exchange/cond/branch_1_fun/land/pack/mul"}
}

%fused_bare (r0: s32[16]) -> s32[16] {
  %r0 = s32[16]{0} parameter(0)
  ROOT %neg.1 = s32[16]{0} negate(%r0)
}

%branch_flush (s: (u32[64], s32[16], u32[16])) -> u32[64] {
  %s = (u32[64]{0}, s32[16]{0}, u32[16]{0}) parameter(0)
  %g0 = u32[64]{0} get-tuple-element(%s), index=0
  %g1 = s32[16]{0} get-tuple-element(%s), index=1
  %g2 = u32[16]{0} get-tuple-element(%s), index=2
  %fusion.5 = s32[16]{0} fusion(%g1), kind=kLoop, calls=%fused_mixed
  %fusion.6 = s32[16]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_bare
  %fusion.7 = s32[16]{0} fusion(%fusion.6), kind=kLoop, calls=%fused_bare, metadata={op_name="jit(chunk)/exchange/cond/branch_1_fun/land/neg"}
  ROOT %fusion.4 = u32[64]{0} fusion(%g0, %fusion.7, %g2), kind=kCustom, calls=%fused_scatter
}

%branch_skip (t: (u32[64], s32[16], u32[16])) -> u32[64] {
  %t = (u32[64]{0}, s32[16]{0}, u32[16]{0}) parameter(0)
  ROOT %h0 = u32[64]{0} get-tuple-element(%t), index=0
}

ENTRY %main (x: (u32[64], s32[16], u32[16]), c: pred[]) -> u32[64] {
  %x = (u32[64]{0}, s32[16]{0}, u32[16]{0}) parameter(0)
  %c = pred[] parameter(1)
  ROOT %conditional.1 = u32[64]{0} conditional(%c, %x, %x), true_computation=%branch_flush, false_computation=%branch_skip, metadata={op_name="jit(chunk)/exchange/cond"}
}
"""


def test_a_fusion_whose_root_lost_its_name_takes_what_its_instructions_agree_on():
    """The chip's compiler merges the two 32-bit halves of a 64-bit scatter
    into one scatter that carries no `op_name`, and the fusion around it
    then has none: it belongs to the deepest scope the instructions inside
    it share, not to the branch that holds it (in the four-chip chunk these
    are the bucketing's three largest operations). Its own name still goes
    first, and a fusion that names nothing inside inherits as before."""
    table = scopes.parse_hlo_text(_FUSED_SCATTER)
    assert table["fusion.4"] == ("u32[64]", "exchange/bucket", "exchange")
    assert table["fusion.5"][1] == "exchange/land"  # sort and pack agree on the landing
    assert table["fusion.6"][1] == "exchange"  # nothing named inside: the branch's scope
    assert table["fusion.7"][1] == "exchange/land"  # its own name, whatever lies inside
    assert table["conditional.1"][1] == "exchange"
    assert "scatter.1" not in table  # inside a fusion: no operation of its own
