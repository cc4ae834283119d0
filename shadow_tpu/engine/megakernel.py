"""Fused Pallas round megakernel for the pop→handle→push cycle.

The structural cost of the device engines is dispatch/traffic, not FLOPs:
one pump iteration at bench scale is ~hundreds of XLA fusions, and every
microstep round-trips the [H, Q] event queue, the [H, S] flow table and
the outbox through HBM (round-5 verdict Next #3 — round-over-round HLO
fusion yielded ~2x/round against a 135x gap). This module owns that
structure outright: ONE Pallas kernel launch per round iteration runs all
`pump_k` pop→classify→commit→emit microsteps over VMEM-resident tiles of
the host-state rows. Per launch, every state array is read from HBM once
and written once; the k intermediate queue/flow-table/outbox states live
only in VMEM/registers.

Shared semantics, not a fifth copy: the kernel body executes the *same*
`pump_microstep` function as the XLA pump engine (engine/pump.py) — the
carry refactor means the megakernel's bit-identity to the pump (and
transitively to the full handler and the scalar/native oracles) is
structural. Classification, RNG draws (threefry, counter-based), the
event total-order key, and all TCP/shaping integer arithmetic are the
byte-for-byte identical program, just scheduled differently.

Execution tiers:

  * CPU: `interpret=True` — the kernel is discharged to ordinary XLA
    ops, jittable, bit-identical; this is the always-on conformance path
    (tests/test_megakernel.py) and the only tier that has ever run.
  * TPU: compiled via Mosaic over host tiles — today REFUSED by the
    chip's compiler before the body is looked at (int64 carry leaves;
    docs/megakernel.md "Engine selection", tests/test_chip_compile.py),
    so `engine: megakernel` on a TPU fails with the compiler's error and
    `auto` never selects it. Any other backend name is an error: a
    Pallas kernel is never interpreted on an accelerator. Tiling is row-local by
    construction (every microstep op is elementwise over [H]/[H,S]/[H,K]
    rows or a per-row reduction), so any tile split of the host axis is
    bit-identical; cross-tile scalars (min_used, the rejected flag) are
    reduced per tile in the kernel and folded outside.

Event kinds handled in-kernel are exactly the pump classes (P1 ingress
defer/drop, P2 receiver data completion, P3 sender cumulative ACK +
send-engine flush); everything else (handshakes, FIN/RST, recovery,
timer fires, model triggers) is deferred to the full XLA handler in the
same round iteration, and the round-boundary exchange stays OUTSIDE the
kernel on the flush_outbox path — the dense grid landing
(equeue.push_many_sorted / shard all_to_all) or the sort-based segment
exchange (equeue.push_many_segment / ppermute ring) per cfg.exchange;
the kernel's per-host outbox staging is identical either way, which is
what keeps the carry host-tileable (no global pool leaf ever enters it).
See docs/megakernel.md for the VMEM tile layout and measured costs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shadow_tpu.engine.pump import (
    PumpCarry,
    pump_carry_finish,
    pump_carry_init,
    pump_microstep,
)
from shadow_tpu.engine.state import EngineConfig, SimState
from shadow_tpu.graph.routing import RoutingTables

# Per-tile VMEM budget for auto tile selection: the carry tile plus the
# replicated routing tables must fit well under the ~16 MB/core VMEM with
# headroom for Mosaic temporaries. (Interpret mode ignores this — the
# "tiles" are ordinary XLA slices — but auto picks the same shape so the
# two tiers exercise identical programs.)
_VMEM_TILE_BUDGET_BYTES = 6 * 1024 * 1024


def _carry_row_bytes(c: PumpCarry) -> int:
    """Bytes per host row across every host-axis leaf of the carry."""
    h = c.seq.shape[0]
    total = 0
    for leaf in jax.tree.leaves(c):
        if leaf.ndim >= 1 and leaf.shape[0] == h:
            per_row = leaf.dtype.itemsize
            for d in leaf.shape[1:]:
                per_row *= d
            total += per_row
    return total


def resolve_tile(cfg: EngineConfig, c: PumpCarry) -> int:
    """Host rows per Pallas program. cfg.megakernel_tile wins when set;
    auto = the largest power-of-two divisor of H whose carry tile fits
    the VMEM budget (whole-H when nothing smaller is needed or possible)."""
    h = c.seq.shape[0]
    if cfg.megakernel_tile:
        return cfg.megakernel_tile
    row = _carry_row_bytes(c)
    if h * row <= _VMEM_TILE_BUDGET_BYTES:
        return h
    # largest power of two dividing h (any smaller power of two divides too)
    g = h & -h
    th = g
    while th > 8 and th * row > _VMEM_TILE_BUDGET_BYTES:
        th //= 2
    return max(th, 1)


def _launch(
    c: PumpCarry,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
    interpret: bool,
) -> PumpCarry:
    """One pallas_call running cfg.pump_k microsteps over host tiles."""
    h = c.seq.shape[0]
    th = resolve_tile(cfg, c)
    grid = h // th

    # Loud guard on the tiling invariants the leaf classification below
    # assumes (a future pump-capable model could otherwise silently break
    # bit-identity at grid > 1): the ONLY scalar carry leaf may be
    # min_used (its per-tile partials are jnp.minimum-folded — any other
    # scalar would be min-merged wrongly), and the only legitimate
    # non-host-axis leaves are the known replicated context arrays.
    # (The tracker plane's carry lanes — trk_bytes_ctrl/trk_bytes_data/
    # trk_retrans, engine/pump.py — are ordinary [H] leaves and tile like
    # every other counter; its round counters are SimState scalars that
    # never enter the carry.)
    for path, leaf in jax.tree_util.tree_leaves_with_path(c):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 0 and "min_used" not in name:
            raise ValueError(
                f"megakernel carry has scalar leaf {name}: only min_used "
                "may be scalar (per-tile partials fold via min); give the "
                "leaf a leading host axis or extend the merge logic"
            )
        if leaf.ndim >= 1 and leaf.shape[0] != h and "codel_table" not in name:
            raise ValueError(
                f"megakernel carry leaf {name} (shape {leaf.shape}) does "
                "not lead with the host axis and is not a known "
                "replicated table — tiling would replicate it stale"
            )

    leaves, treedef = jax.tree.flatten(c)
    # Three leaf classes: host-axis leaves are tiled over the grid; scalar
    # leaves (min_used) ride as (1,) arrays whose per-tile partials come
    # back as (grid,) and are min-reduced outside (min is the only scalar
    # combine the carry needs — min_used only ever folds via jnp.minimum);
    # anything else (the CoDel table) is replicated read-through context.
    scalar = [leaf.ndim == 0 for leaf in leaves]
    tiled = [
        leaf.ndim >= 1 and leaf.shape[0] == h for leaf in leaves
    ]
    leaves_in = [
        leaf.reshape((1,)) if s else leaf for leaf, s in zip(leaves, scalar)
    ]

    def _tiled_spec(leaf):
        nd = leaf.ndim
        return pl.BlockSpec(
            (th,) + leaf.shape[1:],
            functools.partial(lambda n, i: (i,) + (0,) * (n - 1), nd),
        )

    def _replicated_spec(leaf):
        nd = leaf.ndim
        return pl.BlockSpec(
            leaf.shape, functools.partial(lambda n, i: (0,) * n, nd)
        )

    def _pertile_spec(leaf):  # (1,) per program -> (grid,) output
        return pl.BlockSpec((1,), lambda i: (i,))

    in_specs = [
        _tiled_spec(leaf_in) if t else _replicated_spec(leaf_in)
        for leaf_in, t in zip(leaves_in, tiled)
    ]
    out_specs = [
        _pertile_spec(leaf_in)
        if s
        else (_tiled_spec(leaf_in) if t else _replicated_spec(leaf_in))
        for leaf_in, s, t in zip(leaves_in, scalar, tiled)
    ]
    out_shape = [
        jax.ShapeDtypeStruct((grid,), leaf.dtype)
        if s
        else jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        for leaf, s in zip(leaves_in, scalar)
    ]

    we = jnp.asarray(window_end, jnp.int64).reshape((1,))
    extra_in = [we, tables.host_node, tables.lat_ns, tables.rel]
    in_specs += [_replicated_spec(x) for x in extra_in]
    n_carry = len(leaves_in)

    def kernel(*refs):
        in_refs, out_refs = refs[: n_carry + 4], refs[n_carry + 4 :]
        vals = []
        for r, s in zip(in_refs[:n_carry], scalar):
            v = r[...]
            vals.append(v[0] if s else v)
        ct = treedef.unflatten(vals)
        we_k = in_refs[n_carry][0]
        tbl = RoutingTables(
            host_node=in_refs[n_carry + 1][...],
            lat_ns=in_refs[n_carry + 2][...],
            rel=in_refs[n_carry + 3][...],
        )
        for _ in range(cfg.pump_k):
            ct = pump_microstep(ct, we_k, model, tbl, cfg)
        for r, v, s in zip(out_refs, jax.tree.leaves(ct), scalar):
            r[...] = v.reshape((1,)) if s else v

    out_leaves = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*leaves_in, *extra_in)

    merged = [
        jnp.min(leaf_out) if s else leaf_out
        for leaf_out, s in zip(out_leaves, scalar)
    ]
    return treedef.unflatten(merged)


def megakernel_stage(
    st: SimState,
    window_end: jax.Array,
    model,
    tables: RoutingTables,
    cfg: EngineConfig,
) -> tuple[SimState, jax.Array]:
    """Drop-in replacement for pump_stage: identical signature, identical
    results (bit-for-bit), one fused kernel launch instead of pump_k
    separately-scheduled XLA microstep programs. Carry build (one routing
    gather) and merge-back (FIFO flush push, outbox rebuild) stay plain
    XLA — they run once per launch, not per microstep."""
    if cfg.pump_k <= 0:
        raise ValueError("megakernel_stage requires pump_k > 0")
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        # never interpreted on an accelerator, whatever it calls itself
        raise ValueError(
            f"the megakernel runs interpreted on 'cpu' and compiled on "
            f"'tpu'; backend {backend!r} is neither"
        )
    interpret = backend == "cpu"
    c = pump_carry_init(st, model, tables, cfg)
    c = _launch(c, window_end, model, tables, cfg, interpret)
    return pump_carry_finish(st, c, model, cfg)


def resolve_stage_cfg(cfg: EngineConfig) -> EngineConfig:
    """The megakernel's effective config: pump_k defaults to 8 microsteps
    per launch when the caller left it unset."""
    if cfg.pump_k > 0:
        return cfg
    return dataclasses.replace(cfg, pump_k=8)
