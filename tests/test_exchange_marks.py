"""The exchange's four counts are kept in every program (engine/round.py
run_round / flush_outbox): `exch_hwm` (most entries a shard staged in one
round), `land_hwm` (most arrivals one destination landed in one round),
`land_passes` (the landing loop's passes) and `flush_cols` (the outbox
columns the flushes flattened) are accumulated whatever `cfg.tracker`
says, as `rounds_live` is, and feed nothing back.

Pinned here at 16 hosts, on one device and block-sharded over four virtual
ones, for tgen (TCP, netstack) and phold: a tracker-off run's four counts
equal the tracker-on run's of the same seed leaf for leaf, every leaf
outside the tracker plane is equal too, and the rest of the tracker plane
stays at zero with the tracker off, as it was before the marks left it.
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu.engine import round as rnd
from shadow_tpu.engine.round import run_until
from shadow_tpu.engine.sharded import AXIS, ShardedRunner
from shadow_tpu.simtime import NS_PER_MS

MARKS = ("exch_hwm", "land_hwm", "land_passes", "flush_cols")
END = 30 * NS_PER_MS


def _run(kind, devices, tracker):
    if kind == "tgen":
        cfg, model, tables, st0 = _tgen_world(16, 0.02, 20_000_000, seed=3)
    else:
        cfg, model, tables, st0 = _phold_world(16)
    cfg = dataclasses.replace(cfg, tracker=tracker)
    probes = []
    if devices == 1:
        out = run_until(st0, END, model, tables, cfg, rounds_per_chunk=4,
                        on_chunk=probes.append)
    else:
        mesh = Mesh(np.array(jax.devices()[:devices]), (AXIS,))
        runner = ShardedRunner(mesh, model, tables, cfg, rounds_per_chunk=4)
        out = runner.run_until(st0, END, on_chunk=probes.append)
    return out, probes[-1]


def _leaves(tree):
    """{path: numpy leaf}, a typed key as its words."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out[jax.tree_util.keystr(path)] = np.asarray(leaf)
    return out


@pytest.mark.parametrize("kind", ("tgen", "phold"))
@pytest.mark.parametrize("devices", (1, 4), ids=("one-chip", "four-devices"))
def test_the_marks_count_with_the_tracker_off(kind, devices):
    off, probe_off = _run(kind, devices, tracker=False)
    on, probe_on = _run(kind, devices, tracker=True)
    for name in MARKS:
        got, want = np.asarray(getattr(off.tracker, name)), np.asarray(getattr(on.tracker, name))
        assert np.array_equal(got, want), name
        # on each shard's row 0 and nowhere else
        rows = np.flatnonzero(got)
        assert rows.size and set(rows) <= set(range(0, 16, 16 // devices)), (name, rows)
        assert getattr(probe_off, name) == getattr(probe_on, name) > 0
    assert probe_off.exch_hwm == int(off.tracker.exch_hwm.max())
    assert probe_off.land_hwm == int(off.tracker.land_hwm.max())
    assert probe_off.land_passes == int(off.tracker.land_passes.sum())
    # whole blocks of columns a live round that staged something, on every
    # shard as many: at most shards x live rounds x the capacity
    assert probe_off.flush_cols == int(off.tracker.flush_cols.sum())
    o_cap = off.outbox.valid.shape[1]
    assert probe_off.flush_cols % devices == 0
    assert o_cap // 8 * devices <= probe_off.flush_cols <= devices * probe_off.rounds_live * o_cap
    # the marks feed nothing back: the simulated state and every counter
    # outside the tracker plane are the tracker-on run's
    a, b = _leaves(off.replace(tracker=None)), _leaves(on.replace(tracker=None))
    assert a.keys() == b.keys()
    for path in a:
        assert np.array_equal(a[path], b[path]), path
    # and nothing else of the tracker plane was turned on
    for path, leaf in _leaves(off.tracker).items():
        if not any(name in path for name in MARKS):
            assert not leaf.any(), path
    assert int(on.tracker.queue_hwm.max()) > 0  # the plane itself still counts


@pytest.mark.parametrize("name", MARKS)
def test_the_probe_carries_each_count_by_name(name):
    """A lane is read through ChunkProbe's field of the same name: the
    PROBE_* index and the field's position agree, whatever they are."""
    fields = [f.name for f in dataclasses.fields(rnd.ChunkProbe)]
    assert len(fields) == rnd.PROBE_LANES
    assert fields[getattr(rnd, "PROBE_" + name.upper())] == name
    cfg, model, tables, st0 = _phold_world(16)
    st = run_until(st0, END, model, tables, cfg, rounds_per_chunk=4)
    probe = rnd.ChunkProbe.from_array(rnd.state_probe(st))
    leaf = np.asarray(getattr(st.tracker, name))
    assert getattr(probe, name) == int(leaf.max() if name.endswith("_hwm") else leaf.sum()) > 0

