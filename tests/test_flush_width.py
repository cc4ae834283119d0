"""The flush flattens the staged columns, not the outbox's capacity
(engine/round.py flush_outbox): a lax.while_loop hands the landing's sort
one block of flush_block(O) slot columns at a time, for as many blocks as
hold the busiest row's fill, and one pull lands what the blocks grouped.

Pinned here on tiny worlds: (a) whatever the busiest row staged, the state
after flush_outbox is the whole-outbox flush's leaf for leaf, on one
device, block-sharded over four virtual ones in both exchange modes, and
over whole rounds of either engine; the columns taken are the fewest
whole blocks that fit and TrackerState.flush_cols books them; (b) the
invariant the slice rests on, valid[h, o] == (o < fill[h]), on what each
engine stages, after grow_state and after a checkpoint round-trip; (c) a
cfg.ensemble trace keeps ONE block, the whole outbox; (d) the chunk holds
one sort, of H x flush_block(O) entries, and no other.
"""

import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh

from test_mesh import _canon_queue
from test_pipeline import _phold_world
from test_pump import _world as _tgen_world

from shadow_tpu import equeue
from shadow_tpu.engine import round as rnd
from shadow_tpu.engine.sharded import AXIS, shard_state, state_specs
from shadow_tpu.engine.state import EngineConfig, grow_state, init_state, state_to_host
from shadow_tpu.runtime import checkpoint
from shadow_tpu.simtime import NS_PER_MS

HOSTS, OUTBOX, QUEUE = 8, 16, 64
BLOCK = rnd.flush_block(OUTBOX)
# the busiest row's fill: 0, 1, O, and W and W + 1 for widths W of one, two,
# four and seven blocks
FILLS = sorted({0, 1, OUTBOX} | {k * BLOCK + d for k in (1, 2, 4, 7) for d in (0, 1)})
# the leaves that say HOW a flush ran, booked by flush_outbox and not by the
# flush body the comparison calls directly
BOOKED = ("land_hwm", "land_passes", "flush_cols")
GRIDS = ("time", "tie", "kind", "data", "aux")  # the queue's slot grids


def _cols(busiest, block=BLOCK):
    """The columns a flush takes: the fewest whole blocks that hold the
    busiest row's fill."""
    return -(-busiest // block) * block


@pytest.mark.parametrize(
    "o_cap,block", [(16, 2), (512, 64), (256, 32), (64, 8), (48, 6), (8, 1), (4, 1), (1, 1),
                    (100, 10), (13, 1)],
)
def test_the_block_is_a_function_of_the_capacity_alone(o_cap, block):
    """An eighth of the capacity, the largest divisor of it that is no more,
    none below 1."""
    assert rnd.flush_block(o_cap) == block and o_cap % block == 0


def _cfg(**over):
    return EngineConfig(
        num_hosts=HOSTS, queue_capacity=QUEUE, outbox_capacity=OUTBOX,
        runahead_ns=NS_PER_MS, **over,
    )


def _staged(cfg, busiest, seed=5):
    """An empty-queue state whose outbox row 5 (the third of four shards)
    staged `busiest` entries and every other row at most as many, in the
    first fill[h] columns as stage_packets leaves them."""
    r = np.random.default_rng(seed + busiest)
    st = init_state(cfg, model_state=())
    h, o = st.outbox.valid.shape
    fill = r.integers(0, busiest + 1, size=h)
    fill[5] = busiest
    valid = np.arange(o)[None, :] < fill[:, None]
    n = h * o
    ob = st.outbox.replace(
        valid=jnp.asarray(valid),
        dst=jnp.asarray(np.where(valid, r.integers(0, h, size=(h, o)), 0), jnp.int32),
        time=jnp.asarray(
            np.where(valid, 10 * NS_PER_MS + r.permutation(n).reshape(h, o), np.asarray(st.outbox.time))
        ),
        tie=jnp.asarray(np.where(valid, 1 + np.arange(n).reshape(h, o), 0)),
        data=jnp.asarray(
            np.where(valid[:, None, :], r.integers(1, 1 << 20, size=st.outbox.data.shape), 0), jnp.int32
        ),
        aux=jnp.asarray(np.where(valid, r.integers(1, 1500, size=(h, o)), 0), jnp.int32),
        fill=jnp.asarray(fill, jnp.int32),
    )
    return st.replace(outbox=ob)


@functools.lru_cache(maxsize=None)
def _flushes(plane):
    """(cfg, place, the flush as the engine calls it, the whole-outbox flush
    body called directly) for one plane, each compiled once."""
    if plane == "one-device":
        cfg = _cfg()
        return (
            cfg, lambda st: st,
            jax.jit(lambda st: rnd.flush_outbox(st, None, cfg)),
            jax.jit(lambda st: rnd._flush_outbox_traffic(st, None, cfg)),
        )
    cfg = _cfg(exchange=plane)
    mesh = Mesh(np.array(jax.devices()[:4]), (AXIS,))
    specs = state_specs(init_state(cfg, model_state=()))

    def sharded(f):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False))

    def whole(st):
        st, max_land = rnd._flush_outbox_traffic(st, AXIS, cfg)
        # max_land rides out on land_hwm's row 0, where flush_outbox books it
        return st.replace(tracker=st.tracker.replace(land_hwm=st.tracker.land_hwm.at[0].set(max_land)))

    return (
        cfg, lambda st: shard_state(st, mesh),
        sharded(lambda st: rnd.flush_outbox(st, AXIS, cfg)),
        sharded(whole),
    )


def _leaves(tree):
    return {
        jax.tree_util.keystr(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key)
    }


def _assert_equal_but(got, want, skipped=BOOKED):
    a, b = _leaves(got), _leaves(want)
    assert a.keys() == b.keys()
    for path in a:
        if not any(path.endswith("." + name) for name in skipped):
            assert np.array_equal(a[path], b[path]), path


@pytest.mark.parametrize("busiest", FILLS)
@pytest.mark.parametrize("plane", ("one-device", "all_to_all", "all_gather"))
def test_flush_equals_the_whole_outbox_flush(plane, busiest):
    cfg, place, flush, whole = _flushes(plane)
    st = place(_staged(cfg, busiest))
    got = flush(st)
    want = whole(st)
    if plane == "one-device":
        want, max_land = want
        max_lands = [int(max_land)]
    else:
        max_lands = np.asarray(want.tracker.land_hwm)[:: HOSTS // 4].tolist()
    if plane == "one-device":
        _assert_equal_but(got, want)
    else:
        # across shards a destination's arrivals come block by block, and
        # inside a block peer by peer, where one block of the whole outbox
        # hands them over peer by peer: the same events in other free slots
        # (pop order is key-driven), every other leaf equal
        _assert_equal_but(got, want, skipped=BOOKED + tuple("queue." + g for g in GRIDS))
        for h in range(HOSTS):
            assert _canon_queue(got.queue, h) == _canon_queue(want.queue, h), h
    assert not np.asarray(got.outbox.valid).any() and not np.asarray(got.outbox.fill).any()
    assert int(got.queue.count.sum()) == int(np.asarray(st.outbox.valid).sum())
    assert int(got.queue.overflow.sum()) == 0 and int(got.outbox.overflow.sum()) == 0
    # the columns taken: the fewest whole blocks that hold the busiest
    # row's fill WHEREVER that row lives (every shard takes the same), 0
    # for a skipped flush; booked on each shard's row 0 and nowhere else
    width = _cols(busiest)
    rows = len(max_lands)
    cols = np.zeros(HOSTS, np.int32)
    cols[:: HOSTS // rows] = width
    assert np.asarray(got.tracker.flush_cols).tolist() == cols.tolist()
    hwm = np.zeros(HOSTS, np.int32)
    hwm[:: HOSTS // rows] = max_lands
    assert np.asarray(got.tracker.land_hwm).tolist() == hwm.tolist()
    assert np.asarray(got.tracker.land_passes).tolist() == (-(-hwm // equeue.LAND_LANES)).tolist()
    probe = rnd.ChunkProbe.from_array(rnd.state_probe(got))
    assert probe.flush_cols == rows * width


def test_flush_cols_sums_over_the_flushes():
    cfg, _place, flush, _whole = _flushes("one-device")
    st, want = init_state(cfg, model_state=()), 0
    for busiest in (3, 0, 16, 1, 0, 8):
        st = flush(st.replace(outbox=_staged(cfg, busiest).outbox, queue=equeue.create(HOSTS, QUEUE)))
        want += _cols(busiest)
    assert np.asarray(st.tracker.flush_cols).tolist() == [want] + [0] * (HOSTS - 1)


# --- whole rounds of either engine ---------------------------------------


def _fills_hold(ob):
    valid, fill = np.asarray(ob.valid), np.asarray(ob.fill)
    return np.array_equal(valid, np.arange(valid.shape[-1]) < fill[..., None])


@contextlib.contextmanager
def _traced_with(name, value):
    """engine.round's `name` replaced while a jitted function is traced."""
    real = getattr(rnd, name)
    setattr(rnd, name, value)
    try:
        yield
    finally:
        setattr(rnd, name, real)


@functools.lru_cache(maxsize=None)
def _engine_runs(engine):
    """A 16-host tgen world under one engine: the state after 12 rounds of
    the block loop, the state after the same rounds with every flush on
    the whole outbox as one block, and what rounds 3, 7 and 13 staged,
    taken right before their flush."""
    cfg, model, tables, st0 = _tgen_world(16, 0.02, 20_000_000, seed=3)
    cfg = dataclasses.replace(cfg, engine=engine, pump_k=3 if engine == "pump" else 0)
    end = jnp.asarray(10_000 * NS_PER_MS, jnp.int64)

    def a_round():  # traced anew for each caller: one scan of one round
        return jax.jit(lambda s: rnd.run_rounds_scan(s, end, 1, model, tables, cfg))

    def drain(s):  # run_round, its flush taken out
        window_end = rnd._next_window_end(s, end, cfg, None, tables=tables)
        return rnd.run_round(s, window_end, model, tables, cfg)

    step, states = a_round(), [st0]
    for _ in range(12):
        states.append(step(states[-1]))
    ladder = states[-1]
    with _traced_with("flush_block", lambda o: o):
        step, whole = a_round(), st0
        for _ in range(12):
            whole = step(whole)
    with _traced_with("flush_outbox", lambda st, axis_name, cfg=None: st):
        drain = jax.jit(drain)
        staged = [drain(states[n]) for n in (2, 6, 12)]
    return cfg, ladder, whole, staged


@pytest.mark.parametrize("engine", ("plain", "pump"))
def test_rounds_equal_the_whole_outbox_rounds(engine):
    cfg, ladder, whole, _staged_states = _engine_runs(engine)
    rnd.check_capacity(ladder)
    assert int(ladder.packets_sent.sum()) > 0
    _assert_equal_but(ladder, whole, skipped=("flush_cols",))
    # every live round's flush took whole blocks; the one-block rounds took O
    o = cfg.outbox_capacity
    assert int(ladder.tracker.flush_cols[0]) % rnd.flush_block(o) == 0
    assert 0 < int(ladder.tracker.flush_cols[0]) < int(whole.tracker.flush_cols[0])
    assert int(whole.tracker.flush_cols[0]) % o == 0
    assert int(whole.tracker.flush_cols[0]) <= int(whole.rounds_live) * o


@pytest.mark.parametrize("engine", ("plain", "pump"))
def test_staged_entries_are_each_rows_first_fill_columns(engine):
    cfg, _ladder, _whole, staged = _engine_runs(engine)
    assert len(staged) == 3 and any(int(s.outbox.fill.max()) > 1 for s in staged)
    for st in staged:
        assert _fills_hold(st.outbox)
        # and the block loop's flush of what the engine staged is the whole one's
        got = rnd.flush_outbox(st, None, cfg)
        want, _max_land = rnd._flush_outbox_traffic(st, None, cfg)
        _assert_equal_but(got, want)
        assert _fills_hold(got.outbox)


def test_the_invariant_survives_grow_state():
    cfg = _cfg()
    st = _staged(cfg, 5)
    grown = grow_state(st, outbox_capacity=2 * OUTBOX)
    assert grown.outbox.valid.shape == (HOSTS, 2 * OUTBOX) and _fills_hold(grown.outbox)
    gcfg = dataclasses.replace(cfg, outbox_capacity=2 * OUTBOX)
    got, want = rnd.flush_outbox(grown, None, gcfg), rnd.flush_outbox(st, None, cfg)
    # blocks of 4 columns grown, of 2 before: 8 and 6 columns hold a fill of 5
    assert (int(got.tracker.flush_cols[0]), int(want.tracker.flush_cols[0])) == (8, 6)
    _assert_equal_but(got.replace(outbox=None), want.replace(outbox=None), skipped=("flush_cols",))


def test_the_invariant_survives_a_checkpoint(tmp_path):
    cfg = _cfg()
    st = _staged(cfg, 9)
    path = checkpoint.save_checkpoint(str(tmp_path / "ckpt.npz"), state_to_host(st), {"fingerprint": "f"})
    assert checkpoint.peek_checkpoint_meta(path)["version"] == checkpoint.CHECKPOINT_VERSION == 4
    back, _meta = checkpoint.load_checkpoint(path, init_state(cfg, model_state=()), fingerprint="f")
    assert _fills_hold(back.outbox)
    _assert_equal_but(back, st, skipped=())
    _assert_equal_but(rnd.flush_outbox(back, None, cfg), rnd.flush_outbox(st, None, cfg), skipped=())


# --- what is lowered -------------------------------------------------------


def _sort_sizes(text):
    """Entries of every sort in a lowered (StableHLO) text, ascending."""
    return sorted(
        int(m.group(1))
        for m in re.finditer(r'stablehlo\.sort"?\(.*?\}\) : \(tensor<(\d+)xi32>', text, re.S)
    )


def test_an_ensemble_trace_keeps_one_block():
    st = _staged(_cfg(), 3)
    plain = jax.jit(lambda s: rnd.flush_outbox(s, None, _cfg())).lower(st).as_text()
    assert _sort_sizes(plain) == [HOSTS * BLOCK]
    ens_cfg = _cfg(ensemble=True)
    ens = jax.jit(lambda s: rnd.flush_outbox(s, None, ens_cfg))
    assert _sort_sizes(ens.lower(st).as_text()) == [HOSTS * OUTBOX]
    # and it books the one block it has, whatever was staged
    assert int(ens(st).tracker.flush_cols[0]) == OUTBOX
    assert int(ens(_staged(_cfg(), 0)).tracker.flush_cols[0]) == 0
    _assert_equal_but(ens(st), rnd.flush_outbox(st, None, _cfg()), skipped=("flush_cols",))
    # batched over replicas too: the cond's two branches, one of them the skip
    batch = jax.tree.map(lambda *xs: jnp.stack(xs), st, _staged(_cfg(), 0), _staged(_cfg(), 16))
    out = jax.jit(jax.vmap(lambda s: rnd.flush_outbox(s, None, ens_cfg)))(batch)
    assert np.asarray(out.tracker.flush_cols)[:, 0].tolist() == [OUTBOX, 0, OUTBOX]


def test_the_chunk_holds_one_sort_of_one_block():
    cfg, model, tables, st0 = _phold_world(8)
    lowered = jax.jit(rnd._run_chunk, static_argnums=(2, 3, 5)).lower(
        st0, jnp.asarray(40 * NS_PER_MS, jnp.int64), 4, model, tables, cfg
    )
    assert rnd.flush_block(cfg.outbox_capacity) == 1
    assert _sort_sizes(lowered.as_text()) == [cfg.num_hosts * 1]


# --- the benchmark's reader ------------------------------------------------


def test_flat_pct_reads_the_columns_over_chips_rounds_and_capacity(monkeypatch):
    """`exchange.flat_pct` = the unit's flush_cols over chips x live rounds x
    the outbox capacity; None against a program whose probe has no
    flush_cols (the parent), that keeps no probes, or whose newest entry
    was not the unit; it raises nothing."""
    import importlib.util
    import pathlib
    import types

    from shadow_tpu import scopes

    bench = pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
    monkeypatch.syspath_prepend(str(bench))
    spec = importlib.util.spec_from_file_location(
        "exchange_flat_pct", bench / "layer_metrics" / "exchange.flat_pct.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def probe(**lanes):
        return dataclasses.replace(rnd.ChunkProbe.from_array([0] * rnd.PROBE_LANES), **lanes)

    ctx = types.SimpleNamespace(iters_per_unit=40, chips=1)
    kept = scopes.EntryProbes(
        hosts=100, outbox_slots=100 * 512,
        entry=probe(rounds_live=7, iters=60, flush_cols=7 * 512),
        chunk=probe(rounds_live=12, iters=100, flush_cols=7 * 512 + 4 * 64 + 128),
    )
    monkeypatch.setattr(scopes, "last_probes", kept)
    assert mod.read(ctx) == pytest.approx(100.0 * (4 * 64 + 128) / (5 * 512))
    # four chips: every shard books the same width, the capacity is a row's
    ctx.chips = 4
    kept.chunk = probe(rounds_live=12, iters=100, flush_cols=7 * 512 + 4 * 5 * 64)
    assert mod.read(ctx) == pytest.approx(12.5)
    ctx.iters_per_unit = 41  # the newest entry was not the unit
    assert mod.read(ctx) is None
    ctx.iters_per_unit = 40
    # the parent's probe: 25 lanes, no flush_cols
    old = types.SimpleNamespace(
        **{f.name: 0 for f in dataclasses.fields(rnd.ChunkProbe) if f.name != "flush_cols"}
    )
    old.rounds_live, old.iters = 12, 100
    kept.chunk = old
    assert mod.read(ctx) is None
    kept.chunk = None  # an entry that launched no chunk
    assert mod.read(ctx) is None
    monkeypatch.setattr(scopes, "last_probes", None)
    assert mod.read(ctx) is None
