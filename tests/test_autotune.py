"""Unit tests for the compile-budget autotuner's planner
(runtime/autotune.py) — the pure decision logic, exercised through the
persisted probe cache so no XLA compile is paid here."""

import json

import jax
import pytest

from shadow_tpu.engine import EngineConfig
from shadow_tpu.runtime import autotune
from shadow_tpu.runtime.autotune import (
    AutotunePlan,
    candidate_ladder,
    plan_pump_k,
    plan_rounds_per_chunk,
)


def _cfg(**kw):
    return EngineConfig(num_hosts=8, runahead_ns=1_000_000, **kw)


def _seed_cache(tmp_path, cfg, probe_wall_s, probe_rpc=4):
    """Pre-seed the probe cache so the planner never runs a probe."""
    key = autotune._cache_key(cfg, probe_rpc, jax.default_backend())
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({key: {"probe_wall_s": probe_wall_s}}))
    return str(path)


def test_candidate_ladder_walks_down_to_floor():
    assert candidate_ladder(256) == [256, 128, 64, 32, 16]
    assert candidate_ladder(100) == [100, 64, 32, 16]
    assert candidate_ladder(32) == [32, 16]
    # a non-default floor is always appended
    assert candidate_ladder(64, floor=8) == [64, 32, 16, 8]


def test_no_budget_disables():
    plan = plan_rounds_per_chunk(
        None, None, None, _cfg(), requested=128, budget_s=0.0
    )
    assert plan.source == "disabled"
    assert plan.rounds_per_chunk == 128


def test_requested_at_floor_skips_probe():
    plan = plan_rounds_per_chunk(
        None, None, None, _cfg(), requested=16, budget_s=100.0
    )
    assert plan.source == "floor"
    assert plan.rounds_per_chunk == 16
    assert plan.probe_wall_s is None


def test_cached_probe_corrects_oversized_rpc(tmp_path):
    # probe said 4 rounds compile in 10 s -> 128 rounds project to 320 s,
    # way past a 60 s budget; the ladder lands on 16 (projection 40 s)
    cfg = _cfg()
    cache = _seed_cache(tmp_path, cfg, probe_wall_s=10.0)
    plan = plan_rounds_per_chunk(
        None, None, None, cfg, requested=128, budget_s=60.0,
        cache_path=cache,
    )
    assert plan.source == "cache"
    assert plan.rounds_per_chunk == 16
    assert plan.projected_compile_s == pytest.approx(40.0)


def test_cached_probe_keeps_fitting_rpc(tmp_path):
    cfg = _cfg()
    cache = _seed_cache(tmp_path, cfg, probe_wall_s=0.1)
    plan = plan_rounds_per_chunk(
        None, None, None, cfg, requested=128, budget_s=60.0,
        cache_path=cache,
    )
    assert plan.source == "cache"
    assert plan.rounds_per_chunk == 128


def test_n_compiles_scales_projection(tmp_path):
    # the same probe wall that fits one compile does not fit six
    cfg = _cfg()
    cache = _seed_cache(tmp_path, cfg, probe_wall_s=1.0)
    one = plan_rounds_per_chunk(
        None, None, None, cfg, requested=128, budget_s=40.0,
        n_compiles=1.0, cache_path=cache,
    )
    six = plan_rounds_per_chunk(
        None, None, None, cfg, requested=128, budget_s=40.0,
        n_compiles=6.0, cache_path=cache,
    )
    assert one.rounds_per_chunk == 128
    assert six.rounds_per_chunk < 128


def test_cache_key_canonicalizes_seed(tmp_path):
    # two worlds differing only in seed share one probe entry
    cache = _seed_cache(tmp_path, _cfg(seed=1), probe_wall_s=10.0)
    plan = plan_rounds_per_chunk(
        None, None, None, _cfg(seed=2), requested=128, budget_s=60.0,
        cache_path=cache,
    )
    assert plan.source == "cache"


def test_lazy_state_thunk_not_built_on_cache_hit(tmp_path):
    # st0 may be a zero-arg callable; early exits (cache hit here, also
    # the rpc floor / zero budget) must never pay the full-width state
    # build behind it
    def boom():
        raise AssertionError("probe state built despite a warm cache")

    cache = _seed_cache(tmp_path, _cfg(), probe_wall_s=10.0)
    plan = plan_rounds_per_chunk(
        boom, None, None, _cfg(), requested=128, budget_s=60.0,
        cache_path=cache,
    )
    assert plan.source == "cache"


def _plan(**kw) -> AutotunePlan:
    base = dict(
        rounds_per_chunk=32, requested=32, budget_s=100.0, n_compiles=1.0,
        probe_rpc=4, probe_wall_s=1.0, projected_compile_s=8.0,
        pump_k=None, source="cache", backend="cpu",
    )
    base.update(kw)
    return AutotunePlan(**base)


def test_plan_pump_k_never_raises_callers_value():
    # chosen candidate 16 >= caller's 8: keep (pump_k stays None)
    plan = plan_pump_k(_plan(budget_s=10_000.0), _cfg(engine="pump", pump_k=8))
    assert plan.pump_k is None


def test_plan_pump_k_caps_under_tight_budget():
    plan = plan_pump_k(
        _plan(probe_wall_s=10.0, budget_s=20.0),
        _cfg(engine="pump", pump_k=16),
    )
    assert plan.pump_k is not None and plan.pump_k < 16


def test_plan_pump_k_projection_not_diluted_by_current_k():
    # per_k = 0.5 * (32/4) = 4 s/microstep; limit = 20 * 0.25 = 5 s.
    # Every candidate's projected compile (4*16, 4*8, 4*4) exceeds the
    # share, so the cap must land at the ladder floor — a projection
    # divided by the caller's current pump_k would wrongly accept 8
    # (the BENCH_r05-style oversized compile this planner exists to stop)
    plan = plan_pump_k(
        _plan(probe_wall_s=0.5, budget_s=20.0),
        _cfg(engine="pump", pump_k=8),
    )
    assert plan.pump_k == 4


def test_plan_pump_k_noop_without_probe_or_on_plain():
    assert plan_pump_k(
        _plan(probe_wall_s=None), _cfg(engine="pump", pump_k=8)
    ).pump_k is None
    assert plan_pump_k(_plan(), _cfg(engine="plain")).pump_k is None
