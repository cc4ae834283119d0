#!/usr/bin/env python3
"""Where a front-door round goes on the device: the event handler, the
round-boundary flush, and the pieces of the flush's landing
(equeue.push_many_sorted: index sort and runs, word gather into sorted
order, the [H, queue] pull gather, the merging where pass).

Three parts, each printing one JSON line per timing (median and min of
`--reps` blocked calls, milliseconds, on whatever backend JAX has — a
time is a device time only when `backend` says tpu):

  world    the config's world as the front door builds it: `--iters`
           handle_one_iteration calls in one scan (ms per iteration),
           then flush_outbox on the outbox those iterations filled;
  landing  push_many_sorted alone on a synthetic whole outbox
           (hosts x outbox entries, `--fill` of them valid, uniform
           destinations) at deliver_lanes 48 and queue_capacity (one
           program since the landing became a pull), its four pieces, and — with `--parent-equeue PATH`, a copy
           of an older shadow_tpu/equeue.py — that file's push_many_sorted
           on the same inputs (results compared leaf for leaf);
  divide   int64 `//` by a constant and by a per-host divisor against
           intmath.divmod_nonneg, 1,000 chained steps in one scan.

  python tools/profile_landing.py [--config YAML] [--hosts N] [--out FILE]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(ROOT, "examples", "tgen-10k", "shadow.yaml"))
    ap.add_argument("--hosts", type=int, default=0, help="cut the host groups evenly (0 = as written)")
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--fill", type=float, nargs="+", default=[0.006, 1.0],
                    help="valid share of the synthetic outbox (0.006 = the tgen-10k run's ~3,800 packets per round)")
    ap.add_argument("--parent-equeue", default="")
    ap.add_argument("--parts", nargs="+", default=["world", "landing", "divide"])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from shadow_tpu import equeue
    from shadow_tpu.config import load_config_file
    from shadow_tpu.engine import round as rnd
    from shadow_tpu.engine.state import init_state
    from shadow_tpu.intmath import divmod_nonneg
    from shadow_tpu.runtime.manager import Manager

    backend = jax.default_backend()
    lines = []

    def emit(**kw):
        kw = {"backend": backend, **kw}
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def timed(name, fn, *a, per=1, **facts):
        """Compile (first call, timed apart), then `reps` blocked calls."""
        t0 = time.perf_counter()
        try:
            out = jax.block_until_ready(fn(*a))
        except Exception as e:  # noqa: BLE001 — say so and go on to the next timing
            emit(name=name, error=f"{type(e).__name__}: {e}"[:300], **facts)
            raise
        first = time.perf_counter() - t0
        ms = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a))
            ms.append((time.perf_counter() - t0) * 1e3 / per)
        emit(name=name, median_ms=statistics.median(ms), min_ms=min(ms),
             first_call_s=round(first, 1), reps=args.reps, **facts)
        return out

    config = load_config_file(args.config)
    if args.hosts:
        per, rem = divmod(args.hosts, len(config.hosts))
        if rem or per < 1:
            raise SystemExit(f"--hosts {args.hosts} does not divide over {len(config.hosts)} host groups")
        for spec in config.hosts:
            spec.quantity = per
    w = Manager(config).build_world()
    cfg = w.ecfg
    h, qcap, ocap = cfg.num_hosts, cfg.queue_capacity, cfg.outbox_capacity
    emit(name="shapes", hosts=h, queue_capacity=qcap, outbox_capacity=ocap,
         deliver_lanes=cfg.deliver_lanes or qcap, device=jax.devices()[0].device_kind)

    if "world" in args.parts:
        st0 = jax.jit(lambda: rnd.bootstrap(
            init_state(cfg, w.model.init(), tx_bytes_per_interval=w.tx_refill,
                       rx_bytes_per_interval=w.rx_refill), w.model, cfg))()
        # a window wide enough that every iteration finds eligible hosts
        we = jnp.asarray(40_000_000, jnp.int64)

        def fill(s, tb):
            def body(s, _):
                return rnd.handle_one_iteration(s, we, w.model, tb, cfg), None
            return jax.lax.scan(body, s, None, length=args.iters)[0]

        st = timed("handle_one_iteration", jax.jit(fill), st0, w.tables, per=args.iters,
                   iters_per_call=args.iters)
        staged = int(np.asarray(st.outbox.valid).sum())
        timed("flush_outbox", jax.jit(lambda s: rnd.flush_outbox(s, None, cfg)), st,
              staged_packets=staged, outbox_entries=h * ocap)
        del st, st0

    if "landing" in args.parts:
        m = h * ocap
        rng = np.random.default_rng(22)
        parent = None
        if args.parent_equeue:
            spec = importlib.util.spec_from_file_location("parent_equeue", args.parent_equeue)
            parent = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(parent)

        def pieces(d):
            """push_many_sorted's own steps, re-spelled here so each can be
            timed alone; `whole` composes them and is checked against the
            real function below, so this copy cannot drift unnoticed."""

            def sort(dst, valid):
                key1 = jnp.where(valid, dst, h).astype(jnp.int32)
                pos = jnp.arange(m, dtype=jnp.int32)
                _, order = jax.lax.sort((key1, pos), num_keys=1, is_stable=True)
                cnt, begin = equeue.run_bounds(key1, h)
                return order, begin, cnt

            def pack(tm, tie, kind, data, aux):
                lo = lambda x: x.astype(jnp.int32)  # noqa: E731
                hi = lambda x: (x >> 32).astype(jnp.int32)  # noqa: E731
                return jnp.concatenate(
                    [jnp.stack([lo(tm), hi(tm), lo(tie), hi(tie), kind, aux]), data.T])

            def gather(words, order):
                return words[:, order]

            def pull(q, begin, cnt, words_s):
                free = q.time == equeue.TIME_MAX
                fr = (jnp.cumsum(free, axis=1) - free).astype(jnp.int32)
                land = jnp.minimum(jnp.minimum(cnt, d), qcap - q.count)
                take = free & (fr < land[:, None])
                return take, land, words_s[:, jnp.minimum(begin[:, None] + fr, m - 1)]

            def merge(q, take, land, cnt, g):
                def l64(low, high):
                    low = jax.lax.bitcast_convert_type(low, jnp.uint32)
                    return (high.astype(jnp.int64) << 32) | low.astype(jnp.int64)

                gt = l64(g[0], g[1])
                return q.replace(
                    time=jnp.where(take, gt, q.time),
                    tie=jnp.where(take, l64(g[2], g[3]), q.tie),
                    kind=jnp.where(take, g[4], q.kind),
                    data=jnp.where(take[:, :, None], jnp.moveaxis(g[6:], 0, -1), q.data),
                    aux=jnp.where(take, g[5], q.aux),
                    count=q.count + land,
                    overflow=q.overflow + (jnp.minimum(cnt, d) - land),
                    head_time=jnp.minimum(q.head_time, jnp.min(
                        jnp.where(take, gt, equeue.TIME_MAX), axis=1)))

            return sort, pack, gather, pull, merge

        def jitted(d):
            """One set of compiled functions per grid width, shared by
            every fill level (a compile here is minutes on the chip)."""
            fns = {k: jax.jit(f) for k, f in zip(
                ("sort", "pack", "gather", "pull", "merge"), pieces(d))}
            fns["new"] = jax.jit(
                lambda q, *a: equeue.push_many_sorted(q, *a, deliver_lanes=d))
            if parent is not None and d == 48:
                fns["parent"] = jax.jit(
                    lambda q, *a: parent.push_many_sorted(q, *a, deliver_lanes=d))
            return fns

        widths = {d: jitted(d) for d in sorted({48, cfg.deliver_lanes or qcap})}
        q0 = equeue.create(h, qcap)
        for_parent = []  # timed after the "divide" part: its compile is the long one
        for fill_share in args.fill:
            valid = jnp.asarray(rng.random(m) < fill_share)
            dst = jnp.asarray(rng.integers(0, h, m), jnp.int32)
            tm = jnp.asarray(rng.integers(0, 1 << 40, m), jnp.int64)
            tie = jnp.asarray(rng.integers(0, 1 << 62, m), jnp.int64)
            kind = jnp.asarray(rng.integers(1, 5, m), jnp.int32)
            data = jnp.asarray(rng.integers(0, 1 << 30, (m, equeue.PAYLOAD_LANES)), jnp.int32)
            aux = jnp.asarray(rng.integers(0, 1500, m), jnp.int32)
            ent = (dst, valid, tm, tie, kind, data, aux)
            for d, fn in widths.items():
                f = dict(entries=m, valid=int(np.asarray(valid).sum()), deliver_lanes=d)
                new = timed("landing_new", fn["new"], q0, *ent, **f)
                order, begin, cnt = timed("landing_new.sort", fn["sort"], dst, valid, **f)
                rows = fn["pack"](tm, tie, kind, data, aux)
                rows_s = timed("landing_new.gather", fn["gather"], rows, order, **f)
                take, land, g = timed("landing_new.pull", fn["pull"], q0, begin, cnt, rows_s, **f)
                whole = timed("landing_new.merge", fn["merge"], q0, take, land, cnt, g, **f)
                emit(name="landing_new.pieces_equal_whole", **f, ok=all(
                    bool(jnp.array_equal(a, b)) for a, b in zip(
                        jax.tree.leaves(whole.replace(overflow=new.overflow)),
                        jax.tree.leaves(new))))
                del g, take, rows, rows_s, whole
                if "parent" in fn:
                    for_parent.append((f, ent, new))
                del new

    if "divide" in args.parts:
        steps = 1000
        x0 = jnp.asarray(np.random.default_rng(5).integers(1 << 20, 1 << 50, h), jnp.int64)
        dv = jnp.asarray(np.random.default_rng(6).integers(1, 1 << 24, h), jnp.int64)

        def chain(div):
            def f(x, d):
                def body(c, _):
                    return x + (div(c, d) & 1023), None  # next operand depends on this quotient
                return jax.lax.scan(body, x, None, length=steps)[0]
            return jax.jit(f)

        for name, div in (
            ("floordiv_const", lambda c, d: c // 1_000_000),
            ("divmod_nonneg_const", lambda c, d: divmod_nonneg(c, 1_000_000)[0]),
            ("floordiv_var", lambda c, d: c // d),
            ("divmod_nonneg_var", lambda c, d: divmod_nonneg(c, d)[0]),
        ):
            try:
                timed("divide." + name, chain(div), x0, dv, per=steps, lanes=h)
            except Exception:  # noqa: BLE001 — reported by timed(); the others are independent
                pass

    if "landing" in args.parts:
        for f, ent, new in for_parent:
            old = timed("landing_parent", widths[48]["parent"], parent.create(h, qcap), *ent, **f)
            emit(name="landing_parent.equal_new", **f, ok=all(
                bool(jnp.array_equal(a, b)) for a, b in zip(
                    jax.tree.leaves(old), jax.tree.leaves(new))))

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
