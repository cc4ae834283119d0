#!/usr/bin/env python3
"""Ask the chip's compiler, without the chip.

Compiles pieces of the front-door run path for a *described* TPU v5e
(`jax.experimental.topologies`, no device attached) at the shapes of a
config file, and prints per piece: compiled or the compiler's words, the
compile wall, and `memory_analysis()`. Nothing runs, so this says nothing
about results or speed: a compile that passes is a compile, never a run.

Code that asks `jax.default_backend()` sees the CPU here, so the pieces are
called directly with explicit engine names and `jax.eval_shape` shapes that
carry the described device's sharding:

  chunk            `_run_chunk` — the whole program `shadow-tpu run` launches
  sharded          the ShardedRunner chunk on the described 2x2 (4 devices)
  run_round        one round: drain while_loop + flush
  handle           `handle_one_iteration` — the full event handler
  pump_microstep   one pump microstep on a PumpCarry
  pump_stage       pump_k cond-guarded microsteps + carry init/finish
  flush            the round-boundary exchange cfg.exchange selects
  window           `_next_window_end`

Run with JAX_PLATFORMS=cpu, every call under a `timeout`, in the background:

  JAX_PLATFORMS=cpu timeout 1200 python tools/compile_for_chip.py \\
      --hosts 10240 --engine plain chunk > /root/scratch/chunk-plain.log 2>&1 &

Only one process at a time may load the TPU's library; to run several of
these at once, set ALLOW_MULTIPLE_LIBTPU_LOAD=1 in the shell (never in the
repository). `--hlo-dump DIR` passes XLA's --xla_dump_to; `--hlo-stats`
prints the op histogram of the optimized HLO of each piece, and how many
of its operations lie under each of the engine's scopes.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

PIECES = (
    "chunk", "sharded", "run_round", "handle", "pump_microstep", "pump_stage",
    "flush", "window",
)


def build_world(config_path: str, hosts: int):
    """The world the front door would build from `config_path`, with the
    host groups cut evenly to `hosts` in total (0 = as written)."""
    from shadow_tpu.config import load_config_file
    from shadow_tpu.runtime.manager import Manager

    config = load_config_file(config_path)
    if hosts:
        per, rem = divmod(hosts, len(config.hosts))
        if rem or per < 1:
            raise SystemExit(
                f"--hosts {hosts} does not divide over {len(config.hosts)} host groups"
            )
        for spec in config.hosts:
            spec.quantity = per
    return config, Manager(config).build_world()


def state_shapes(world, ecfg):
    import jax

    from shadow_tpu.engine.round import bootstrap
    from shadow_tpu.engine.state import init_state

    return jax.eval_shape(
        lambda: bootstrap(
            init_state(
                ecfg, world.model.init(),
                tx_bytes_per_interval=world.tx_refill,
                rx_bytes_per_interval=world.rx_refill,
            ),
            world.model, ecfg,
        )
    )


def on_device(tree, sharding):
    """ShapeDtypeStructs of `tree` (arrays or shapes), each carrying the
    described device's (or mesh's) sharding."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def hlo_histogram(text: str, top: int = 25) -> str:
    ops = collections.Counter(
        m.group(1) for m in re.finditer(r"= \S+ ([a-z\-]+)\(", text)
    )
    return ", ".join(f"{k}:{v}" for k, v in ops.most_common(top))


def scope_histogram(text: str) -> str:
    """Operations of the optimized HLO by the engine's `jax.named_scope`
    path (shadow_tpu/scopes.py): "" traced under no scope, None made by the
    compiler in a body no scope encloses."""
    from shadow_tpu import scopes

    paths = collections.Counter(v[1] for v in scopes.parse_hlo_text(text).values())
    return ", ".join(f"{k!r}:{v}" for k, v in paths.most_common())


def report(name: str, lowered_fn, hlo_stats: bool, dump_dir: str = "") -> bool:
    """Lower + compile one piece; print one block. Returns compiled?"""
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    try:
        lowered = lowered_fn()
        t1 = time.perf_counter()
        print(f"   traced+lowered in {t1 - t0:.1f}s "
              f"({len(lowered.as_text().splitlines())} StableHLO lines)", flush=True)
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — the compiler's words are the result
        words = f"{type(e).__name__}: {e}"
        print(f"   REFUSED after {time.perf_counter() - t0:.1f}s: {words[:2000]}",
              flush=True)
        return False
    wall = time.perf_counter() - t1
    ma = compiled.memory_analysis()
    mem = "memory_analysis: n/a" if ma is None else (
        f"args {ma.argument_size_in_bytes / 2**20:.1f} MiB, "
        f"out {ma.output_size_in_bytes / 2**20:.1f} MiB, "
        f"temp {ma.temp_size_in_bytes / 2**20:.1f} MiB, "
        f"alias {ma.alias_size_in_bytes / 2**20:.1f} MiB, "
        f"code {ma.generated_code_size_in_bytes / 2**20:.1f} MiB"
    )
    print(f"   COMPILED in {wall:.1f}s; {mem}", flush=True)
    if dump_dir:  # the chip's compiler does not honour --xla_dump_to itself
        with open(os.path.join(dump_dir, name + ".optimized.txt"), "w") as f:
            f.write(compiled.as_text())
    if hlo_stats:
        text = compiled.as_text()
        print(f"   optimized HLO: {len(text.splitlines())} lines; {hlo_histogram(text)}",
              flush=True)
        print(f"   operations by scope: {scope_histogram(text)}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("pieces", nargs="+", choices=PIECES)
    ap.add_argument("--config", default="examples/tgen-10k/shadow.yaml")
    ap.add_argument("--hosts", type=int, default=0, help="cut the world to N hosts")
    ap.add_argument("--engine", default="plain", choices=("plain", "pump"))
    ap.add_argument("--pump-k", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds per chunk (0 = the config's)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=INT",
                    help="override an EngineConfig field, e.g. deliver_lanes=64")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hlo-dump", metavar="DIR")
    ap.add_argument("--hlo-stats", action="store_true")
    args = ap.parse_args(argv)

    if args.hlo_dump:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_dump_to={args.hlo_dump} --xla_dump_hlo_pass_re=NONE"
        ).strip()

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    import shadow_tpu  # noqa: F401  (x64 on)
    from shadow_tpu.engine import round as rnd
    from shadow_tpu.engine.state import trace_static_cfg

    jax.config.update("jax_enable_compilation_cache", False)

    config, world = build_world(args.config, args.hosts)
    over = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        over[k] = int(v)
    pump_k = args.pump_k if args.engine != "plain" else 0
    ecfg = trace_static_cfg(
        dataclasses.replace(world.ecfg, engine=args.engine, pump_k=pump_k, **over)
    )
    rounds = args.rounds or config.experimental.rounds_per_chunk
    model = world.model

    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    one_chip = SingleDeviceSharding(topo.devices[0])
    print(f"# {ecfg.num_hosts} hosts, engine={args.engine} pump_k={pump_k}, "
          f"rounds_per_chunk={rounds}, queue={ecfg.queue_capacity} "
          f"outbox={ecfg.outbox_capacity} deliver_lanes={ecfg.deliver_lanes} "
          f"exchange={ecfg.exchange} max_iters={ecfg.max_iters_per_round}; "
          f"device {topo.devices[0].device_kind} ({args.topology}, described)",
          flush=True)

    shapes = state_shapes(world, ecfg)
    st = on_device(shapes, one_chip)
    tables = on_device(world.tables, one_chip)
    t64 = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)

    def piece(name):
        if name == "chunk":
            return lambda: jax.jit(
                rnd._run_chunk, static_argnums=(2, 3, 5), donate_argnums=(0,)
            ).lower(st, t64, rounds, model, tables, ecfg)
        if name == "run_round":
            return lambda: jax.jit(
                lambda s, we, tb: rnd.run_round(s, we, model, tb, ecfg)
            ).lower(st, t64, tables)
        if name == "handle":
            return lambda: jax.jit(
                lambda s, we, tb: rnd.handle_one_iteration(s, we, model, tb, ecfg)
            ).lower(st, t64, tables)
        if name == "flush":
            return lambda: jax.jit(
                lambda s: rnd.flush_outbox(s, None, ecfg)
            ).lower(st)
        if name == "window":
            return lambda: jax.jit(
                lambda s, e, tb: rnd._next_window_end(s, e, ecfg, None, tables=tb)
            ).lower(st, t64, tables)
        if name in ("pump_microstep", "pump_stage"):
            from shadow_tpu.engine import pump

            pcfg = ecfg if ecfg.pump_k > 0 else dataclasses.replace(
                ecfg, pump_k=args.pump_k
            )
            if name == "pump_stage":
                return lambda: jax.jit(
                    lambda s, we, tb: pump.pump_stage(s, we, model, tb, pcfg)
                ).lower(st, t64, tables)
            carry = on_device(
                jax.eval_shape(
                    lambda s, tb: pump.pump_carry_init(s, model, tb, pcfg),
                    shapes, world.tables,
                ),
                one_chip,
            )
            return lambda: jax.jit(
                lambda c, we, tb: pump.pump_microstep(c, we, model, tb, pcfg)
            ).lower(carry, t64, tables)
        if name == "sharded":
            from shadow_tpu.engine.sharded import AXIS, ShardedRunner, state_specs

            mesh = Mesh(np.array(topo.devices), (AXIS,))
            runner = ShardedRunner(mesh, model, world.tables, ecfg, rounds)
            specs = state_specs(shapes)
            flat_specs = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
            leaves, treedef = jax.tree.flatten(shapes)
            st_m = treedef.unflatten([
                jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s))
                for x, s in zip(leaves, flat_specs)
            ])
            rep = NamedSharding(mesh, P())
            tables_m = on_device(world.tables, rep)
            end_m = jax.ShapeDtypeStruct((), jnp.int64, sharding=rep)
            return lambda: runner._chunk_fn(shapes).lower(st_m, tables_m, end_m)
        raise AssertionError(name)

    ok = True
    for name in args.pieces:
        ok &= report(name, piece(name), args.hlo_stats, args.hlo_dump or "")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
