#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the front door (`python -m shadow_tpu.cli run`) on a deployment's
own document and checks what comes out by the repo's own means.
`--deployment tgen-10k` (the default; examples/tgen-10k/shadow.yaml:
10,240 hosts, 32-node lossy graph, 100 Mbit hosts, half the hosts tgen
clients, 500 ms) or `fattree-10k` (benchmarks/configs/fattree-10k.json:
10,240 hosts on a k=16 fat-tree, 5 us lookahead, 5,120 saturating TCP
flows at 1 Gbit, 5 ms) or `phold-512k` (benchmarks/configs/phold-512k.json:
524,288 PHOLD hosts, the world that fills a chip, 50 ms = 25 rounds) or
`fattree-10k-cabled` (benchmarks/configs/fattree-10k-cabled.json: the
fat-tree with cables of unequal length, flows out of step, 5 ms). The
full-size phases run the document's own host count and stop time. This
process never imports JAX: one process at a time owns the chip, so every
run is a child that exits before the next starts, and the device facts
come back through the `device` block the run writes into sim-stats.json.

Phases, each failing the script on its own:

  parity  the same document cut to 256 hosts and a shorter stop time
          (100 ms; the fat-trees' 3 ms), once on the device
          and once on the independent scalar oracle
          (experimental.scheduler: cpu-ref, that child alone is given
          JAX_PLATFORMS=cpu): every per-host counter equal.
  cold    the full-size run: exit 0, on a TPU, the engine `auto` means
          there, no `degraded` and no `recovery` block, events handled.
  warm    the same command in a new process: counters identical leaf for
          leaf, and no entry added to the compile cache that `cold` filled.

`--chips 4` runs instead exactly two children, the full-size YAML with
general.parallelism 4 and 1, and requires equal counters and four
distinct device ids. `--rehearse` runs everything small on whatever
platform JAX has (the sandbox, the tests); without it any platform other
than `tpu` fails within seconds.

The last line of stdout is one JSON object,
{"ok": ..., "device": {"platform", "kind", "count"}}; sizes, walls, engine
and cache facts go on the lines before it. Exit code 0 only when ok.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# deployment -> (its document, parity stop time, rehearsal hosts, rehearsal stop time)
DEPLOYMENTS = {
    "tgen-10k": (os.path.join("examples", "tgen-10k", "shadow.yaml"), "100 ms", 64, "50 ms"),
    "fattree-10k": (os.path.join("benchmarks", "configs", "fattree-10k.json"), "3 ms", 128, "3 ms"),
    "phold-512k": (os.path.join("benchmarks", "configs", "phold-512k.json"), "100 ms", 64, "50 ms"),
    "fattree-10k-cabled": (os.path.join("benchmarks", "configs", "fattree-10k-cabled.json"),
                           "3 ms", 128, "3 ms"),
}
# what `engine: auto` means for this config (pump_k unset) on every
# backend — engine/round.py effective_engine
AUTO_ENGINE = "plain"
COUNTERS = ("events_handled", "packets_sent", "packets_dropped")
CHILD_TIMEOUT_S = 1000

_DEVICE_PROBE = (
    "import json, jax; d = jax.devices()[0]; "
    "print(json.dumps({'platform': d.platform, 'kind': d.device_kind, "
    "'count': len(jax.devices())}))"
)


class Failed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def probe_device() -> dict:
    """What JAX finds, asked of a child that exits before any run starts."""
    r = subprocess.run(
        [sys.executable, "-c", _DEVICE_PROBE],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    if r.returncode != 0:
        raise Failed(f"JAX found no device: {r.stderr.strip()[-500:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def document(deployment: str) -> str:
    return os.path.join(ROOT, DEPLOYMENTS[deployment][0])


def cut_config(deployment: str, out_dir: str, name: str, hosts: "int | None",
               stop_time: "str | None", parallelism: int = 1, scheduler: str = "tpu") -> str:
    """The deployment's document with its host groups cut evenly to
    `hosts` and its stop time set (None: the document's own), device count
    and scheduler set; returns the path written. The world itself is never
    rebuilt here."""
    import yaml

    with open(document(deployment)) as f:
        cfg = yaml.safe_load(f)  # JSON is YAML
    if hosts is not None:
        per, rem = divmod(hosts, len(cfg["hosts"]))
        if rem or per < 1:
            raise Failed(f"{hosts} hosts do not divide over {len(cfg['hosts'])} groups")
        for spec in cfg["hosts"].values():
            spec["quantity"] = per
    if stop_time is not None:
        cfg["general"]["stop_time"] = stop_time
    cfg["general"]["data_directory"] = os.path.join(out_dir, name + ".data")
    cfg["general"]["parallelism"] = parallelism
    cfg["experimental"]["scheduler"] = scheduler
    path = os.path.join(out_dir, name + ".yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return path


def run_child(out_dir: str, name: str, config: str, env=None) -> dict:
    """One `shadow-tpu run` in its own process; returns its sim-stats."""
    data_dir = os.path.join(out_dir, name + ".data")
    shutil.rmtree(data_dir, ignore_errors=True)
    log = os.path.join(out_dir, name + ".log")
    cmd = [
        sys.executable, "-m", "shadow_tpu.cli", "run", config, "--no-recover",
        "--trace-file", os.path.join(out_dir, name + ".trace.json"),
    ]
    t0 = time.monotonic()
    with open(log, "w") as f:
        try:
            rc = subprocess.run(
                cmd, cwd=ROOT, env=env, stdout=f, stderr=subprocess.STDOUT,
                timeout=CHILD_TIMEOUT_S,
            ).returncode
        except subprocess.TimeoutExpired:
            rc = f"timeout after {CHILD_TIMEOUT_S}s"
    wall = time.monotonic() - t0
    if rc != 0:
        with open(log) as f:
            tail = f.read()[-3000:]
        raise Failed(f"{name}: `{' '.join(cmd[1:])}` ended with {rc} after {wall:.0f}s\n{tail}")
    with open(os.path.join(data_dir, "sim-stats.json")) as f:
        stats = json.load(f)
    stats["_process_wall_s"] = round(wall, 1)
    return stats


def compile_wall(stats: dict) -> float:
    return stats.get("tracker", {}).get("phases", {}).get("compile+launch", {}).get("total_s", -1.0)


def check_device_run(name: str, stats: dict, device: dict, rehearse: bool) -> None:
    dev = stats.get("device")
    if not dev:
        raise Failed(f"{name}: sim-stats.json has no `device` block")
    if dev["platform"] != device["platform"]:
        raise Failed(f"{name}: ran on {dev['platform']}, probe saw {device['platform']}")
    if dev["platform"] != "tpu" and not rehearse:
        raise Failed(f"{name}: ran on {dev['platform']}, not on a TPU")
    if dev["engine"] != AUTO_ENGINE:
        raise Failed(f"{name}: engine {dev['engine']!r} ran, `auto` means {AUTO_ENGINE!r}")
    for block in ("degraded", "recovery"):
        if block in stats:
            raise Failed(f"{name}: run has a `{block}` block: {json.dumps(stats[block])[:500]}")
    if stats["events_handled"] <= 0:
        raise Failed(f"{name}: no event handled")
    say(f"{name}: {stats['num_hosts']} hosts, {stats['sim_seconds']} sim-s, engine "
        f"{dev['engine']} on {dev['platform']} ({dev['kind']}), device ids {dev['ids']}")
    say(f"{name}: compile wall (compile+launch span) {compile_wall(stats):.1f} s")
    say(f"{name}: run wall {stats['wall_seconds']:.1f} s, process wall "
        f"{stats['_process_wall_s']} s, {stats['events_handled']} events handled, "
        f"{stats['packets_sent']} packets sent, {stats['packets_dropped']} dropped")


def same_counters(a_name: str, a: dict, b_name: str, b: dict) -> None:
    """Totals and every per-host counter both runs published, leaf for leaf."""
    for k in COUNTERS:
        if a[k] != b[k]:
            raise Failed(f"{k}: {a_name} {a[k]} != {b_name} {b[k]}")
    pa, pb = a["per_host"], b["per_host"]
    for k in sorted(set(pa) & set(pb)):
        if pa[k] != pb[k]:
            bad = [i for i, (x, y) in enumerate(zip(pa[k], pb[k])) if x != y]
            raise Failed(f"per-host {k} differs between {a_name} and {b_name} "
                         f"on {len(bad)} hosts, first host {bad[0]}")
    say(f"{a_name} == {b_name}: totals and per-host "
        f"{sorted(set(pa) & set(pb))} equal on {len(pa['events_handled'])} hosts")


def cache_entries() -> "tuple[str, set[str]]":
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    try:
        return path, set(os.listdir(path))
    except FileNotFoundError:
        return path, set()


def sizes(deployment: str, rehearse: bool):
    """(parity hosts, full hosts, parity stop, full stop); None = the document's own."""
    _doc, parity_stop, rehearse_hosts, rehearse_stop = DEPLOYMENTS[deployment]
    if rehearse:
        return rehearse_hosts, rehearse_hosts, rehearse_stop, rehearse_stop
    return 256, None, parity_stop, None


def one_chip(out_dir: str, device: dict, rehearse: bool, deployment: str) -> None:
    small, full, small_stop, full_stop = sizes(deployment, rehearse)
    cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")

    # (i) parity at a small size against the scalar oracle
    dev_cfg = cut_config(deployment, out_dir, "parity-device", small, small_stop)
    ref_cfg = cut_config(deployment, out_dir, "parity-oracle", small, small_stop, scheduler="cpu-ref")
    on_dev = run_child(out_dir, "parity-device", dev_cfg)
    check_device_run("parity-device", on_dev, device, rehearse)
    oracle = run_child(out_dir, "parity-oracle", ref_cfg, env=cpu_env)
    say(f"parity-oracle: cpu-ref, {oracle['events_handled']} events in "
        f"{oracle['wall_seconds']:.1f} s")
    same_counters("parity-device", on_dev, "parity-oracle", oracle)

    # (ii) cold run at full size, (iii) the same command again
    cfg = cut_config(deployment, out_dir, "full", full, full_stop)
    cache_dir, before = cache_entries()
    cold = run_child(out_dir, "full", cfg)
    check_device_run("cold", cold, device, rehearse)
    _, after_cold = cache_entries()
    warm = run_child(out_dir, "full", cfg)
    check_device_run("warm", warm, device, rehearse)
    _, after_warm = cache_entries()
    same_counters("cold", cold, "warm", warm)
    added = after_warm - after_cold
    say(f"compile cache {cache_dir}: {len(before)} entries before, "
        f"{len(after_cold)} after cold, {len(added)} added by warm; compile wall "
        f"cold {compile_wall(cold):.1f} s, warm {compile_wall(warm):.1f} s "
        f"({'shorter' if compile_wall(warm) < compile_wall(cold) else 'not shorter'})")
    if rehearse:
        say("compile cache: reported, not required in a rehearsal")
    elif not after_cold:
        raise Failed(f"compile cache {cache_dir} is empty after the cold run")
    elif added:
        raise Failed(f"warm run added {len(added)} compile-cache entries: {sorted(added)[:5]}")


def four_chips(out_dir: str, device: dict, rehearse: bool, deployment: str) -> None:
    _small, hosts, _small_stop, stop = sizes(deployment, rehearse)
    runs = {}
    for n in (4, 1):
        name = f"chips{n}"
        cfg = cut_config(deployment, out_dir, name, hosts, stop, parallelism=n)
        runs[n] = run_child(out_dir, name, cfg)
        check_device_run(name, runs[n], device, rehearse)
    ids = runs[4]["device"]["ids"]
    if len(set(ids)) != 4:
        raise Failed(f"parallelism 4 ran on device ids {ids}, not on four distinct ones")
    same_counters("chips4", runs[4], "chips1", runs[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="small size on whatever platform JAX has")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--deployment", choices=sorted(DEPLOYMENTS), default="tgen-10k")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for configs, logs and run data")
    args = ap.parse_args(argv)

    config = document(args.deployment)
    device, ok = None, False
    try:
        if not os.path.exists(config) or not os.path.isdir(os.path.join(ROOT, "shadow_tpu")):
            raise Failed(f"{config} or the shadow_tpu package is missing beside this script")
        device = probe_device()
        say(f"device: {json.dumps(device)}")
        if device["platform"] != "tpu" and not args.rehearse:
            raise Failed(f"JAX found no accelerator (platform {device['platform']!r}); "
                         "--rehearse runs small on the CPU")
        if device["count"] < args.chips:
            raise Failed(f"--chips {args.chips} needs {args.chips} devices, JAX sees {device['count']}")
        out_dir = os.path.abspath(args.out)
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        (four_chips if args.chips == 4 else one_chip)(
            out_dir, device, args.rehearse, args.deployment)
        ok = True
    except Failed as e:
        say(f"FAILED: {e}")
    except Exception as e:  # noqa: BLE001 — the last line must still be the result
        say(f"FAILED: {type(e).__name__}: {e}")
    if ok:
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": False, **({"device": device} if device else {})}), flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
