"""Managed-tier benchmark: N-pair C HTTP client/server matrix under the
hybrid scheduler (guests on sharded CPU kernel workers, packets on the
device engine). The managed-scale counterpart of the scripted tgen
metric (round-2 verdict item 1).

  python tools/bench_hybrid.py [pairs] [workers] [fetches] [nbytes]

Prints one JSON line: guests, syscalls, wall_s, sim-s/wall-s, fetches.
On this image wall-clock parallel speedup is bounded by the single CPU
core — the workers exist for correctness + scaling shape; run on a
multi-core host for the real curve.
"""

import json
import pathlib
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

NS_PER_MS = 1_000_000
NS_PER_SEC = 1_000_000_000


def main():
    pairs = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    workers = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    fetches = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    nbytes = int(sys.argv[4]) if len(sys.argv) > 4 else 20_000

    from shadow_tpu.engine import EngineConfig
    from shadow_tpu.graph import NetworkGraph, compute_routing
    from shadow_tpu.hostk.kernel import ProcessSpec
    from shadow_tpu.runtime.hybrid import ParallelHybridScheduler

    src = pathlib.Path(__file__).resolve().parent.parent / "examples" / "http-matrix"
    build = pathlib.Path(tempfile.mkdtemp(prefix="httpm-"))
    bins = {}
    for name in ("http_server", "http_client"):
        dst = build / name
        subprocess.run(["cc", "-O2", "-o", str(dst), str(src / f"{name}.c")], check=True)
        bins[name] = str(dst)

    # two-site topology, 10 ms apart, 1 ms self-latency (the round window)
    graph = NetworkGraph.from_gml(
        """graph [
  directed 0
  node [ id 0 ]
  node [ id 1 ]
  edge [ source 0 target 0 latency "1 ms" ]
  edge [ source 1 target 1 latency "1 ms" ]
  edge [ source 0 target 1 latency "10 ms" ]
]"""
    )
    host_names = [f"server{i}" for i in range(pairs)] + [f"client{i}" for i in range(pairs)]
    host_nodes = [0] * pairs + [1] * pairs
    tables = compute_routing(graph).with_hosts(host_nodes)
    W = graph.min_latency_ns()
    cfg = EngineConfig(
        num_hosts=2 * pairs,
        queue_capacity=256,
        outbox_capacity=64,
        runahead_ns=W,
        seed=7,
    )
    specs = []
    for i in range(pairs):
        specs.append(
            ProcessSpec(
                host=f"server{i}",
                args=[bins["http_server"], "8080", str(nbytes), str(fetches)],
            )
        )
        specs.append(
            ProcessSpec(
                host=f"client{i}",
                args=[bins["http_client"], f"server{i}", "8080", str(fetches)],
                start_ns=(50 + (i % 200)) * NS_PER_MS,  # staggered start
            )
        )

    sched = ParallelHybridScheduler(
        tables,
        cfg,
        host_names=host_names,
        host_nodes=host_nodes,
        specs=specs,
        num_workers=workers,
        seed=7,
        data_dir=build / "data",
    )
    sim_sec = 30
    t0 = time.perf_counter()
    try:
        try:
            sched.run(sim_sec * NS_PER_SEC)
        finally:
            sched.shutdown()
        wall = time.perf_counter() - t0
        stats = sched.stats()
        info = sched.proc_info()
    finally:
        sched.close()

    ok = sum(
        1
        for p in info
        if p["host"].startswith("client") and f"fetched {fetches}/{fetches}".encode() in p["stdout"]
    )
    print(
        json.dumps(
            {
                "metric": f"hybrid_http_{2*pairs}guests_syscalls_per_wall_sec",
                "guests": 2 * pairs,
                "workers": workers,
                "clients_ok": ok,
                "clients": pairs,
                "syscalls": stats["syscalls_handled"],
                "packets": stats["packets_sent"],
                "device_passes": sched.device_passes,
                "phase_wall": {k: round(v, 3) for k, v in getattr(sched, "phase_wall", {}).items()},
                "wall_s": round(wall, 2),
                "syscalls_per_s": int(stats["syscalls_handled"] / wall),
                "sim_s_per_wall_s": round(sim_sec / wall, 4),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
