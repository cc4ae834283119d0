#!/usr/bin/env python3
"""Generate a k-ary fat-tree: its GML topology, or the whole front-door
document of BASELINE config 4 ("iperf-2 TCP saturation, 10k-host fat-tree
topology") that `shadow-tpu run` and the benchmark take.

A k-ary fat-tree has (k/2)^2 core switches and k pods of k switches (k/2
aggregation + k/2 edge); hosts attach to edge switches via
network_node_id. Node ids: cores 0 .. (k/2)^2-1, then pod by pod the
pod's k/2 aggregation switches followed by its k/2 edge switches. An
edge-aggregation link has latency edge + agg (10 + 20 us), an
aggregation-core link agg + core (20 + 50 us), and every edge switch a
5 us self-loop (the path between two of its hosts, and the world's
lookahead). Nothing is drawn: the output is a function of the arguments,
byte for byte.

  gen_fattree.py [k] > fattree.gml          # k even, default 8: the GML alone
  gen_fattree.py --config > benchmarks/configs/fattree-10k.json
  gen_fattree.py --config --pod-step-us 8 --edge-step-us 3 \
      > benchmarks/configs/fattree-10k-cabled.json

`--config` writes the document as JSON (which is YAML): `general`,
`network.graph.inline`, `experimental`, one host group per edge switch in
pod order (`p<pod>e<edge>`), every group running one `tgen` process with
the same arguments, and an `x-benchmark` header that names the source,
what was assumed and what was cut. tgen's own rule pairs the hosts: the
first half (the lower pods) are clients, the second half servers, client
i fetches from server i, so every flow crosses the core. What the source
leaves open is fixed here, once, as constants beside `GUARANTEES` (host
bandwidth 1 Gbit, loss 0.0005 on aggregation-core links, tgen's
`resp_bytes` 125,000,000 and `pause` 500 ms, stop time 5 ms,
`max_iters_per_round` 256), so that a regenerated document cannot drift
from its `source`. Options, with the values
`benchmarks/configs/fattree-10k.json` was made with as defaults:

  --k 16                  the fat-tree's arity (64 core, 128 agg, 128 edge)
  --hosts-per-edge 80     hosts on each edge switch (10,240 in all)
  --queue-capacity 512 --outbox-capacity 256
  --rounds-per-chunk 32   experimental.*. Capacities: twice the high-water
                          marks of the 10,240-host world to 5 ms (queue 129,
                          outbox 127: a refilled bucket's 83 packets leave
                          in ONE 5 us round), as powers of two; a smaller
                          world takes smaller ones. A chunk of 32 rounds
                          covers 4-8 ms of this world: the adaptive window
                          skips simulated time in which no host has an
                          event, and the flows move in step
  --pod-step-us 0 --edge-step-us 0
                          cables of unequal length (PR 34). With steps P / E
                          the aggregation-core links of pod p take
                          70 + P p us and the edge-aggregation links of edge
                          switch e of every pod 30 + E e us (pods at unequal
                          distance from the core row, racks from their
                          pod's aggregation row); the self-loop, and so the
                          lookahead, stays 5 us. `fattree-10k-cabled` is
                          8 / 3: client pod p, edge e reaches its server
                          over 264 + 16 p + 6 e us, 64 distinct paths of
                          264-418 us, so the 5,120 flows fall out of step
                          and the adaptive window finds an event every few
                          microseconds (~120 live rounds a millisecond where
                          the lock-step world has 5). With a step the
                          defaults of two options change, to what that world
                          needs: --outbox-capacity 512 (marks 162 / 160 by
                          5 ms: two refills' bursts meet at one host) and
                          --rounds-per-chunk 128 (one chunk a millisecond)

The GML alone takes the host bandwidth of the older examples (10 Gbit)
and no loss, as `examples/fattree/shadow.yaml` expects.
"""

from __future__ import annotations

import argparse
import json
import sys

GUARANTEES = [
    "bit-identical per-host results for a seed whatever the chunking or device count",
    "conservative windows: no event handled before the window that contains it",
    "no event or packet lost to a capacity overflow (overflow == 0)",
    "loss only by the graph's seeded draws",
]
# what BASELINE config 4 leaves open, as the document's `assumed` lists it
HOST_BW_BITS = 1_000_000_000  # upstream Shadow's 1_gbit_switch, up and down
CORE_LOSS = 0.0005  # each aggregation-core link; a cross-pod path: 0.001
RESP_BYTES = 125_000_000  # 1 s at line rate: no response ends inside a run
PAUSE = "500 ms"
STOP_TIME = "5 ms"
MAX_ITERS_PER_ROUND = 256
# queue / outbox high-water marks of the k=16 x 80 world with steps 8 / 3 to
# 5 ms, the same on three seeds (PERF.md section 4): what its capacities
# are twice of, as powers of two
CABLED_MARKS = (162, 160)


def fattree_ids(k: int) -> dict:
    """{switch name: node id} in the order the GML lists the nodes."""
    assert k % 2 == 0
    half = k // 2
    names = [f"core{c}" for c in range(half * half)]
    for p in range(k):
        names += [f"agg{p}.{a}" for a in range(half)]
        names += [f"edge{p}.{e}" for e in range(half)]
    return {name: i for i, name in enumerate(names)}


def fattree_gml(k: int, core_latency_us=50, agg_latency_us=20, edge_latency_us=10,
                host_bw_bits=10_000_000_000, core_loss=0.0,
                pod_step_us=0, edge_step_us=0) -> str:
    """The GML. With steps, cables of unequal length: every
    edge-aggregation link of edge switch e of a pod is `edge_step_us * e`
    longer, every aggregation-core link of pod p `pod_step_us * p`."""
    half = k // 2
    ids = fattree_ids(k)
    lines = ["graph [", "  directed 0"]
    for name, i in ids.items():
        # hosts attach to the edge switches, which carry the host bandwidth
        extra = (
            f' host_bandwidth_up "{host_bw_bits} bit" host_bandwidth_down "{host_bw_bits} bit"'
            if name.startswith("edge") and host_bw_bits
            else ""
        )
        lines.append(f"  node [ id {i}{extra} ]")

    def edge(a, b, lat_us, loss=0.0):
        extra = f" packet_loss {loss}" if loss else ""
        lines.append(
            f'  edge [ source {ids[a]} target {ids[b]} latency "{lat_us} us"{extra} ]'
        )

    # self-loops so same-node host pairs have a path
    for p in range(k):
        for e in range(half):
            edge(f"edge{p}.{e}", f"edge{p}.{e}", 5)
    # edge <-> agg within a pod (full bipartite)
    for p in range(k):
        for e in range(half):
            for a in range(half):
                edge(f"edge{p}.{e}", f"agg{p}.{a}",
                     edge_latency_us + agg_latency_us + edge_step_us * e)
    # agg <-> core: agg a connects to cores [a*half, (a+1)*half)
    for p in range(k):
        for a in range(half):
            for c in range(a * half, (a + 1) * half):
                edge(f"agg{p}.{a}", f"core{c}",
                     agg_latency_us + core_latency_us + pod_step_us * p, core_loss)
    lines.append("]")
    return "\n".join(lines)


def _path_us(k: int, pod_step_us: int, edge_step_us: int) -> "list[int]":
    """One-way latency of every client's path to its server, by tgen's rule
    (pod p, edge e fetches from pod p + k/2, edge e), one entry an edge
    switch of the client pods."""
    half = k // 2
    return [2 * (30 + edge_step_us * e) + (70 + pod_step_us * p) + (70 + pod_step_us * (p + half))
            for p in range(half) for e in range(half)]


def fattree_config(k=16, hosts_per_edge=80, queue_capacity=512, outbox_capacity=None,
                   rounds_per_chunk=None, pod_step_us=0, edge_step_us=0) -> dict:
    """The front door's document for tgen saturation on a k-ary fat-tree.
    Without steps every cable of a tier has one length (`fattree-10k`:
    outbox 256, 32 rounds a chunk unless given); with steps they are
    unequal (`fattree-10k-cabled`: outbox 512, 128 rounds a chunk)."""
    half = k // 2
    ids = fattree_ids(k)
    cabled = bool(pod_step_us or edge_step_us)
    if outbox_capacity is None:
        outbox_capacity = 512 if cabled else 256
    if rounds_per_chunk is None:
        rounds_per_chunk = 128 if cabled else 32
    if cabled:
        paths = _path_us(k, pod_step_us, edge_step_us)
        options = f" --pod-step-us {pod_step_us} --edge-step-us {edge_step_us}"
        notes = {
            "link_latency_us": f"edge-aggregation 30 + {edge_step_us} e for edge switch e = 0..{half - 1} "
                               f"of every pod (racks at unequal distance from their pod's aggregation "
                               f"row), aggregation-core 70 + {pod_step_us} p for pod p = 0..{k - 1} (pods "
                               f"at unequal distance from the core row), edge self-loop 5 (the "
                               f"lookahead, as fattree-10k's); one-way client-to-server paths "
                               f"{min(paths)}-{max(paths)} us, {len(set(paths))} distinct "
                               f"({paths[0]} + {2 * pod_step_us} p + {2 * edge_step_us} e for client pod p, "
                               f"edge switch e), so the flows do not move in step",
            "queue_capacity": f"{queue_capacity} (the default is twice the high-water mark of the "
                              f"k=16 x 80 world with steps 8 / 3 to 5 ms, {CABLED_MARKS[0]} on three "
                              f"seeds, as a power of two)",
            "outbox_capacity": f"{outbox_capacity} (likewise: mark {CABLED_MARKS[1]}; two refills' "
                               f"bursts meet at one host now that round-trip times differ, so 256 no "
                               f"longer holds twice the mark)",
            "rounds_per_chunk": f"{rounds_per_chunk} (chunking is trajectory-neutral; ~120 live rounds "
                                f"a simulated millisecond in slow start, so one chunk a millisecond)",
        }
    else:
        options = ""
        notes = {
            "link_latency_us": "edge-aggregation 30, aggregation-core 70, edge self-loop 5 "
                               "(gen_fattree.py's defaults); cross-pod RTT 400 us",
            "queue_capacity": f"{queue_capacity} (the default is twice the high-water mark of the "
                              "k=16 x 80 world to 5 ms, 129 on three seeds, as a power of two)",
            "outbox_capacity": f"{outbox_capacity} (likewise: mark 127, a refilled bucket's 83 "
                               "packets and what was staged before them, in one round)",
            "rounds_per_chunk": f"{rounds_per_chunk} (chunking is trajectory-neutral)",
        }
    groups = {
        f"p{p:02d}e{e}": {
            "network_node_id": ids[f"edge{p}.{e}"],
            "quantity": hosts_per_edge,
            "processes": [{"path": "tgen", "args": {"resp_bytes": RESP_BYTES, "pause": PAUSE}}],
        }
        for p in range(k) for e in range(half)
    }
    return {
        "x-benchmark": {
            "source": "BASELINE.json config 4 'iperf-2 TCP saturation, 10k-host fat-tree topology'; "
                      f"world as examples/fattree/gen_fattree.py --config{options} emits it",
            "assumed": {
                "link_latency_us": notes["link_latency_us"],
                "hosts_per_edge_switch": hosts_per_edge,
                "host_bandwidth": f"{HOST_BW_BITS} bit up and down (upstream Shadow's 1_gbit_switch)",
                "packet_loss": f"{CORE_LOSS} on each aggregation-core link, 0 elsewhere "
                               "(keeps the loss draws, and so the control, alive)",
                "resp_bytes": f"{RESP_BYTES} (1 s at line rate: no response ends inside a run, "
                              "as iperf's one long flow)",
                "pause": PAUSE,
                "pairs": "tgen's rule: the first half of the hosts (the lower pods) clients, the "
                         "second half servers, client i to server i: every flow crosses the core",
                "queue_capacity": notes["queue_capacity"],
                "outbox_capacity": notes["outbox_capacity"],
                "rounds_per_chunk": notes["rounds_per_chunk"],
            },
            "reduced": [] if k == 34 else ["network.graph"],
            "reduced_why": f"a k-ary fat-tree of 10k hosts is k=34 (1,445 switches, 17 hosts a "
                           f"switch); the reference's routing builds an [n, n, n] int64 tensor, "
                           f"24 GB at n=1,445, so the graph is k={k} ({5 * half * half} switches) "
                           f"with {hosts_per_edge} hosts a switch. Shadow's graph carries latency "
                           f"and loss only, so hosts per switch changes no host's traffic.",
            "guarantees": GUARANTEES,
        },
        "general": {"stop_time": STOP_TIME, "seed": 7},
        "network": {"graph": {"type": "gml", "inline": fattree_gml(
            k, host_bw_bits=HOST_BW_BITS, core_loss=CORE_LOSS,
            pod_step_us=pod_step_us, edge_step_us=edge_step_us)}},
        "experimental": {
            "scheduler": "tpu",
            "engine": "auto",
            "queue_capacity": queue_capacity,
            "outbox_capacity": outbox_capacity,
            "max_iters_per_round": MAX_ITERS_PER_ROUND,
            "rounds_per_chunk": rounds_per_chunk,
        },
        "hosts": groups,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("k_gml", nargs="?", type=int, default=None,
                    help="without --config: the arity of the GML to print (default 8)")
    ap.add_argument("--config", action="store_true")
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--hosts-per-edge", type=int, default=80)
    ap.add_argument("--queue-capacity", type=int, default=512)
    ap.add_argument("--outbox-capacity", type=int, default=None)
    ap.add_argument("--rounds-per-chunk", type=int, default=None)
    ap.add_argument("--pod-step-us", type=int, default=0)
    ap.add_argument("--edge-step-us", type=int, default=0)
    args = ap.parse_args(argv)
    if not args.config:
        print(fattree_gml(args.k_gml or 8))
        return 0
    options = {k: v for k, v in vars(args).items() if k not in ("config", "k_gml")}
    doc = fattree_config(**options)
    json.dump(doc, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
