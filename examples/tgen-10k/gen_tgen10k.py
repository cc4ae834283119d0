#!/usr/bin/env python3
"""Generate the tgen-10k deployment: the headline world, as a config the
front door runs (`shadow-tpu run`).

10,240 hosts on a 32-node random graph (each node links to 6 random peers
and its ring successor, 2-11 ms edges with 0.5 % loss, 2 ms self-loops),
100 Mbit hosts, tgen with half the hosts clients (the model default) fetching 100 kB responses
with a 500 ms pause, queue 384 / outbox 64 / 256 iterations per round,
500 ms of simulated time. (With 32 outbox slots this world dropped 32
packets on the chip in its first chunk — a server in
slow start emits a 39-segment flight inside one adaptive window, measured
high water 35 at 256 hosts — so the front-door deployment takes 64 rather
than lean on rollback-and-regrow, which recompiles for minutes.) Hosts are laid out by node block (320 per node,
clients on nodes 0-15, servers on 16-31).

  gen_tgen10k.py > shadow.yaml

The committed shadow.yaml is this script's output. Smaller cuts of it are
made in one place only: chip_smoke.py's cut_config sets the host
quantities and the stop time of that file for its parity and rehearsal
phases; the world itself is never regenerated there.
"""

from __future__ import annotations

import random
import sys

NODES = 32
HOSTS = 10240
STOP_TIME = "500 ms"
SEED = 7


def graph_gml(seed: int = SEED) -> str:
    rng = random.Random(seed)
    bw = 'host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit"'
    lines = ["graph [", "  directed 0"]
    for i in range(NODES):
        lines.append(f"  node [ id {i} {bw} ]")
        lines.append(f'  edge [ source {i} target {i} latency "2 ms" ]')
    for i in range(NODES):
        for j in rng.sample(range(NODES), 6) + [(i + 1) % NODES]:
            if j != i:
                lat = rng.randrange(2, 12)
                lines.append(
                    f'  edge [ source {i} target {j} latency "{lat} ms" '
                    "packet_loss 0.005 ]"
                )
    lines.append("]")
    return "\n".join(lines)


def config_yaml() -> str:
    per_node = HOSTS // NODES
    gml = "\n".join("      " + ln for ln in graph_gml().splitlines())
    groups = "\n".join(
        f"""  n{i:02d}:
    network_node_id: {i}
    quantity: {per_node}
    processes:
      - path: tgen
        args: {{ resp_bytes: 100000, pause: "500 ms" }}"""
        for i in range(NODES)
    )
    return f"""# tgen-10k: {HOSTS} hosts on a 32-node lossy random graph, half of them
# tgen clients. Generated by examples/tgen-10k/gen_tgen10k.py; edit there.
#   shadow-tpu run examples/tgen-10k/shadow.yaml
general:
  stop_time: "{STOP_TIME}"
  seed: {SEED}
  data_directory: shadow.data

network:
  graph:
    type: gml
    inline: |
{gml}

experimental:
  scheduler: tpu
  engine: auto
  queue_capacity: 384
  outbox_capacity: 64
  max_iters_per_round: 256
  rounds_per_chunk: 64

hosts:
{groups}
"""


if __name__ == "__main__":
    sys.stdout.write(config_yaml())
