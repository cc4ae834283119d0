"""The plain reference's side of a cell: the world from the configuration's
own JSON, the reference binary, and the per-host comparison.

Nothing here imports the program (`shadow_tpu`) or JAX, and nothing the
program computed is read: graph, routing, host placement, refills, window
width and model arguments are worked out again from the configuration file
by the rules the configuration's source documents:

* GML graph, undirected unless `directed 1`; a duplicated edge keeps the
  lower latency; reliability of an edge is float32(1 - packet_loss).
* Routing: all-pairs shortest latency by min-plus squaring of the edge
  matrix with a free diagonal, ceil(log2(n-1))+... squarings
  (`(n-1).bit_length()`); a candidate replaces the current path only when
  strictly shorter, and among equal candidates the smallest intermediate
  node wins; a path's reliability is the float32 product of its two halves
  at each squaring; the diagonal is the self-loop edge.
* Hosts: the `hosts` groups in file order, `quantity` each, on the node
  their `network_node_id` names; a host's bandwidth is its node's.
* Token-bucket refill per 1 ms: (bits_per_s // 8) * 1_000_000 // 10**9,
  at least 1 where a bandwidth is set.
* Window (runahead): the smallest edge or path latency.
* tgen: the first half of the hosts are clients, the rest servers.
"""

from __future__ import annotations

import json
import os
import re
import struct
import subprocess

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TIME_MAX = (1 << 63) - 1
WORLD_MAGIC = 0x57524C44
MODELS = {"tgen": 0, "phold": 1}
# the columns pdes_ref writes, in order; the names are the program's
# `host_stats` keys, which is where the harness reads its side from
COUNTERS = ("events_handled", "packets_sent", "packets_dropped",
            "codel_dropped", "bytes_sent", "bytes_recv")

_TIME_UNITS = {"ns": 1, "us": 1_000, "ms": 1_000_000, "s": 1_000_000_000,
               "sec": 1_000_000_000}
_BW_UNITS = {"bit": 1, "kbit": 10**3, "mbit": 10**6, "gbit": 10**9}


def parse_time_ns(v) -> int:
    if isinstance(v, (int, float)):
        return int(v) * 1_000_000_000
    m = re.fullmatch(r"\s*(\d+)\s*([a-z]+)\s*", str(v).lower())
    if not m or m.group(2) not in _TIME_UNITS:
        raise ValueError(f"cannot read a time from {v!r}")
    return int(m.group(1)) * _TIME_UNITS[m.group(2)]


def parse_bits_per_s(v) -> int:
    m = re.fullmatch(r"\s*(\d+)\s*([a-z]+)\s*", str(v).lower())
    if not m or m.group(2) not in _BW_UNITS:
        raise ValueError(f"cannot read a bandwidth from {v!r}")
    return int(m.group(1)) * _BW_UNITS[m.group(2)]


def parse_gml(text: str):
    """(directed, nodes, edges) of the flat GML the configurations carry:
    `node [ key value ... ]` and `edge [ key value ... ]` records."""
    toks = re.findall(r'"[^"]*"|\[|\]|[^\s\[\]]+', text)
    directed, nodes, edges = False, [], []
    i = 0
    while i < len(toks):
        t = toks[i]
        if t == "directed":
            directed = toks[i + 1] == "1"
            i += 2
        elif t in ("node", "edge") and toks[i + 1] == "[":
            rec, i = {}, i + 2
            while toks[i] != "]":
                val = toks[i + 1]
                rec[toks[i]] = val[1:-1] if val.startswith('"') else val
                i += 2
            (nodes if t == "node" else edges).append(rec)
            i += 1
        else:
            i += 1
    return directed, nodes, edges


def routing(lat0: np.ndarray, rel0: np.ndarray):
    """All-pairs (latency, reliability) by min-plus squaring; see the
    module's docstring for the rules."""
    n = lat0.shape[0]
    lat, rel = lat0.copy(), rel0.copy()
    idx = np.arange(n)
    lat[idx, idx] = 0
    rel[idx, idx] = np.float32(1.0)
    big = lat >= TIME_MAX
    for _ in range(max(1, max(n - 1, 1).bit_length())):
        # cand[i, k, j] = lat[i, k] + lat[k, j], saturating at TIME_MAX
        a = np.where(big, TIME_MAX // 2, lat)
        cand = a[:, :, None] + a[None, :, :]
        cand = np.where(big[:, :, None] | big[None, :, :], TIME_MAX, cand)
        k = np.argmin(cand, axis=1)  # first (smallest) k among equals
        cl = np.take_along_axis(cand, k[:, None, :], axis=1)[:, 0, :]
        cr = (rel[:, :, None] * rel[None, :, :]).astype(np.float32)
        cr = np.take_along_axis(cr, k[:, None, :], axis=1)[:, 0, :]
        upd = cl < lat
        lat = np.where(upd, cl, lat)
        rel = np.where(upd, cr, rel).astype(np.float32)
        big = lat >= TIME_MAX
    lat[idx, idx] = np.diagonal(lat0)
    rel[idx, idx] = np.diagonal(rel0)
    return lat, rel


class World:
    """What the reference simulates, from the configuration alone."""

    def __init__(self, config: dict, seed: int):
        directed, nodes, edges = parse_gml(config["network"]["graph"]["inline"])
        ids = [int(nd["id"]) for nd in nodes]
        index = {nid: i for i, nid in enumerate(ids)}
        n = len(ids)
        lat0 = np.full((n, n), TIME_MAX, np.int64)
        rel0 = np.zeros((n, n), np.float32)
        for e in edges:
            s, t = index[int(e["source"])], index[int(e["target"])]
            elat = parse_time_ns(e["latency"])
            erel = np.float32(1.0 - float(e.get("packet_loss", 0.0)))
            for a, b in ([(s, t)] if directed else [(s, t), (t, s)]):
                if elat < lat0[a, b]:
                    lat0[a, b], rel0[a, b] = elat, erel
        self.lat, self.rel = routing(lat0, rel0)

        def node_bw(nd, key):
            return parse_bits_per_s(nd[key]) if key in nd else 0

        host_node, tx, rx, models, args = [], [], [], set(), []
        for spec in config["hosts"].values():
            ni = index[int(spec["network_node_id"])]
            (proc,) = spec["processes"]
            models.add(proc["path"])
            args.append(json.dumps(proc.get("args", {}), sort_keys=True))
            for _ in range(int(spec.get("quantity", 1))):
                host_node.append(ni)
                tx.append(node_bw(nodes[ni], "host_bandwidth_up"))
                rx.append(node_bw(nodes[ni], "host_bandwidth_down"))
        if len(models) != 1 or len(set(args)) != 1:
            raise ValueError("the reference runs one model with one set of arguments")
        self.model = models.pop()
        self.args = json.loads(args[0])
        self.host_node = np.asarray(host_node, np.int32)
        self.h = len(host_node)

        def refill(bps):
            bps = np.asarray(bps, np.int64)
            r = (bps // 8) * 1_000_000 // 1_000_000_000
            return np.where(bps > 0, np.maximum(r, 1), 0).astype(np.int64)

        self.use_netstack = bool(max(tx) > 0 or max(rx) > 0)
        self.tx_refill, self.rx_refill = refill(tx), refill(rx)
        finite = self.lat[self.lat < TIME_MAX]
        self.runahead_ns = int(min(lat0[lat0 < TIME_MAX].min(), finite.min()))
        self.bootstrap_end_ns = parse_time_ns(
            config["general"].get("bootstrap_end_time", "0 ns"))
        self.seed = int(seed)

    def model_args(self) -> "list[int]":
        a = self.args
        if self.model == "tgen":
            clients = self.h // 2
            return [clients, self.h - clients, int(a.get("resp_bytes", 100_000)),
                    parse_time_ns(a.get("pause", "500 ms"))]
        if self.model == "phold":
            return [parse_time_ns(a.get("min_delay", "1 ms")),
                    parse_time_ns(a.get("max_delay", "20 ms")),
                    int(a.get("ball_bytes", 0))]
        raise ValueError(f"the reference has no model {self.model!r}")

    def write(self, path: str, end_ns: int, *, lossless: bool = False) -> None:
        """The binary world file pdes_ref reads. `lossless` is the control:
        every path's reliability set to 1, which breaks the guarantee
        "loss only by the graph's seeded draws" and nothing else."""
        head = [WORLD_MAGIC, self.lat.shape[0], self.h, MODELS[self.model],
                self.seed, int(end_ns), self.runahead_ns, self.bootstrap_end_ns,
                int(self.use_netstack)] + self.model_args()
        head += [0] * (16 - len(head))
        rel = np.ones_like(self.rel) if lossless else self.rel
        with open(path, "wb") as f:
            f.write(struct.pack("<16q", *head))
            f.write(self.lat.astype("<i8").tobytes())
            f.write(rel.astype("<f4").tobytes())
            f.write(self.host_node.astype("<i4").tobytes())
            f.write(self.tx_refill.astype("<i8").tobytes())
            f.write(self.rx_refill.astype("<i8").tobytes())


def build_reference(work_dir: str) -> str:
    """Compile pdes_ref.c into `work_dir` (a directory of this run's own);
    returns the binary's path."""
    os.makedirs(work_dir, exist_ok=True)
    out = os.path.join(work_dir, "pdes_ref")
    subprocess.run(
        ["cc", "-O2", "-o", out, os.path.join(HERE, "pdes_ref.c"), "-lm"],
        check=True, capture_output=True, text=True, timeout=120,
    )
    return out


def run_reference(binary: str, world: World, end_ns: int, work_dir: str,
                  *, lossless: bool = False) -> "dict[str, np.ndarray]":
    """Per-host counters of the reference at simulated time `end_ns`."""
    tag = "control" if lossless else "ref"
    wf = os.path.join(work_dir, f"{tag}.world")
    of = os.path.join(work_dir, f"{tag}.out")
    world.write(wf, end_ns, lossless=lossless)
    subprocess.run([binary, wf, of], check=True, capture_output=True,
                   text=True, timeout=300)
    raw = np.fromfile(of, dtype="<i8")
    h = int(raw[0])
    if h != world.h or raw.size != 1 + len(COUNTERS) * h:
        raise ValueError(f"{of}: not {len(COUNTERS)} columns of {world.h} hosts")
    cols = raw[1:].reshape(len(COUNTERS), h)
    return {name: cols[i] for i, name in enumerate(COUNTERS)}


def compare(got: dict, want: dict) -> dict:
    """Every per-host counter of `got` against the reference's `want`.
    Returns the numbers `correct` rests on, each an exact count whose
    limit is 0: hosts on which any counter differs, and per counter the
    absolute difference of the totals."""
    differ = np.zeros(len(want[COUNTERS[0]]), bool)
    out = {}
    for name in COUNTERS:
        g = np.asarray(got[name], np.int64)
        w = np.asarray(want[name], np.int64)
        if g.shape != w.shape:
            raise ValueError(f"{name}: {g.shape} hosts against {w.shape}")
        differ |= g != w
        out[f"total_gap.{name}"] = int(abs(int(g.sum()) - int(w.sum())))
    return {"hosts_differing": int(differ.sum()), **out}
