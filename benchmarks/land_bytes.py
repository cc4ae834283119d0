"""The bytes one landing has to move, and how many landings a unit holds,
from the configuration's document alone.

The landing (`equeue.push_many_sorted`, scope `exchange/land`) merges a
round's M staged packets into the H x Q queue slots. Whatever implements
it, it reads and writes the five queue arrays once, reads the M entries in
destination order once, and, as a pull, writes the gathered `[14, H, Q]`
words once. An entry and a slot are the same 14 32-bit words: time and tie
as two each, kind, aux and the 8 payload lanes. Memory-bound: the landing
computes next to nothing, so its roofline is these bytes over the chip's
HBM rate (`peaks.json`).

Kept with the benchmark, beside `roofline.py`, so that no later change to
the program can alter what the share is measured against.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORDS = 14  # 32-bit words of a queue slot and of a staged entry
_NS = {"ns": 1, "us": 1_000, "ms": 1_000_000, "s": 1_000_000_000}
_LATENCY = re.compile(r'latency\s+"\s*(\d+)\s*(ns|us|ms|s)\s*"')


def land_min_bytes(hosts: int, queue_capacity: int, entries: int) -> int:
    """One landing on one chip: `hosts` x `queue_capacity` slots, a batch
    of `entries` (the whole outbox: hosts x outbox_capacity)."""
    slots = hosts * queue_capacity
    queue_read_and_written = 2 * WORDS * 4 * slots
    sorted_words_read = WORDS * 4 * entries
    pulled_words_written = WORDS * 4 * slots
    return queue_read_and_written + sorted_words_read + pulled_words_written


def config_doc(cell: dict) -> dict:
    """The document of the cell's configuration, as BENCHMARK.json names it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        configs = {c["name"]: c for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        return json.load(f)


def shapes(doc: dict) -> "tuple[int, int, int]":
    """(hosts, queue slots a host, entries a landing) of a one-chip run."""
    hosts = sum(int(g.get("quantity", 1)) for g in doc["hosts"].values())
    exp = doc["experimental"]
    return hosts, int(exp["queue_capacity"]), hosts * int(exp["outbox_capacity"])


def rounds_per_unit(doc: dict, unit_sim_ms: int) -> int:
    """Landings in a unit: one a round, and a round is one lookahead (the
    graph's least edge latency) of simulated time where every window holds
    an event, as in a stationary PHOLD world; the adaptive window makes
    fewer and longer rounds only where windows stand empty, so this is
    the most a unit can hold, and the share computed from it the highest."""
    text = doc["network"]["graph"]["inline"]
    lookahead_ns = min(int(n) * _NS[u] for n, u in _LATENCY.findall(text))
    return -(-unit_sim_ms * _NS["ms"] // lookahead_ns)
