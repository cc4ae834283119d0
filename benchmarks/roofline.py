"""The table of peaks and the bytes a kernel has to move, from shapes.

Kept with the benchmark so that no later change to the program can alter
what a roofline share is measured against.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json; add it with its source")
    return table[device_kind]


def tree_nbytes(tree) -> int:
    """Bytes of every array leaf of a pytree, from shape and dtype alone."""
    import jax

    return sum(int(x.size) * int(x.dtype.itemsize) for x in jax.tree_util.tree_leaves(tree))


def flush_min_bytes(outbox, queue) -> int:
    """The least traffic a round-boundary flush needs, whatever implements
    it: every outbox array read once, every queue array read once and
    written once. Memory-bound: the flush computes next to nothing."""
    return tree_nbytes(outbox) + 2 * tree_nbytes(queue)
