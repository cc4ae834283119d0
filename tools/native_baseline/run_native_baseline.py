"""The one serializer of the native C baseline's (tgen_pdes.c) tables
format, for tests/test_native_baseline.py: the C PDES simulates the world
whose routing tables it is handed, counter-identical to the device engine
and the Python oracle."""

from __future__ import annotations

import struct


def write_tables(path, tables) -> None:
    """int32 n_nodes, int64 lat[n*n] ns, float32 rel[n*n]."""
    import numpy as np

    lat = np.asarray(tables.lat_ns, dtype=np.int64)
    rel = np.asarray(tables.rel, dtype=np.float32)
    with open(path, "wb") as f:
        f.write(struct.pack("<i", lat.shape[0]))
        f.write(lat.tobytes())
        f.write(rel.tobytes())
