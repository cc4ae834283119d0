#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size: the plain reference with
one stated guarantee broken (no loss draws: every path's reliability 1),
put in the program's place and compared with the reference as a run's
per-host counters are. It has to come out as not correct on every seed.

    python3 benchmarks/control.py --workload <cell> --seeds 1 2 3

No run of the benchmark calls this; it needs no chip and imports neither
JAX nor the program. One line per seed, the numbers beside their limit 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from reference import world as refworld  # noqa: E402
from run import ROOT, find_cell, load_json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _cell, config, params = find_cell(bench, args.workload)
    raw = load_json(os.path.join(ROOT, config["file"]))
    end_ns = (int(params["warm_sim_ms"]) + int(params["unit_sim_ms"])) * 1_000_000
    failed_to_fail = 0
    with tempfile.TemporaryDirectory(prefix="bench-control-") as work:
        binary = refworld.build_reference(work)
        for seed in args.seeds:
            world = refworld.World(raw, seed)
            want = refworld.run_reference(binary, world, end_ns, work)
            control = refworld.run_reference(binary, world, end_ns, work, lossless=True)
            numbers = refworld.compare(control, want)
            correct = all(v == 0 for v in numbers.values())
            failed_to_fail += correct
            print(json.dumps({"workload": args.workload, "seed": seed, "hosts": world.h,
                              "control_correct": correct, "limit": 0, "numbers": numbers}),
                  flush=True)
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
