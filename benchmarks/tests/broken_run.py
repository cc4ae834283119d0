"""Test helper: run benchmarks/run.py with the timed path broken underneath.

    python broken_run.py <fault> <run.py's arguments...>

`state_unchanged`: from the third call on (the timed units), the scheduler
still runs, so the driver's probes are the real ones, but hands back the
state it was given. `answer_altered`: one host's `packets_sent` is one too
many in every state the scheduler returns. Either way the rest of the run,
the comparison with the reference included, goes on as in any run, and
`correct` has to come out false.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

fault = sys.argv[1]

import shadow_tpu  # noqa: E402,F401
from shadow_tpu.runtime.scheduler import TpuScheduler  # noqa: E402

real_run = TpuScheduler.run
calls = [0]


def broken(self, end_time_ns, *a, start_state=None, **kw):
    calls[0] += 1
    out = real_run(self, end_time_ns, *a, start_state=start_state, **kw)
    if fault == "state_unchanged" and calls[0] >= 3:
        return start_state
    if fault == "answer_altered":
        return out.replace(packets_sent=out.packets_sent.at[3].add(1))
    return out


if fault not in ("state_unchanged", "answer_altered", "none"):
    raise SystemExit(f"unknown fault {fault!r}")
TpuScheduler.run = broken
sys.argv = [os.path.join(ROOT, "benchmarks", "run.py")] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
