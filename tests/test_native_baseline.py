"""The native C baseline (tools/native_baseline/tgen_pdes.c) must compute
the *same simulation* as the Python scalar oracle (cpu_ref/tgen_ref.py) —
same threefry draws, same TCP/shaping integer arithmetic, same window
loop — so a native rate measured with it is provably of identical
semantics at native speed, not of a lighter workload."""

import json
import pathlib
import subprocess

import pytest

from shadow_tpu.cpu_ref.tgen_ref import CpuRefTgen
from shadow_tpu.netstack import bw_bits_per_sec_to_refill
from shadow_tpu.simtime import NS_PER_MS, NS_PER_SEC

from tests.test_cpu_ref_tgen import _world

REPO = pathlib.Path(__file__).resolve().parent.parent
NB = REPO / "tools" / "native_baseline"


@pytest.fixture(scope="module")
def nb_bin(tmp_path_factory):
    out = tmp_path_factory.mktemp("nb") / "tgen_pdes"
    subprocess.run(
        ["cc", "-O2", "-o", str(out), str(NB / "tgen_pdes.c"), "-lm"], check=True
    )
    return out


def _run_c(nb_bin, tmp_path, tables, num_hosts, end_ns, seed, resp, pause,
           runahead, refill):
    import sys

    sys.path.insert(0, str(NB))
    from run_native_baseline import write_tables

    tp = tmp_path / "tables.bin"
    write_tables(tp, tables)
    r = subprocess.run(
        [str(nb_bin), str(tp), str(num_hosts), str(end_ns), str(seed),
         str(resp), str(pause), str(runahead), str(refill), str(refill)],
        check=True, capture_output=True, text=True,
    )
    return json.loads(r.stdout)


def test_native_baseline_matches_python_oracle(nb_bin, tmp_path):
    """Counter-for-counter identity with CpuRefTgen on the lossy+shaped
    configuration (loss draws, CoDel, token buckets, retransmits all in
    play)."""
    cfg, model, tables, host_node, bw = _world(8, 0.02, True, seed=13)
    end = 400 * NS_PER_MS

    ref = CpuRefTgen(cfg, model, tables, host_node,
                     tx_bytes_per_interval=bw, rx_bytes_per_interval=bw)
    ref.bootstrap()
    ref.run_until(end)

    c = _run_c(nb_bin, tmp_path, tables, 8, end, cfg.seed,
               model.resp_bytes, model.pause_ns, cfg.runahead_ns, bw)

    assert c["events"] == sum(ref.events_handled)
    assert c["packets_sent"] == sum(ref.packets_sent)
    assert c["packets_dropped"] == sum(ref.packets_dropped)
    assert c["codel_dropped"] == sum(ref.codel_dropped)
    assert c["streams_started"] == sum(ref.streams_started)
    assert c["streams_done"] == sum(ref.streams_done)
    assert c["bytes_down"] == sum(ref.bytes_down)
    assert c["resets"] == sum(ref.resets)
    assert c["bytes_sent"] == sum(ref.bytes_sent)
    assert c["bytes_recv"] == sum(ref.bytes_recv)
    assert c["retransmits"] == sum(
        s.retransmits for row in ref.slots for s in row
    )


def test_native_baseline_bench_topology_smoke(nb_bin, tmp_path):
    """The benchmark's world (examples/tgen-10k: 32-node lossy graph,
    100 Mbit shaping), built through the front door and cut to 64 hosts,
    completes and reports a plausible native rate."""
    import sys

    sys.path.insert(0, str(REPO / "tools"))
    from compile_for_chip import build_world

    _config, world = build_world(str(REPO / "examples/tgen-10k/shadow.yaml"), 64)
    cfg, model = world.ecfg, world.model
    c = _run_c(nb_bin, tmp_path, world.tables, 64, int(0.1 * NS_PER_SEC),
               cfg.seed, model.resp_bytes, model.pause_ns, cfg.runahead_ns,
               bw_bits_per_sec_to_refill(100_000_000))
    assert c["streams_done"] == 32  # one stream per client in 100 ms
    assert c["bytes_down"] == 32 * model.resp_bytes
    assert c["rate"] > 1.0
