"""Test harness: run everything on a virtual 8-device CPU mesh.

Force the CPU platform with 8 virtual devices (the multi-chip sharding
tests) before jax's backends initialize, then import shadow_tpu (which
enables x64). Nothing here touches the TPU: the compile-for-the-chip tests
describe their topology inside their own module-scoped fixture
(tests/test_chip_compile.py).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# JAX's persistent compilation cache stays off under pytest, for this
# process and (through the environment) every child a test starts: an
# executable the CPU backend serialized may be refused when read back
# (XLA's AOT loader checks target-machine features), and tests never write
# a cache into the checkout. runtime/compile_cache.py
# place_persistent_cache only decides the directory; it never turns the
# cache back on.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

import pytest  # noqa: E402

import shadow_tpu  # noqa: E402,F401  (enables x64)

# ---- quick/full suite tiers --------------------------------------------
# The always-green quick tier is `pytest -m "not slow"` (~5 min on the
# 1-core CI box); the full suite (~24 min) runs everything. Tests whose
# measured wall time exceeds ~8 s are marked slow by base name, so new
# parametrizations of a slow test inherit the marker. Re-derive the list
# with `pytest --durations=0` when timings drift.
SLOW_TESTS = {
    "test_client_reaches_closed_after_timewait",
    "test_config_bandwidth_reaches_engine",
    "test_determinism_two_runs_identical",
    "test_device_tcp_matches_scalar_oracle",
    "test_ensemble_matches_single_tgen",
    "test_ensemble_checkpoint_resume_exact",
    "test_ensemble_checkpoint_straddling_quiescence_exact",
    "test_ensemble_recovery_regrows_whole_batch",
    "test_ensemble_pipelined_matches_sync",
    "test_device_tgen_matches_scalar_oracle",
    "test_dynamic_matches_static_results",
    "test_dynamic_window_covers_more_time",
    "test_engine_matches_cpu_reference",
    "test_engine_netstack_matches_cpu_reference",
    "test_example_config_runs",
    "test_fattree_bulk_tcp_smoke",
    "test_goodput_tracks_bandwidth_cap",
    "test_handshake_and_transfer_no_loss",
    "test_http_example",
    "test_http_matrix_104_hosts",
    "test_hybrid_run_twice_deterministic",
    "test_manager_end_to_end_tpu",
    "test_manager_tpu_matches_cpu_ref_scheduler",
    "test_many_pairs_all_complete",
    "test_many_to_few_servers",
    "test_netstack_jit_matches_debug_and_shapes_traffic",
    "test_parallel_matches_serial",
    "test_parallel_worker_count_invariant",
    "test_phold_compact_bit_identical",
    "test_python_http_server_serves_curl",
    "test_python_http_server_deterministic",
    "test_sharded_bulk_tcp_1k_hosts_matches_single",
    "test_sharded_compact_matches_single_device",
    "test_sharded_matches_single_device",
    # Mesh-round budget split (tests/test_mesh.py + the daemon
    # compaction pin): the tier-1 suite ran 782s of its 870s cap before
    # this round, so the quick tier takes only the acceptance pins —
    # phold slice equivalence, mesh checkpoint/resume, the (replica,
    # shard) capacity naming, plan/spec validation, and the 4-job
    # one-compile sweep smoke (~60s together). The full-stack tgen slice
    # pin (~4 min shard_map compile), the whole-batch regrow pin
    # (mirroring its already-slow ensemble counterpart), and the
    # kill-during-compaction daemon pin (subprocess daemons) run in the
    # full tier.
    "test_mesh_slice_matches_single_tgen_pump",
    "test_mesh_recovery_regrows_whole_batch",
    "test_daemon_journal_compaction_survives_kill",
    # Elastic-mesh round budget split (tests/test_elastic.py,
    # tests/test_elastic_cli.py): the quick tier keeps the acceptance
    # pins — the 2x4-checkpoint-resumes-anywhere CLI matrix, the
    # device-loss CLI completion, the degraded-grid capacity naming,
    # the terminal-outside-mesh pin, and the pure units (~60s). The
    # engine-level leaf-exact replay pin, the regrow-on-degraded-grid
    # pin, and the sweep-batch survival pin each pay extra mesh
    # compiles (~20 s apiece) and run in the full tier.
    "test_device_loss_degrades_mesh_and_replays_leaf_exact",
    "test_whole_batch_regrow_on_grid_reached_via_degradation",
    "test_sweep_batch_survives_device_loss",
    "test_device_loss_terminal_outside_mesh_is_structured",
    "test_capacity_naming_on_grid_reached_via_degradation",
    # Elastic-round REBALANCE: the quick tier measured 1080s on this
    # box (the 870s cap was already breached before this round's ~60s
    # of acceptance pins — the 782s PR-14 number was a faster day).
    # Moved to the full tier, each with quick-tier coverage of the same
    # plane retained: the shaped pump-vs-plain tgen matrix (~122s —
    # test_pump_unshaped_world_matches still pins pump-tgen equivalence
    # quick), the pump-tgen tracker cross-engine cell (~80s — the phold
    # trajectory pin, probe-lane, fold and CLI tracker tests stay
    # quick), the onion example ensemble rung (~62s — the registry
    # [onion] smoke and the single-run example stay), and the
    # netstack-noop equivalence (~30s — bootstrap-period shaping and
    # the TCP suites keep quick netstack coverage).
    "test_pump_bit_identical_tgen",
    "test_tracker_counters_cross_engine_pump_tgen",
    "test_onion_example_replicas_aggregate",
    "test_netstack_unlimited_is_noop",
    "test_streams_cycle",
    "test_streams_deterministic",
    "test_system_curl_run_twice_strace_identical",
    "test_tgen_compact_bit_identical",
    "test_transfer_completes_under_loss",
    "test_unmatched_segment_draws_rst",
    # ~38 s solo (two end-to-end 64 MB managed-guest runs); under
    # full-suite contention the guests' syscall waits flake on wall time
    # (CHANGES.md PR 8) — the structural work-ratio assertions inside it
    # are contention-proof, the wall is not, so it runs in the full tier
    "test_bulk_pipe_stream_integrity_and_speed",
    # the adaptive-window equivalence MATRIX (engines x tgen, sharded,
    # ensemble) pays an XLA compile per cell (~40-90 s each on this box);
    # the quick tier keeps the tentpole pins (phold leaf-exactness +
    # iteration reduction, checkpoint roundtrip)
    "test_adaptive_matches_fixed_tgen_engines",
    "test_adaptive_matches_fixed_sharded",
    "test_adaptive_matches_fixed_ensemble_slices",
    # ~25 s; the quick tier already runs the real checkpoint machinery
    # with adaptive windows on by default (tests/test_robustness.py)
    "test_adaptive_checkpoint_roundtrip_leaf_exact",
    # overlay equivalence matrix (tests/test_overlay.py): each cell pays
    # an onion/cdn/gossip XLA compile (the onion handler is tgen-class);
    # the quick tier keeps the registry smoke (one compile per model)
    # and the example CLI smoke
    "test_onion_pump_matches_plain",
    "test_overlay_ensemble_slices_exact",
    "test_onion_chaos_capacity_recovers_leaf_exact",
    "test_onion_circuits_streams_and_scheduling",
    "test_cdn_hierarchy_fills_downward",
    "test_gossip_churn_and_view_mixing",
    # one compile per example rung is enough for the quick tier: it
    # keeps the --replicas 2 CLI smoke (the satellite contract — the
    # ensemble path subsumes the single-run plumbing), the single-run
    # rung joins the full tier
    "test_onion_example_runs",
}


# ---- managed-guest (LD_PRELOAD shim) availability ----------------------
# The hostk/hybrid/managed suites run real executables under the
# LD_PRELOAD shim. In some container images the shim cannot load into
# guests at all (observed here: `symbol lookup error: libshadow_shim.so:
# undefined symbol: dlsym` — a glibc linking mismatch — so every guest
# exits 127; the seed suites fail there pre-existing, CHANGES.md PR 4).
# Probe ONCE per session — compile a trivial guest and run it under a
# minimal NetKernel in a subprocess (a subprocess so a hung guest cannot
# wedge collection) — and when the probe fails, auto-skip the
# guest-execution tests with the probe's reason instead of failing them
# one by one. Engine-level suites never skip.

_GUEST_PROBE_SCRIPT = r"""
import pathlib, subprocess, sys, tempfile
root = pathlib.Path(sys.argv[1])
sys.path.insert(0, str(root))
from shadow_tpu.graph import NetworkGraph, compute_routing
from shadow_tpu.hostk.kernel import NetKernel, ProcessSpec
tmp = pathlib.Path(tempfile.mkdtemp(prefix="shim-probe-"))
src = tmp / "guest.c"
src.write_text("int main(void) { return 0; }\n")
exe = tmp / "guest"
subprocess.run(["cc", "-O0", "-o", str(exe), str(src)], check=True)
graph = NetworkGraph.from_gml(
    'graph [ directed 0 node [ id 0 ] '
    'edge [ source 0 target 0 latency "1 ms" ] ]'
)
tables = compute_routing(graph).with_hosts([0])
k = NetKernel(tables, host_names=["h"], host_nodes=[0], seed=1,
              data_dir=tmp / "data")
p = k.add_process(ProcessSpec(host="h", args=[str(exe)]))
try:
    k.run(1_000_000_000)
finally:
    k.shutdown()
print("GUEST_OK" if p.exit_code == 0
      else f"GUEST_BAD: trivial guest exited {p.exit_code} "
           f"(state {p.state}) under the shim")
"""

# The seed tests that REQUIRE working guest execution (real binaries
# under the shim — directly, via the hybrid scheduler, or via the
# managed CLI): exactly these skip when the probe fails. Their modules
# also hold engine-level and native-guest tests that pass without the
# shim, which is why this is a test list, not a module list.
GUEST_EXEC_TESTS = {
    "test_cli_managed_end_to_end",
    "test_cli_serial_scheduler_matches_hybrid",
    "test_cli_double_run_strace_identical",
    "test_cli_managed_shutdown_while_blocked",
    "test_cli_expected_running_killed_at_stop",
    "test_udp_echo_under_simulated_network",
    "test_exit_codes_reaped",
    "test_breadth_under_shim",
    "test_breadth2_deterministic_views",
    "test_msg_waitall",
    "test_cpp_guest_under_shim",
    "test_dns_apis_under_shim",
    "test_fd_guest_matches_native",
    "test_descriptor_families",
    "test_file_sandbox_and_virtual_devices",
    "test_urandom_deterministic_per_seed",
    "test_random_deterministic_per_seed",
    "test_fork_guest_under_shim",
    "test_forking_server_serves_three_curls",
    "test_forking_server_deterministic",
    "test_fs_breadth_values",
    "test_raw_futex_semantics",
    "test_go_patterns",
    "test_mm_guest_matches_native",
    "test_mm_ledger_tracks_guest_mappings",
    "test_fifo_keeps_burst_order",
    "test_rr_interleaves_sockets",
    "test_rr_deterministic",
    "test_raw_clone_thread_adopted",
    "test_raw_clone_slot_reuse",
    "test_raw_syscalls_intercepted",
    "test_unshaped_blast_arrives_at_line_rate",
    "test_sender_bandwidth_paces_the_burst",
    "test_receiver_bandwidth_paces_the_burst",
    "test_tcp_bulk_over_shaped_link",
    "test_signals_guest_native",
    "test_signals_guest_under_shim",
    "test_cross_process_kill",
    "test_default_disposition_terminates",
    "test_shutdown_time_uses_sigterm",
    "test_tcp_echo_small",
    "test_tcp_bulk_transfer",
    "test_tcp_retransmission_under_loss",
    "test_tcp_connection_refused",
    "test_pcap_capture",
    "test_tcp_strace_written",
    "test_threads_guest_under_shim",
    "test_main_pthread_exit_workers_continue",
    "test_rdtsc_serves_sim_time",
    "test_unix_guest_native",
    "test_unix_guest_under_shim",
    "test_unix_echo_two_processes_same_host",
    "test_hybrid_matches_serial_tcp",
    "test_hybrid_matches_serial_tcp_under_loss",
    "test_system_curl_fetches_in_sim",
    "test_system_wget_fetches_in_sim",
    "test_system_curl_sees_simulated_time",
    "test_sack_fewer_retransmits_equal_goodput",
    "test_autotune_tracks_bdp",
}


def _managed_guest_reason():
    """None when managed guests work here; else a short skip reason.
    Called at most once per session (pytest_collection_modifyitems)."""
    import subprocess
    import sys as _sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        r = subprocess.run(
            [_sys.executable, "-c", _GUEST_PROBE_SCRIPT, root],
            capture_output=True,
            text=True,
            timeout=180,
        )
    except subprocess.TimeoutExpired:
        return "managed-guest probe hung (>180s): guest never completed"
    if "GUEST_OK" in r.stdout:
        return None
    bad = [ln for ln in r.stdout.splitlines() if ln.startswith("GUEST_BAD")]
    tail = bad or (r.stdout + r.stderr).strip().splitlines()
    detail = tail[-1][:200] if tail else f"rc={r.returncode}"
    return (
        "managed-guest (LD_PRELOAD shim) execution does not work in this "
        f"environment: {detail}"
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
    guest_items = [
        i for i in items if item_base_name(i) in GUEST_EXEC_TESTS
    ]
    if guest_items:
        reason = _managed_guest_reason()
        if reason is not None:
            marker = pytest.mark.skip(reason=reason)
            for item in guest_items:
                item.add_marker(marker)


def item_base_name(item) -> str:
    return item.name.split("[")[0]


def pytest_report_header(config):
    return f"jax {jax.__version__}, devices: {jax.device_count()} ({jax.default_backend()})"
