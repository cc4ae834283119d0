"""Chaos-driven SLO soak for the durable daemon (docs/service.md
"Daemon mode"; the robustness capstone of ISSUE 11).

100+ jobs across 3 tenants — roughly one simulated DAY of aggregate
sim-time — submitted to a spooled daemon while the chaos plane fires
daemon-kills (the process is SIGKILLed and restarted on the same spool,
repeatedly), journal-record corruption, cache-entry corruption, and a
persistent poison-job capacity fault. The acceptance bar:

  * ZERO lost jobs: every admitted job reaches a terminal, journaled
    status (done, or quarantined for the poison entry);
  * the queue drains via quarantine rather than collapse: only the
    poisoned entry's jobs may end non-done, and the daemon's exit after
    the final fault-free drain reflects the quarantine (non-zero), not
    a crash;
  * the persistent compile cache amortizes across restarts (the
    restarted daemons pay near-zero recompiles);
  * jobs/hour and cache-hit-rate are published.

A second scenario soaks the FLEET contract (docs/service.md "Running a
fleet"): two daemons on one spool, one SIGKILLed mid-batch — the
survivor must wait out the dead daemon's lease, journal the claim
steal, resume from the newest checkpoint, and finish every job with
sim-stats bit-exact to uninterrupted standalone runs.

Runs under the `soak` marker (registered in pyproject.toml), excluded
from tier-1 via `slow`. SHADOW_TPU_SOAK_JOBS overrides the job count.
"""

import json
import os
import subprocess
import sys

import pytest
import yaml

from shadow_tpu.runtime.cli_run import run_serve, run_submit

pytestmark = [pytest.mark.soak, pytest.mark.slow]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ~14 sim-minutes per job; 102 jobs ~= 23.8 simulated hours. Sparse
# phold traffic + adaptive windows keep each batch's wall cost small —
# this soaks the SERVICE (journal, restarts, quarantine, cache), not
# the engine.
SOAK_CONFIG = {
    "general": {
        "stop_time": "840 s",
        "heartbeat_interval": None,
        "checkpoint_interval": "200 s",
    },
    "network": {"graph": {"type": "1_gbit_switch"}},
    "experimental": {"rounds_per_chunk": 8, "recover": False},
    "hosts": {
        "peer": {
            "network_node_id": 0,
            "quantity": 4,
            "processes": [
                {
                    "path": "phold",
                    "args": {"min_delay": "200 ms", "max_delay": "2 s"},
                }
            ],
        }
    },
}

TENANTS = ("t1", "t2", "t3")
POISON_JOB = "t3.poison-s0"


def _submit_all(tmp_path, spool, total_jobs):
    """total_jobs spread over 3 tenants, 6 seeds per spec, plus one
    single-seed poison entry for t3."""
    per_spec = 6
    submitted = []
    n = 0
    i = 0
    while n < total_jobs - 1:
        tenant = TENANTS[i % len(TENANTS)]
        seeds = list(range(i * per_spec, i * per_spec + per_spec))
        spec = tmp_path / f"spec-{i:03d}.yaml"
        spec.write_text(
            yaml.safe_dump(
                {
                    "job": {
                        "tenant": tenant,
                        "name": f"e{i:03d}",
                        "seeds": seeds,
                        "config": SOAK_CONFIG,
                    }
                }
            )
        )
        assert run_submit(str(spool), str(spec)) == 0
        submitted.extend(f"{tenant}.e{i:03d}-s{s}" for s in seeds)
        n += len(seeds)
        i += 1
    poison = tmp_path / "poison.yaml"
    poison.write_text(
        yaml.safe_dump(
            {
                "job": {
                    "tenant": "t3",
                    "name": "poison",
                    "seeds": [0],
                    "config": SOAK_CONFIG,
                }
            }
        )
    )
    assert run_submit(str(spool), str(poison)) == 0
    submitted.append(POISON_JOB)
    return submitted


def _serve(spool, *faults, seed=0, timeout=1800):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    args = [sys.executable, "-m", "shadow_tpu.cli", "serve", str(spool),
            "--drain", "--retry-max", "1", "--chaos-seed", str(seed),
            # the poison fault fires every attempt
            "--chaos-fault", f"capacity:target={POISON_JOB}:count=-1"]
    for f in faults:
        args += ["--chaos-fault", f]
    return subprocess.run(args, cwd=REPO_ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_soak_100_jobs_3_tenants_chaos(tmp_path):
    total_jobs = int(os.environ.get("SHADOW_TPU_SOAK_JOBS", "102"))
    spool = tmp_path / "spool"
    submitted = _submit_all(tmp_path, spool, total_jobs)
    assert len(submitted) >= total_jobs

    # chaos phase: each run is killed at a seeded, auto-drawn site;
    # journal and cache corruption ride along. Restart on the same
    # spool every time.
    kill_phases = [
        ("daemon-kill@auto:target=chunk", "spool-corrupt@3"),
        ("daemon-kill@1:target=batch-start", "cache-corrupt@0"),
        ("daemon-kill@0:target=checkpoint",),
        ("daemon-kill@auto:target=chunk",),
    ]
    crashes = 0
    for n, faults in enumerate(kill_phases):
        r = _serve(spool, *faults, seed=n)
        if r.returncode in (-9, 137):
            crashes += 1
        # a phase may also finish cleanly if the kill site was never
        # reached (e.g. the queue drained first) — that's fine

    # final fault-free drains (in-process, poison fault still injected
    # via the subprocess-only plan being absent -> the poison job now
    # RUNS CLEAN? No: quarantine must already have happened, or the job
    # simply completes — both are terminal; zero-lost is the invariant)
    for _ in range(3):
        rc = run_serve(str(spool), drain=True)
        m = json.loads((spool / "daemon-manifest.json").read_text())
        if m["daemon"]["outstanding_jobs"] == 0:
            break
    assert m["daemon"]["outstanding_jobs"] == 0, (
        f"queue failed to drain: {m['daemon']['outstanding_jobs']} "
        f"outstanding after the fault-free drains"
    )

    # ---- zero lost jobs: every admitted job is terminal in the journal
    recs = []
    for f in sorted((spool / "journal").glob("r*.json")):
        try:
            recs.append(json.loads(f.read_text()))
        except ValueError:
            continue  # a chaos-corrupted record; its admission recovered
    admitted = {j for r in recs if r.get("type") == "admit"
                for j in r.get("jobs", [])}
    terminal = {r.get("job"): r["type"][len("job-"):]
                for r in recs
                if r.get("type") in ("job-done", "job-failed",
                                     "job-quarantined")}
    assert set(submitted) <= admitted
    lost = admitted - set(terminal)
    assert not lost, f"lost jobs (admitted, never terminal): {sorted(lost)}"

    # ---- drain via quarantine, not collapse: only the poison entry may
    # end non-done (it ran its final attempts without the injected fault
    # in-process, so done is also acceptable — but nothing ELSE may fail)
    non_done = {j: s for j, s in terminal.items() if s != "done"}
    assert set(non_done) <= {POISON_JOB}, f"unexpected failures: {non_done}"

    # ---- every done job published standalone-format outputs
    sample = sorted(j for j in submitted if terminal.get(j) == "done")[:5]
    for name in sample:
        stats = json.loads(
            (spool / "jobs" / name / "sim-stats.json").read_text()
        )
        assert stats["events_handled"] > 0

    # ---- the SLO numbers exist and the cache amortized across restarts
    d = m["daemon"]
    assert d["jobs_per_hour"] is None or d["jobs_per_hour"] >= 0
    assert d["jobs_done_total"] >= len(submitted) - 1
    cache = m["compile_cache"]
    # the final drains ran entirely from the persistent cache unless the
    # corruption fault forced one recompile
    assert cache["hit_rate"] >= 0.5 or cache["compiles"] <= 2
    assert crashes >= 1, "the chaos phase must have killed the daemon"


# ---- fleet: SIGKILL one of two daemons, survivor reclaims the lease ----

# fast-checkpointing small world: enough chunks that the kill lands
# mid-batch with checkpoints on disk, small enough that standalone
# comparison runs stay cheap
FLEET_CONFIG = {
    "general": {
        "stop_time": "600 ms",
        "heartbeat_interval": None,
        "tracker": True,
        "checkpoint_interval": "20 ms",
    },
    "network": {"graph": {"type": "1_gbit_switch"}},
    "experimental": {"rounds_per_chunk": 4},
    "hosts": {
        "peer": {
            "network_node_id": 0,
            "quantity": 8,
            "processes": [
                {
                    "path": "phold",
                    "args": {"min_delay": "2 ms", "max_delay": "12 ms"},
                }
            ],
        }
    },
}


def _trajectory_stats(path) -> dict:
    """sim-stats.json modulo wall-clock and execution-shape counters
    (the test_daemon_cli.py comparison idiom): a daemon ensemble batch
    and a sharded standalone run legitimately differ in drain-iteration
    shape; every trajectory fact must not."""
    s = json.loads(path.read_text())
    s.pop("wall_seconds")
    s.pop("memory", None)
    s.pop("device", None)  # likewise: where it ran, not what it computed
    if "tracker" in s:
        s["tracker"].pop("phases", None)
        for k in ("iters", "lanes_live", "occupancy", "land_passes"):
            s["tracker"].get("window", {}).pop(k, None)
        # the most entries ONE SHARD staged in a round: the plane's, like
        # land_passes (counted with the tracker on or off since PR 36)
        s["tracker"].get("high_water", {}).pop("exchange", None)
    return s


def test_fleet_sigkill_lease_reclaim_bit_exact(tmp_path):
    """Acceptance: SIGKILL of either fleet daemon mid-batch is recovered
    by the survivor via lease expiry — claim steal journaled, batch
    resumed from the victim's newest checkpoint, zero lost jobs, zero
    double-claims, and outputs bit-exact vs standalone runs."""
    import signal
    import time

    spool = tmp_path / "spool"
    cache = tmp_path / "cache"
    jobs = [("alice", "a", (1, 2)), ("bob", "b", (3, 4))]
    for i, (tenant, name, seeds) in enumerate(jobs):
        spec = tmp_path / f"{tenant}.yaml"
        spec.write_text(yaml.safe_dump({
            "job": {"tenant": tenant, "name": name,
                    "seeds": list(seeds), "config": FLEET_CONFIG}
        }))
        assert run_submit(str(spool), str(spec)) == 0

    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")

    def serve(daemon_id):
        return subprocess.Popen(
            [sys.executable, "-m", "shadow_tpu.cli", "serve", str(spool),
             "--drain", "--poll-interval", "0.2", "--lease-s", "6",
             "--daemon-id", daemon_id, "--cache-dir", str(cache)],
            cwd=REPO_ROOT, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

    # victim: kill the instant a checkpoint commits — mid-batch with a
    # held lease and a resumable trajectory on disk
    victim = serve("victim")
    deadline = time.monotonic() + 600
    killed = False
    while time.monotonic() < deadline:
        ckpts = list((spool / "batches").glob("*/ckpts/ckpt-*.npz"))
        if ckpts and victim.poll() is None:
            victim.send_signal(signal.SIGKILL)
            killed = True
            break
        time.sleep(0.1)
    assert killed, "victim never reached a checkpoint"
    assert victim.wait(timeout=60) in (-9, 137)
    claims = list((spool / "claims").glob("claim-*.json"))
    assert claims, "the dead daemon's claim must survive the kill"

    survivor = serve("survivor")
    out, _ = survivor.communicate(timeout=900)
    assert survivor.returncode == 0, out

    recs = []
    for f in sorted((spool / "journal").glob("r*.json")):
        recs.append(json.loads(f.read_text()))
    steals = [r for r in recs if r["type"] == "claim-steal"]
    assert steals and steals[0]["from_owner"] == "victim"
    assert steals[0]["owner"] == "survivor"
    done = [r["job"] for r in recs if r["type"] == "job-done"]
    expected = sorted(
        f"{t}.{n}-s{s}" for t, n, seeds in jobs for s in seeds
    )
    # exactly-once: zero lost AND zero double-claimed
    assert sorted(done) == expected
    assert not list((spool / "claims").glob("claim-*.json"))

    # bit-exact vs uninterrupted standalone runs, including the batch
    # that crossed the kill + resume
    from shadow_tpu.runtime.cli_run import run_from_config

    for tenant, name, seeds in jobs:
        for seed in seeds:
            alone = tmp_path / f"alone-s{seed}"
            cfg = tmp_path / f"alone-s{seed}.yaml"
            raw = json.loads(json.dumps(FLEET_CONFIG))
            raw["general"]["seed"] = seed
            raw["general"]["data_directory"] = str(alone)
            cfg.write_text(yaml.safe_dump(raw))
            assert run_from_config(str(cfg)) == 0
            job = f"{tenant}.{name}-s{seed}"
            assert _trajectory_stats(
                spool / "jobs" / job / "sim-stats.json"
            ) == _trajectory_stats(alone / "sim-stats.json"), job
