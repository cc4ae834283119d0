"""Tier-1 smoke for the durable simulation daemon (docs/service.md
"Daemon mode"):

* a two-tenant spool drains end to end: live admissions journaled,
  per-job sim-stats leaf-identical to standalone runs, tenant gauges in
  the Prometheus textfile, a clean `shutdown` journal record;
* the kill-the-daemon invariant: SIGKILL at a chaos-chosen point during
  a multi-tenant run, restart on the same spool, and every admitted job
  completes with sim-stats identical to its uninterrupted standalone
  run — zero jobs lost, the journal recording the crash and whether
  each batch resumed from a checkpoint or restarted from scratch;
* the persistent compile cache: a restarted daemon pays ZERO XLA
  recompiles for previously-compiled worlds (disk hits), and a
  corrupted cache entry degrades to a recompile warning, never a
  failure;
* admission control: quota, backpressure, duplicate, and parse
  rejections are structured journal records with reply files, and
  rejections alone never fail the daemon.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest
import yaml

from shadow_tpu.runtime.cli_run import (
    run_from_config,
    run_serve,
    run_submit,
)


@pytest.fixture(scope="module")
def shared_cache(tmp_path_factory):
    """One persistent compile-cache dir shared by the tests that do NOT
    assert compile counts: the cache key excludes data paths (the
    fingerprint's display keys), so every test spool's identical world
    maps to the same entry — the suite pays the XLA compile once, which
    is the daemon's own economics applied to its tests."""
    return str(tmp_path_factory.mktemp("daemon-cache"))

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_CONFIG = {
    "general": {
        "stop_time": "120 ms",
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


def _spec(tmp_path, fname, tenant, name, seeds, priority=0):
    p = tmp_path / fname
    p.write_text(
        yaml.safe_dump(
            {
                "job": {
                    "tenant": tenant,
                    "name": name,
                    "seeds": list(seeds),
                    "priority": priority,
                    "config": BASE_CONFIG,
                }
            }
        )
    )
    return p


def _stats(path) -> dict:
    """sim-stats.json modulo wall-clock and execution-shape counters —
    the comparison idiom of tests/test_sweep_cli.py (a standalone run
    shards over the 8 virtual devices; a daemon job runs in a
    single-device ensemble batch, so drain-iteration counts and derived
    occupancy legitimately differ; every trajectory fact must not)."""
    s = json.loads(pathlib.Path(path).read_text())
    s.pop("wall_seconds")
    # the memory section prices the run's OWN device footprint (sharded
    # single state vs ensemble batch row): execution shape, not trajectory
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


def _standalone(tmp_path, seed) -> dict:
    d = tmp_path / f"alone-s{seed}"
    cfg = tmp_path / f"alone-s{seed}.yaml"
    raw = json.loads(json.dumps(BASE_CONFIG))
    raw["general"]["seed"] = seed
    raw["general"]["data_directory"] = str(d)
    cfg.write_text(yaml.safe_dump(raw))
    assert run_from_config(str(cfg)) == 0
    return _stats(d / "sim-stats.json")


def _journal(spool) -> "list[dict]":
    recs = []
    for f in sorted((pathlib.Path(spool) / "journal").glob("r*.json")):
        recs.append(json.loads(f.read_text()))
    return recs


def _serve_subprocess(spool, *extra_args, cache_dir=None, timeout=420):
    """Run the daemon CLI in a child process (the SIGKILL target); cwd
    puts the repo on sys.path."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    args = [sys.executable, "-m", "shadow_tpu.cli", "serve", str(spool),
            "--drain", *extra_args]
    if cache_dir:
        args += ["--cache-dir", cache_dir]
    return subprocess.run(
        args, cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def test_daemon_two_tenants_drain_clean(tmp_path, shared_cache):
    """Spool protocol + journal + tenant telemetry, no faults: two
    tenants' specs admit, run, and publish standalone-identical stats,
    and the shutdown is journaled clean."""
    spool = tmp_path / "spool"
    prom = tmp_path / "daemon.prom"
    assert run_submit(
        str(spool), str(_spec(tmp_path, "a.yaml", "alice", "ph", [0, 1]))
    ) == 0
    assert run_submit(
        str(spool), str(_spec(tmp_path, "b.yaml", "bob", "ph", [3, 4]))
    ) == 0
    assert run_serve(
        str(spool), drain=True, metrics_prom=str(prom),
        cache_dir=shared_cache,
    ) == 0

    m = json.loads((spool / "daemon-manifest.json").read_text())
    assert m["jobs_done"] == 4 and m["jobs_failed"] == 0
    assert m["daemon"]["outstanding_jobs"] == 0
    t = m["daemon"]["tenants"]
    assert t["alice"]["done"] == 2 and t["bob"]["done"] == 2

    recs = _journal(spool)
    kinds = [r["type"] for r in recs]
    assert kinds.count("admit") == 2
    assert kinds.count("job-done") == 4
    assert kinds[-1] == "shutdown" and recs[-1]["clean"] is True
    # every record carries a valid payload digest
    assert all("sha256" in r for r in recs)
    # spool lifecycle: both specs archived, incoming empty
    assert len(list((spool / "accepted").iterdir())) == 2
    assert not [
        p for p in (spool / "incoming").iterdir()
        if p.name.endswith(".yaml")
    ]

    # the daemon gauge family (satellite: uptime + per-tenant depth)
    text = prom.read_text()
    assert "shadow_tpu_daemon_uptime_seconds" in text
    assert 'shadow_tpu_tenant_queue_depth{tenant="alice"} 0' in text
    assert 'shadow_tpu_tenant_queue_depth{tenant="bob"} 0' in text

    # per-job outputs leaf-identical to standalone runs
    for name, seed in (("alice.ph-s0", 0), ("bob.ph-s3", 3)):
        job = _stats(spool / "jobs" / name / "sim-stats.json")
        assert job == _standalone(tmp_path, seed)


def test_daemon_sigkill_replay_bit_exact(tmp_path, shared_cache):
    """The kill-the-daemon invariant (acceptance): SIGKILL mid-run at a
    chaos-chosen chunk, restart on the same spool dir, and every
    admitted job completes with sim-stats identical to its
    uninterrupted standalone run — zero lost jobs, the crash and the
    resume decision (checkpoint vs scratch) in the journal. A second
    kill fires the instant a checkpoint commits, pinning the
    resume-from-checkpoint path specifically."""
    spool = tmp_path / "spool"
    assert run_submit(
        str(spool), str(_spec(tmp_path, "c.yaml", "carol", "ph", [0, 1]))
    ) == 0
    r = _serve_subprocess(
        spool, "--chaos-fault", "daemon-kill@2:target=chunk",
        cache_dir=shared_cache,
    )
    assert r.returncode in (-9, 137), r.stderr[-500:]
    recs = _journal(spool)
    assert recs[-1]["type"] != "shutdown"  # no clean-shutdown record

    # restart: journal replay re-queues carol's jobs and finishes them
    assert run_serve(str(spool), drain=True, cache_dir=shared_cache) == 0
    m = json.loads((spool / "daemon-manifest.json").read_text())
    resume = m["daemon"]["resume"]
    assert resume["crashed"] is True and resume["pending_jobs"] == 2
    assert {j for b in resume["batches"] for j in b["jobs"]} == {
        "carol.ph-s0", "carol.ph-s1",
    }
    recs = _journal(spool)
    rr = [r for r in recs if r["type"] == "resume"]
    assert rr and rr[-1]["crashed"] is True

    # second crash class: die the moment checkpoint #1 commits (the
    # warm persistent cache makes this subprocess skip the recompile)
    assert run_submit(
        str(spool), str(_spec(tmp_path, "d.yaml", "dave", "ph", [5, 6]))
    ) == 0
    r = _serve_subprocess(
        spool, "--chaos-fault", "daemon-kill@1:target=checkpoint",
        cache_dir=shared_cache,
    )
    assert r.returncode in (-9, 137), r.stderr[-500:]
    assert run_serve(str(spool), drain=True, cache_dir=shared_cache) == 0
    m = json.loads((spool / "daemon-manifest.json").read_text())
    resume = m["daemon"]["resume"]
    assert resume["crashed"] is True
    dave = [b for b in resume["batches"] if "dave.ph-s5" in b["jobs"]]
    assert dave and dave[0]["checkpoint"], (
        "a kill fired right after a checkpoint commit must resume from "
        f"that checkpoint, got {resume['batches']}"
    )

    # zero lost jobs, bit-exact outputs — resumed-from-checkpoint and
    # restarted-from-scratch alike
    admitted = {
        j for r in recs if r["type"] == "admit" for j in r["jobs"]
    } | {"dave.ph-s5", "dave.ph-s6"}
    done = {
        r["job"] for r in _journal(spool) if r["type"] == "job-done"
    }
    assert admitted <= done
    for name, seed in (("carol.ph-s0", 0), ("dave.ph-s5", 5)):
        job = _stats(spool / "jobs" / name / "sim-stats.json")
        assert job == _standalone(tmp_path, seed)


def test_daemon_persistent_cache_and_corruption(tmp_path, shared_cache):
    """Acceptance: a restarted daemon's persistent compile cache serves
    hits — 0 XLA recompiles for a previously-compiled world — and a
    corrupted cache entry degrades to a recompile warning, never a
    failure. Runs against the module's shared cache, warmed by the
    earlier tests' daemons: a FRESH spool disk-hitting an entry another
    daemon stored is the cross-restart contract at its strongest."""
    from shadow_tpu.runtime import chaos

    if not list(pathlib.Path(shared_cache).glob("exe-*.bin")):
        # standalone invocation of this test: warm the cache the way
        # the module run does (a first daemon compiling and storing)
        warm = tmp_path / "warmspool"
        run_submit(str(warm), str(_spec(tmp_path, "w.yaml", "w", "w", [0, 1])))
        assert run_serve(str(warm), drain=True, cache_dir=shared_cache) == 0

    spool = tmp_path / "spool"
    run_submit(str(spool), str(_spec(tmp_path, "a.yaml", "t", "j1", [0, 1])))
    assert run_serve(str(spool), drain=True, cache_dir=shared_cache) == 0
    m = json.loads((spool / "daemon-manifest.json").read_text())
    cache = m["compile_cache"]
    assert cache["compiles"] == 0, (
        "a restarted daemon must serve previously-compiled worlds from "
        "the persistent cache — zero XLA recompiles"
    )
    assert cache["hits"] == 1
    assert cache["persistent"]["disk_hits"] == 1

    # corrupt the entry: the next daemon hitting the SAME executable
    # shape recompiles with a warning — and re-persists a sound entry
    entries = list(pathlib.Path(shared_cache).glob("exe-*.bin"))
    assert len(entries) == 1
    chaos.damage_file(str(entries[0]), truncate=False)
    run_submit(str(spool), str(_spec(tmp_path, "c.yaml", "t", "j3", [8, 9])))
    # a restarted daemon is a new process — and XLA:CPU cannot serialize
    # an executable again in a process that has loaded its serialized twin
    # (`LessThan is not serializable`), which is what the in-process
    # daemons above have done by now
    r = _serve_subprocess(spool, cache_dir=shared_cache)
    assert r.returncode == 0, r.stderr[-2000:]
    m = json.loads((spool / "daemon-manifest.json").read_text())
    cache = m["compile_cache"]
    assert cache["compiles"] == 1  # the corrupt entry forced a recompile
    assert cache["persistent"]["disk_skips"] >= 1
    assert cache["persistent"]["disk_stores"] == 1  # re-persisted
    assert m["jobs_failed"] == 0 and m["jobs_done"] == 2


def test_daemon_admission_control(tmp_path, shared_cache):
    """Quota, backpressure, duplicate, and parse refusals: structured,
    journaled rejection records + reply files; rejections alone leave
    the daemon clean (exit 0)."""
    spool = tmp_path / "spool"
    (spool / "incoming").mkdir(parents=True)
    # 3-job spec for alice against a quota of 1 -> quota rejection
    run_submit(
        str(spool), str(_spec(tmp_path, "a.yaml", "alice", "big", [0, 1, 2]))
    )
    # 2-job spec for bob against max_queue 1 -> backpressure
    run_submit(str(spool), str(_spec(tmp_path, "b.yaml", "bob", "two", [0, 1])))
    # unparseable spec -> parse rejection
    (spool / "incoming" / "zz-broken.yaml").write_text("job: [not, a, map]\n")
    assert (
        run_serve(
            str(spool), drain=True,
            quotas=["alice=1"], max_queue=1,
        )
        == 0
    )
    recs = _journal(spool)
    reasons = {r["reason"] for r in recs if r["type"] == "reject"}
    assert reasons == {"quota", "backpressure", "parse"}
    rejected = sorted(p.name for p in (spool / "rejected").iterdir())
    assert len([n for n in rejected if n.endswith(".reason.json")]) == 3
    # a reply file names the structured reason
    reason_doc = json.loads(
        next(
            p for p in (spool / "rejected").iterdir()
            if "a.yaml.reason.json" in p.name
        ).read_text()
    )
    assert reason_doc["reason"] == "quota"
    m = json.loads((spool / "daemon-manifest.json").read_text())
    assert m["daemon"]["tenants"]["alice"]["rejected_specs"] == 1
    assert m["jobs_done"] == 0 and m["jobs_failed"] == 0

    # duplicate (tenant, entry) resubmission under a new digest rejects;
    # the identical digest is an idempotent no-op admission
    run_submit(
        str(spool), str(_spec(tmp_path, "c.yaml", "carol", "ph", [0, 1]))
    )
    assert run_serve(str(spool), drain=True, cache_dir=shared_cache) == 0
    run_submit(
        str(spool), str(_spec(tmp_path, "c2.yaml", "carol", "ph", [0, 5]))
    )
    assert run_serve(str(spool), drain=True, cache_dir=shared_cache) == 0
    recs = _journal(spool)
    assert any(
        r["type"] == "reject" and r["reason"] == "duplicate" for r in recs
    )


def test_daemon_journal_compaction_survives_kill(tmp_path, shared_cache):
    """Journal compaction (ROADMAP item 5 follow-on): terminal records
    fold into a sha-digested snapshot + tail so the journal stops
    growing one file per record — and a SIGKILL injected the instant a
    snapshot commits (before the covered records are deleted) loses
    nothing: restart replays snapshot + tail, ignores the stale
    already-covered records, and finishes every admitted job with
    standalone-identical stats."""
    spool = tmp_path / "spool"
    run_submit(
        str(spool), str(_spec(tmp_path, "a.yaml", "alice", "ph", [0, 1]))
    )
    r = _serve_subprocess(
        spool, "--journal-compact-every", "3",
        "--chaos-fault", "daemon-kill:target=compact",
        cache_dir=shared_cache,
    )
    assert r.returncode in (-9, 137), r.stderr[-500:]
    jdir = spool / "journal"
    snaps = sorted(jdir.glob("snap-*.json"))
    assert snaps, "the kill fires only AFTER a snapshot committed"
    snap = json.loads(snaps[-1].read_text())
    through = snap["through_seq"]
    # the kill landed between commit and deletion: stale covered records
    # are still on disk — replay must ignore them, not double-apply
    stale = [
        p for p in jdir.glob("r*.json")
        if int(p.name[1:9]) <= through
    ]
    assert stale, "deletions must not have run before the kill"

    # restart on the same spool: snapshot + tail replays, jobs finish
    assert run_serve(
        str(spool), drain=True, cache_dir=shared_cache,
        journal_compact_every=3,
    ) == 0
    m = json.loads((spool / "daemon-manifest.json").read_text())
    assert m["jobs_failed"] == 0 and m["jobs_quarantined"] == 0
    assert m["daemon"]["outstanding_jobs"] == 0
    t = m["daemon"]["tenants"]["alice"]
    assert t["admitted"] == 2 and t["done"] == 2
    done = {
        r["job"] for r in _journal(spool) if r["type"] == "job-done"
    } | set(
        j for s in jdir.glob("snap-*.json")
        for j, st in json.loads(s.read_text())["terminal"].items()
        if st == "done"
    )
    assert done == {"alice.ph-s0", "alice.ph-s1"}
    job = _stats(spool / "jobs" / "alice.ph-s0" / "sim-stats.json")
    assert job == _standalone(tmp_path, 0)

    # growth bound: another tenant's round trip through the same spool
    # compacts again — record files stay at ~cadence scale and the
    # finished admission folds to digests (its spec lives in accepted/)
    run_submit(
        str(spool), str(_spec(tmp_path, "b.yaml", "bob", "ph", [3, 4]))
    )
    assert run_serve(
        str(spool), drain=True, cache_dir=shared_cache,
        journal_compact_every=3,
    ) == 0
    assert len(list(jdir.glob("r*.json"))) <= 6
    assert len(list(jdir.glob("snap-*.json"))) <= 2  # keep-2 retention
    newest = json.loads(
        sorted(jdir.glob("snap-*.json"))[-1].read_text()
    )
    folded = {f["entry"] for f in newest["folded_admits"]}
    assert "ph" in folded
    assert all("spec" not in f for f in newest["folded_admits"])
    # compaction is idempotent against the accepted/ rescan: no
    # re-journaled (recovered=True) admissions after folding
    assert not any(
        r.get("recovered") for r in _journal(spool) if r["type"] == "admit"
    )
    m = json.loads((spool / "daemon-manifest.json").read_text())
    assert m["daemon"]["tenants"]["bob"]["done"] == 2
    # alice's history survived two compactions intact
    assert m["daemon"]["tenants"]["alice"]["done"] == 2
