"""Tier-1 smoke for the HTTP front door + daemon fleet (docs/service.md
"HTTP front door" / "Running a fleet"):

* the network admission path: POST /v1/jobs lands a spec in the spool
  through the same atomic drop the CLI uses, the 202 carries the
  canonical job ids, and status/events/results/metrics round-trip
  against the live daemon — events as a chunked ndjson stream closed by
  a terminal sentinel;
* structured refusals: a malformed body is a journaled 400 and an
  over-budget quota-class tenant a journaled 429 with Retry-After,
  while other tenants' jobs proceed (acceptance);
* `submit --wait --http` polls the status endpoint and mirrors the job
  outcome in its exit code;
* the `http-drop` chaos fault surfaces as a structured 503;
* fleet: two daemons drain one spool with zero double-claimed batches
  and zero lost jobs, through lease-based claim files (the SIGKILL
  lease-reclaim half lives in test_daemon_soak.py's soak tier).
"""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest
import yaml

from shadow_tpu.runtime import chaos
from shadow_tpu.runtime.cli_run import run_submit
from shadow_tpu.runtime.daemon import (
    DaemonService,
    _percentiles,
    parse_quota_class,
)

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


@pytest.fixture(scope="module")
def shared_cache(tmp_path_factory):
    """One persistent compile-cache dir for the whole module: every
    test's world is the same BASE_CONFIG shape, so the suite pays the
    XLA compile once (the daemon's economics applied to its tests)."""
    return str(tmp_path_factory.mktemp("httpapi-cache"))


def _spec_text(tenant, name, seeds, config=None):
    return yaml.safe_dump(
        {"job": {"tenant": tenant, "name": name, "seeds": list(seeds),
                 "config": config or BASE_CONFIG}}
    )


def _journal(spool) -> "list[dict]":
    recs = []
    for f in sorted((pathlib.Path(spool) / "journal").glob("r*.json")):
        recs.append(json.loads(f.read_text()))
    return recs


class _Client:
    """Minimal urllib client against a FrontDoor. Non-2xx responses
    come back as (code, headers, body) instead of raising, so tests
    assert on the structured error documents directly."""

    def __init__(self, addr: str):
        self.base = f"http://{addr}"

    def req(self, method, path, body=None, timeout=60):
        r = urllib.request.Request(
            self.base + path,
            data=body.encode() if body is not None else None,
            method=method,
        )
        try:
            with urllib.request.urlopen(r, timeout=timeout) as resp:
                return resp.status, dict(resp.headers), resp.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), e.read().decode()


class _LiveDaemon:
    """An in-process daemon on a background thread with the front door
    up — signal installation no-ops off the main thread, and the stop
    flag is the test's shutdown switch."""

    def __init__(self, spool, **kwargs):
        self.svc = DaemonService(str(spool), **kwargs)
        self.result: "dict | None" = None
        self.error: "BaseException | None" = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        try:
            self.result = self.svc.run()
        except BaseException as e:  # noqa: BLE001 — surfaced in stop()
            self.error = e

    def __enter__(self):
        self.thread.start()
        deadline = time.monotonic() + 30
        addr_file = os.path.join(self.svc.spool_dir, "http-address")
        while time.monotonic() < deadline:
            if self.error is not None:
                raise self.error
            if self.svc.http_addr is None or os.path.exists(addr_file):
                break
            time.sleep(0.05)
        if self.svc.http_addr is not None:
            with open(addr_file) as f:
                self.client = _Client(f.read().strip())
        return self

    def __exit__(self, *exc):
        self.svc._stop = True
        self.thread.join(timeout=120)
        assert not self.thread.is_alive(), "daemon thread did not stop"
        if self.error is not None and not exc[0]:
            raise self.error


def test_http_round_trip_and_refusals(tmp_path, shared_cache, capsys):
    spool = tmp_path / "spool"
    with _LiveDaemon(
        spool,
        capacity=8,
        poll_interval_s=0.2,
        prom_interval_s=1.0,
        http="127.0.0.1:0",
        quota_classes={"starved": {"device_seconds": 0.0, "queue": None}},
        quota_window_s=120.0,
        cache_dir=shared_cache,
    ) as live:
        c = live.client

        # malformed body: journaled 400 mirroring the reject record
        code, _, body = c.req("POST", "/v1/jobs", body=":-not yaml: [")
        err = json.loads(body)["error"]
        assert code == 400 and err["type"] == "reject"
        assert err["reason"] == "parse" and err["via"] == "http"

        # quota-class refusal: 429-equivalent, Retry-After from the
        # refill window, journaled — while alice proceeds below
        code, hdr, body = c.req(
            "POST", "/v1/jobs", body=_spec_text("starved", "no", [1])
        )
        err = json.loads(body)["error"]
        assert code == 429 and err["reason"] == "quota-class"
        assert 0 < int(hdr["Retry-After"]) <= 120
        assert err["retry_after_s"] > 0

        # the network admission path: 202 carries the canonical ids
        spec = _spec_text("alice", "ph", [1, 2])
        code, _, body = c.req("POST", "/v1/jobs", body=spec)
        doc = json.loads(body)
        assert code == 202
        assert doc["job_ids"] == ["alice.ph-s1", "alice.ph-s2"]

        # admission happens at poll cadence: wait for the id to be known
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if c.req("GET", "/v1/jobs/alice.ph-s1")[0] == 200:
                break
            time.sleep(0.1)

        # live event stream: subscribe BEFORE terminal, read chunked
        # ndjson until the sentinel closes the stream
        stream: "list[dict]" = []

        def _tail():
            code, _, text = c.req(
                "GET", "/v1/jobs/alice.ph-s1/events", timeout=300
            )
            assert code == 200, text
            stream.extend(
                json.loads(ln) for ln in text.splitlines() if ln
            )

        tail = threading.Thread(target=_tail, daemon=True)
        tail.start()

        deadline = time.monotonic() + 300
        status = None
        while time.monotonic() < deadline:
            code, _, body = c.req("GET", "/v1/jobs/alice.ph-s1")
            if code == 200:
                status = json.loads(body)
                if status["status"] in ("done", "failed", "quarantined"):
                    break
            time.sleep(0.3)
        assert status and status["status"] == "done", status
        assert status["stats"]["events_handled"] > 0

        tail.join(timeout=60)
        assert not tail.is_alive(), "event stream never closed"
        assert stream and stream[0]["job"] == "alice.ph-s1"
        assert stream[-1] == {"job": "alice.ph-s1", "terminal": "done"}

        # duplicate entry pre-check: 409 once admitted
        code, _, body = c.req("POST", "/v1/jobs", body=spec)
        assert code == 409
        assert json.loads(body)["error"]["reason"] == "duplicate"

        # results = the job's sim-stats.json verbatim
        code, _, body = c.req("GET", "/v1/jobs/alice.ph-s2/results")
        assert code == 200
        assert json.loads(body) == json.loads(
            (spool / "jobs" / "alice.ph-s2" / "sim-stats.json").read_text()
        )

        # unknown id and traversal-shaped ids refuse cleanly
        code, _, _ = c.req("GET", "/v1/jobs/alice.nope-s9")
        assert code == 404
        code, _, _ = c.req("GET", "/v1/jobs/..%2F..%2Fetc/results")
        assert code == 400

        # metrics: the new families render through the one-TYPE-line
        # write_prom contract
        code, _, text = c.req("GET", "/v1/metrics")
        assert code == 200
        assert text.count("# TYPE shadow_tpu_http_requests_total") == 1
        assert 'shadow_tpu_http_requests_total{route="/v1/jobs",code="202"} 1' in text
        assert 'shadow_tpu_http_requests_total{route="/v1/jobs",code="429"} 1' in text
        assert 'shadow_tpu_http_latency_seconds{quantile="0.99"}' in text
        assert 'shadow_tpu_tenant_budget_remaining{tenant="starved"} 0.0' in text
        assert f'shadow_tpu_daemon_leases_held{{daemon="{live.svc.daemon_id}"}}' in text

        # submit --wait --http: canonical ids printed, HTTP polling,
        # exit code mirrors the outcome (satellite a)
        spec2 = tmp_path / "carol.yaml"
        spec2.write_text(_spec_text("carol", "ph", [7]))
        assert run_submit(
            str(spool), str(spec2), wait=True, timeout=300,
            http=c.base, poll_s=0.3,
        ) == 0
        out = capsys.readouterr().out
        assert "job carol.ph-s7" in out
        assert "carol.ph-s7: done" in out

    # journaled refusals + admission latency survive into the journal
    # and manifest
    recs = _journal(spool)
    rejects = [r for r in recs if r["type"] == "reject"]
    assert {r["reason"] for r in rejects} == {
        "parse", "quota-class", "duplicate"
    }
    admits = [r for r in recs if r["type"] == "admit"]
    assert all(r.get("admit_latency_s") is not None for r in admits)
    m = json.loads((spool / "daemon-manifest.json").read_text())
    lat = m["daemon"]["admit_latency"]
    assert lat["count"] == len(admits)
    assert 0 <= lat["p50"] <= lat["p90"] <= lat["p99"]
    assert m["daemon"]["http"]["address"] == live.client.base[len("http://"):]


def test_http_drop_fault_and_parsers(tmp_path):
    """The http-drop chaos fault is a structured 503 (no daemon state
    touched), plus the pure parsing seams of the quota/latency
    satellites."""
    plan = chaos.FaultPlan(
        seed=0, faults=[chaos.parse_fault_arg("http-drop@0")]
    )
    with chaos.installed(plan):
        with _LiveDaemon(
            tmp_path / "spool", poll_interval_s=0.2, http="127.0.0.1:0",
        ) as live:
            code, hdr, body = live.client.req("GET", "/v1/metrics")
            err = json.loads(body)["error"]
            assert code == 503 and err["reason"] == "http-drop"
            assert int(hdr["Retry-After"]) >= 1
            # the fault fires once (at=0): the retry goes through
            code, _, text = live.client.req("GET", "/v1/metrics")
            assert code == 200 and "shadow_tpu_daemon_uptime_seconds" in text

    assert parse_quota_class("alice=device_seconds:120") == (
        "alice", {"device_seconds": 120.0, "queue": None}
    )
    assert parse_quota_class("bob=device_seconds:0.5,queue:3") == (
        "bob", {"device_seconds": 0.5, "queue": 3}
    )
    for bad in ("alice", "alice=", "alice=queue:3", "a=device_seconds:x",
                "a=device_seconds:-1", "a=device_seconds:1,queue:0"):
        with pytest.raises(ValueError):
            parse_quota_class(bad)

    assert _percentiles([]) == {}
    assert _percentiles([3.0]) == {"p50": 3.0, "p90": 3.0, "p99": 3.0}
    xs = list(range(1, 101))
    assert _percentiles([float(x) for x in xs]) == {
        "p50": 50.0, "p90": 90.0, "p99": 99.0
    }


def test_fleet_two_daemons_one_spool(tmp_path, shared_cache):
    """Acceptance: two daemons drain a multi-tenant flood off ONE spool
    with zero double-claimed batches and zero lost jobs; claims are
    journal-visible, both exits clean."""
    spool = tmp_path / "spool"
    inc = spool / "incoming"
    inc.mkdir(parents=True)
    for i, (tenant, name) in enumerate(
        [("alice", "a"), ("bob", "b"), ("carol", "c")]
    ):
        p = inc / f"{i:020d}-{tenant}.yaml"
        tmp = inc / f".{p.name}.tmp"
        tmp.write_text(_spec_text(tenant, name, [1, 2]))
        os.replace(tmp, p)

    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")

    def serve(daemon_id):
        return subprocess.Popen(
            [sys.executable, "-m", "shadow_tpu.cli", "serve", str(spool),
             "--drain", "--poll-interval", "0.2", "--lease-s", "15",
             "--daemon-id", daemon_id, "--cache-dir", shared_cache],
            cwd=REPO_ROOT, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

    d1, d2 = serve("fleet-1"), serve("fleet-2")
    out1, _ = d1.communicate(timeout=420)
    out2, _ = d2.communicate(timeout=420)
    assert d1.returncode == 0, out1
    assert d2.returncode == 0, out2

    recs = _journal(spool)
    done = [r["job"] for r in recs if r["type"] == "job-done"]
    # zero lost AND zero double-claimed: every job terminal exactly once
    assert sorted(done) == sorted(set(done)) == [
        f"{t}.{n}-s{s}"
        for t, n in (("alice", "a"), ("bob", "b"), ("carol", "c"))
        for s in (1, 2)
    ]
    starts = [r for r in recs if r["type"] == "batch-start"]
    assert len(starts) == 3  # one start per batch across the whole fleet
    # claims released on completion; both shutdowns journaled clean
    assert not list((spool / "claims").glob("claim-*.json"))
    shutdowns = [r for r in recs if r["type"] == "shutdown"]
    assert len(shutdowns) == 2 and all(r["clean"] for r in shutdowns)
