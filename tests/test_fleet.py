"""Serving-fleet tests: supervision, admission control, routing, canary.

Everything here drives the REAL fleet/router machinery
(``serve/fleet.py`` + ``serve/router.py``) against the stdlib stub
replica (``serve/stub.py``) — subprocesses that start in ~100 ms, so
supervision, failover, rolling reload and the canary lifecycle are
exercised end to end without a JAX import per replica.  The heavyweight
variant (real ``task=serve`` CLI replicas, real checkpoints) is the
FLEET=1 tier-1 lane: ``tools/fleet_smoke.py``.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from cxxnet_tpu.serve.fleet import (
    FleetOptions,
    ServingFleet,
    fleet_metrics,
    stub_spawn_fn,
)
from cxxnet_tpu.utils import checkpoint as ckpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_alerts():
    """Canary tests arm a rule on the process-wide alert evaluator; no
    test takes over what an earlier file of its xdist worker left in
    it, nor leaks its own into the next.  The metric families are
    process-wide too: the tests read deltas of counters they own."""
    from cxxnet_tpu.obs import alerts as obs_alerts

    obs_alerts.reset()
    yield
    obs_alerts.reset()


def make_opts(**kw):
    base = dict(
        replicas=2, probe_period_s=0.1, probe_timeout_s=0.5,
        slow_probes=3, start_timeout_s=60.0, restart_backoff_s=0.2,
        restart_backoff_max_s=0.5, replica_inflight=16,
        dispatch_retries=2, dispatch_timeout_s=5.0)
    base.update(kw)
    return FleetOptions(**base)


def start_stub_fleet(opts, per_replica=None, extra=(), model_dir=None,
                    probe_by_hand=False):
    """ServingFleet over stub replicas, started and ready (no HTTP
    front door bound — tests drive ``fleet.router.route`` directly)."""
    fleet = ServingFleet(opts, spawn_fn=stub_spawn_fn(
        extra=extra, per_replica=per_replica), model_dir=model_dir)
    fleet.supervisor.start()
    deadline = time.monotonic() + 60.0
    # the probe loop sleeps a period before its first probe: a test that
    # keeps probes dormant makes the probes that find the stubs ready
    while probe_by_hand and time.monotonic() < deadline and len(
            fleet.supervisor.healthy()) < len(fleet.supervisor.replicas):
        fleet.supervisor.probe_once()
        time.sleep(0.05)
    if not fleet.supervisor.wait_ready(timeout_s=60.0):
        snaps = [r.snapshot() for r in fleet.supervisor.replicas]
        fleet.close(drain_timeout_s=0.0)
        raise AssertionError(f"stub fleet never became ready: {snaps}")
    return fleet


# ----------------------------------------------------------------------
# config surface
def test_fleet_options_from_cfg():
    o = FleetOptions.from_cfg([
        ("replicas", "3"), ("fleet_probe_period_s", "0.5"),
        ("fleet_slow_probes", "5"), ("fleet_replica_inflight", "8"),
        ("fleet_batch_shed_ratio", "0.75"), ("canary", "int8"),
        ("canary_replicas", "1"), ("canary_slice", "0.2"),
        ("canary_min_agreement", "0.95"),
    ])
    assert (o.replicas, o.slow_probes, o.replica_inflight) == (3, 5, 8)
    assert o.batch_shed_ratio == 0.75
    assert o.canary == "int8" and o.canary_slice == 0.2

    # "off" spellings disarm the canary
    assert FleetOptions.from_cfg([("canary", "0")]).canary == ""
    assert FleetOptions.from_cfg([("canary", "off")]).canary == ""

    with pytest.raises(ValueError, match="replicas must be"):
        FleetOptions.from_cfg([("replicas", "0")])
    with pytest.raises(ValueError, match="batch_shed_ratio"):
        FleetOptions.from_cfg([("fleet_batch_shed_ratio", "0")])
    with pytest.raises(ValueError, match="at least one baseline"):
        FleetOptions.from_cfg([
            ("replicas", "2"), ("canary", "int8"),
            ("canary_replicas", "2")])
    with pytest.raises(ValueError, match="canary_slice"):
        FleetOptions.from_cfg([
            ("replicas", "3"), ("canary", "int8"),
            ("canary_slice", "1.5")])


def test_cli_spawn_fn_override_passthrough(monkeypatch):
    """Replica children inherit the fleet's CLI overrides: only the
    fleet-controlling keys are pinned.  A `quant=` override passes
    through to every child when no canary is armed (a fleet launched
    with quant=int8 must not silently serve f32); with a canary armed,
    the canary controller owns per-role precision instead."""
    import subprocess

    from cxxnet_tpu.serve import fleet as fleet_mod

    captured = []
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda cmd, **kw: captured.append(cmd) or object())
    overrides = ["quant=int8", "alert=slow:m:>:1", "replicas=5",
                 "serve_port=1234", "batch_timeout_ms=1"]

    spawn = fleet_mod.cli_spawn_fn("net.conf", overrides,
                                   host="127.0.0.1",
                                   opts=make_opts(replicas=2))
    spawn(fleet_mod.Replica(0, 7001))
    cmd = captured[-1]
    assert "quant=int8" in cmd and "alert=slow:m:>:1" in cmd
    assert "batch_timeout_ms=1" in cmd
    # fleet-controlling keys pinned: single-engine child on ITS port
    assert "replicas=1" in cmd and "replicas=5" not in cmd
    assert "serve_port=7001" in cmd and "serve_port=1234" not in cmd

    canary_opts = make_opts(replicas=3, canary="int8",
                            canary_replicas=1)
    spawn = fleet_mod.cli_spawn_fn("net.conf", overrides,
                                   host="127.0.0.1", opts=canary_opts)
    spawn(fleet_mod.Replica(0, 7002, role="serve"))
    base_cmd = captured[-1]
    spawn(fleet_mod.Replica(2, 7003, role="canary"))
    canary_cmd = captured[-1]
    # per-role precision: baseline pinned f32, canary quantized — the
    # user's quant= override yields to the comparison legs
    assert "quant=0" in base_cmd and "quant=int8" not in base_cmd
    assert "quant=int8" in canary_cmd
    assert "alert=slow:m:>:1" in canary_cmd  # alerts still pass through


# ----------------------------------------------------------------------
# admission control
def test_admission_priority_ordering_unit():
    """The shed order, deterministically: batch 429s first (above the
    shed ratio), interactive holds until the full capacity bound, and
    capacity scales with replicas in rotation.  admit() is atomic —
    every None return RESERVES a slot (check and reservation under one
    lock), so concurrent arrivals can never overshoot the bound."""
    opts = make_opts(replica_inflight=10, batch_shed_ratio=0.5)
    fleet = ServingFleet(opts, spawn_fn=None)  # external mode: no procs
    try:
        sup = fleet.supervisor
        r0 = sup.add_replica()
        r0.state = "healthy"
        router = fleet.router
        assert router.capacity() == 10

        for _ in range(5):  # admit to the shed ratio: 5/10 in flight
            assert router.admit("interactive") is None
        assert router.admit("batch") is not None      # batch sheds...
        assert router.admit("interactive") is None    # ...interactive holds

        for _ in range(4):  # fill to capacity: 10/10
            assert router.admit("interactive") is None
        assert "at capacity" in router.admit("interactive")
        assert router.admit("batch") is not None
        assert router.stats.inflight == 10  # sheds reserved nothing

        # capacity shrinks/grows with the rotation: a second healthy
        # replica doubles the bound, so 10 in flight admits again
        r1 = sup.add_replica()
        r1.state = "healthy"
        assert router.capacity() == 20
        assert router.admit("interactive") is None
        assert router.admit("batch") is not None      # 11/20 >= 0.5 still
        for _ in range(11):
            router.stats.leave()
        assert router.admit("batch") is None          # 0/20: pressure gone
        router.stats.leave()
        # arrivals (shed included) and sheds both accounted
        assert router.stats.requests["interactive"] == 12
        assert router.stats.requests["batch"] == 4
        assert router.stats.shed["batch"] == 3
        assert router.stats.shed["interactive"] == 1
    finally:
        fleet.close(drain_timeout_s=0.0)


def test_admission_batch_sheds_first_saturated():
    """End to end under a genuinely saturated queue: slow replicas hold
    the one capacity slot, a batch arrival 429s while an interactive
    arrival at the same occupancy is still served."""
    opts = make_opts(replicas=2, replica_inflight=1,
                     batch_shed_ratio=0.5, dispatch_timeout_s=10.0)
    fleet = start_stub_fleet(opts, extra=("--delay-ms", "600"))
    try:
        results = {}

        def bg(name, priority):
            results[name] = fleet.router.route(
                "/predict", {"data": [[0.1] * 4]}, priority=priority)

        t1 = threading.Thread(target=bg, args=("first", "interactive"))
        t1.start()
        time.sleep(0.2)  # first request is now in flight (1/2 slots)
        status_batch, body_batch = fleet.router.route(
            "/predict", {"data": [[0.1] * 4]}, priority="batch")
        assert status_batch == 429, body_batch
        assert "batch shed" in body_batch["error"]
        t2 = threading.Thread(target=bg, args=("second", "interactive"))
        t2.start()
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert results["first"][0] == 200
        assert results["second"][0] == 200  # same occupancy, admitted
        assert fleet.router.stats.shed["batch"] == 1
        assert fleet.router.stats.shed["interactive"] == 0
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# deadline budget
def test_deadline_budget_split_route_and_execute():
    opts = make_opts(replicas=1, dispatch_retries=0)
    fleet = start_stub_fleet(opts, extra=("--delay-ms", "100"))
    try:
        # 1. the replica sees only the REMAINING budget: the stub echoes
        # the forwarded deadline_ms, which must be strictly below what
        # the client sent (routing drew from the same budget)
        status, body = fleet.router.route(
            "/predict", {"data": [[0.1] * 4], "deadline_ms": 10000})
        assert status == 200
        assert 0 < body["deadline_ms"] < 10000

        # 2. execute share exhausted: the replica's own deadline check
        # 504s (the stub's delay exceeds the remaining budget) and the
        # router relays it — not a retry, not a 500
        status, body = fleet.router.route(
            "/predict", {"data": [[0.1] * 4], "deadline_ms": 50})
        assert status == 504, body

        # 3. route share exhausted: a budget too small to ever reach a
        # replica 504s locally, before any dispatch
        dispatched_before = fleet.supervisor.replicas[0].dispatched
        status, body = fleet.router.route(
            "/predict", {"data": [[0.1] * 4], "deadline_ms": 1e-4})
        assert status == 504
        assert "before a replica" in body["error"]

        # a non-numeric deadline is a client error (400), matching the
        # single-engine server — never a 500
        status, body = fleet.router.route(
            "/predict", {"data": [[0.1] * 4], "deadline_ms": "abc"})
        assert status == 400 and "deadline_ms" in body["error"]
        assert fleet.supervisor.replicas[0].dispatched == dispatched_before
        assert fleet.router.stats.expired == 1
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# the k-of-N availability invariant
def test_kill_one_of_three_zero_nonshed_failures():
    """SIGKILL 1 of 3 replicas under sustained concurrent load: every
    request still succeeds (failover + ejection), the fleet /healthz
    degrades while capacity is down, and the supervisor restarts the
    dead replica within its backoff budget."""
    opts = make_opts(replicas=3, probe_period_s=0.1, slow_probes=2,
                     probe_timeout_s=0.4, restart_backoff_s=0.2)
    fleet = start_stub_fleet(opts)
    try:
        statuses = []
        stop = threading.Event()
        lock = threading.Lock()

        def loader():
            while not stop.is_set():
                s, body = fleet.router.route(
                    "/predict", {"data": [[0.2] * 4]})
                with lock:
                    statuses.append((s, body if s != 200 else None))
                time.sleep(0.01)

        loaders = [threading.Thread(target=loader) for _ in range(4)]
        for t in loaders:
            t.start()
        time.sleep(0.5)

        victim = fleet.supervisor.replicas[1]
        victim.proc.kill()  # SIGKILL, mid-load
        degraded_seen = False
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            h = fleet.healthz()
            if h["status"] != "ok":
                degraded_seen = True
            if (degraded_seen and victim.restarts >= 1
                    and victim.state == "healthy"):
                break
            time.sleep(0.05)
        time.sleep(0.3)  # keep load on the restored rotation briefly
        stop.set()
        for t in loaders:
            t.join(timeout=30)

        assert degraded_seen  # the front door reported the lost capacity
        assert victim.restarts >= 1 and victim.state == "healthy"
        assert fleet.supervisor.last_restart_wall_s > 0
        bad = [(s, b) for s, b in statuses if s != 200]
        assert not bad, f"{len(bad)} non-200 of {len(statuses)}: {bad[:5]}"
        assert len(statuses) > 50  # the load was real
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# integrity quarantine (eject WITHOUT killing, readmit on clean canary)
def test_integrity_quarantine_ejects_without_kill_then_readmits():
    """A replica whose golden canary fails (healthz reason
    ``integrity_failed``) must leave the rotation but keep its process:
    a restart would land on the same possibly-bad device, and the
    still-running canary is what readmits it after a clean score."""
    from cxxnet_tpu.obs import events as obs_events

    opts = make_opts(replicas=3, probe_period_s=0.1)
    fleet = start_stub_fleet(opts)
    try:
        victim = fleet.supervisor.replicas[1]
        pid_before = victim.pid
        restarts_before = victim.restarts

        def stub_post(path, obj):
            req = urllib.request.Request(
                f"http://127.0.0.1:{victim.port}{path}",
                data=json.dumps(obj).encode("utf-8"),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.loads(r.read().decode("utf-8"))

        # 1. degrade the replica's canary -> supervisor quarantines it
        assert stub_post("/integrity", {"failed": True})["failed"]
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            fleet.supervisor.probe_once()
            if victim.state == "quarantined":
                break
            time.sleep(0.05)
        assert victim.state == "quarantined"
        assert victim not in fleet.supervisor.rotation()
        assert "integrity_failed" in victim.reasons
        # the fleet front door stays up on the two clean replicas
        s, body = fleet.router.route("/predict", {"data": [[0.2] * 4]})
        assert s == 200, body
        # ejected, NOT killed: same process, no restart, still answering
        assert victim.pid == pid_before
        assert victim.restarts == restarts_before
        assert victim.proc.poll() is None
        assert [e for e in obs_events.recent(
            200, kind="fleet.replica_quarantined")
            if e.get("replica") == victim.idx]

        # 2. canary comes back clean -> readmitted, same process
        assert not stub_post("/integrity", {"failed": False})["failed"]
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            fleet.supervisor.probe_once()
            if victim.state == "healthy":
                break
            time.sleep(0.05)
        assert victim.state == "healthy"
        assert victim in fleet.supervisor.rotation()
        assert victim.pid == pid_before
        assert victim.restarts == restarts_before
        assert [e for e in obs_events.recent(
            200, kind="fleet.replica_readmitted")
            if e.get("replica") == victim.idx]
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# rolling reload
def test_rolling_reload_walks_rotation(tmp_path):
    round_file = tmp_path / "round.txt"
    round_file.write_text("1")
    opts = make_opts(replicas=2)
    fleet = start_stub_fleet(
        opts, extra=("--round-file", str(round_file)))
    try:
        assert fleet.healthz()["round"] == 1
        round_file.write_text("2")
        out = fleet.rolling_reload(target_round=2)
        assert not out["aborted"]
        assert [x["ok"] for x in out["replicas"]] == [True, True]
        assert [x["swapped"] for x in out["replicas"]] == [True, True]
        assert fleet.healthz()["round"] == 2
        # reload again with no new round: a clean noop, breaker closed
        out = fleet.rolling_reload()
        assert [x["swapped"] for x in out["replicas"]] == [False, False]
        assert fleet.reload_breaker.state == "closed"
    finally:
        fleet.close(drain_timeout_s=0.0)


def test_rolling_reload_breaker_aborts_rollout():
    """A rollout that keeps failing stops: the breaker opens and the
    remaining replicas are left serving the old model (aborted result,
    not an emptied rotation)."""
    opts = make_opts(replicas=2, probe_period_s=30.0,  # probes dormant
                     reload_breaker_threshold=1, reload_timeout_s=2.0)
    fleet = start_stub_fleet(opts, probe_by_hand=True)
    try:
        # replica 0's process dies; the supervisor (probing every 30 s)
        # has not noticed, so the rollout hits it first and fails
        fleet.supervisor.replicas[0].proc.kill()
        time.sleep(0.2)
        out = fleet.rolling_reload(target_round=9)
        assert out["aborted"] is True
        assert len(out["replicas"]) == 1  # replica 1 never touched
        assert out["replicas"][0]["ok"] is False
        assert fleet.reload_breaker.state in ("open", "half-open")
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# canary lifecycle
def _canary_fleet(tmp_path, disagree):
    opts = make_opts(
        replicas=3, canary="int8", canary_replicas=1,
        canary_slice=0.25, canary_sample=0.8, canary_min_requests=10,
        canary_min_agreement=0.99, canary_decision_period_s=999.0)

    def per_replica(r):
        if r.role == "canary":
            return ("--quant", "int8", "--disagree", str(disagree))
        return ()

    fleet = start_stub_fleet(opts, per_replica=per_replica,
                             model_dir=str(tmp_path))
    fleet.canary._arm_rule()  # rule only; decisions driven by the test
    return fleet


def _drive_until_compared(fleet, n, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    i = 0
    while fleet.canary.compared < n and time.monotonic() < deadline:
        i += 1
        s, _ = fleet.router.route(
            "/predict", {"data": [[0.01 * (i % 50)] * 4]})
        assert s == 200
        time.sleep(0.005)
    assert fleet.canary.compared >= n, (
        f"only {fleet.canary.compared} rows compared after "
        f"{i} requests")


def test_canary_promotes_and_flips_pointer(tmp_path):
    m = fleet_metrics()
    promotes0 = m.canary_total.labels(decision="promote").value
    fleet = _canary_fleet(tmp_path, disagree=0)
    try:
        _drive_until_compared(fleet, fleet.opts.canary_min_requests)
        assert fleet.canary.decide() == "promote"
        assert fleet.canary.state == "promoted"
        assert fleet.canary.agreement() == 1.0
        ptr = ckpt.read_publish_pointer(str(tmp_path))
        assert ptr is not None and ptr["round"] == 1
        assert ptr["metric"]["scheme"] == "int8"
        assert m.canary_total.labels(
            decision="promote").value == promotes0 + 1
        # full weight: a promoted canary is back in the baseline pool
        pool_roles = {r.role for r in (
            fleet.router.pick_replica() for _ in range(8)) if r}
        assert "canary" in {r.role for r in fleet.supervisor.rotation()}
        assert pool_roles  # dispatchable at all
        assert fleet.router._canary_live() is False
    finally:
        fleet.close(drain_timeout_s=0.0)


def test_canary_rollback_through_alert_and_pointer(tmp_path):
    """The rollback acceptance: an injected-disagreement canary is
    detected via the shared metric families, the ``canary_agreement``
    alert fires, the decision rolls back through the publish pointer,
    and the canary replicas relaunch as plain f32 members."""
    from cxxnet_tpu.obs import alerts as obs_alerts

    m = fleet_metrics()
    rollbacks0 = m.canary_total.labels(decision="rollback").value
    fleet = _canary_fleet(tmp_path, disagree=7)
    try:
        canary_replica = fleet.canary.canaries()[0]
        _drive_until_compared(fleet, fleet.opts.canary_min_requests)
        assert fleet.canary.agreement() < 0.99
        assert fleet.canary.decide() == "rollback"
        assert fleet.canary.state == "rolled_back"
        assert "canary_agreement firing" in fleet.canary.decision_reason

        # the pointer records the BASELINE as blessed
        ptr = ckpt.read_publish_pointer(str(tmp_path))
        assert ptr is not None and ptr["round"] == 1
        assert m.canary_total.labels(
            decision="rollback").value == rollbacks0 + 1

        # the canary replica was relaunched as a plain serving member
        assert canary_replica.role == "serve"
        assert canary_replica.restarts >= 1
        assert canary_replica.down_reason == "canary_rollback"

        # the trigger gauge was cleared: /alertz stops firing for a
        # comparison that no longer exists
        ev = obs_alerts.evaluator()
        ev.evaluate_once()
        assert "canary_agreement" not in ev.firing()
    finally:
        fleet.close(drain_timeout_s=0.0)


def test_a_probe_sweep_inside_a_deliberate_restart_records_no_crash(
        monkeypatch):
    """What made the rollback test fail one run in some: the probe loop
    (every 0.1 s here) sweeping between ``restart_replica``'s kill and
    its spawn saw a dead process, wrote ``crash`` over the restart's
    own reason and scheduled a second restart.  Held open for three
    probe periods, the window is hit every time."""
    from cxxnet_tpu.serve.fleet import ReplicaSupervisor

    kill = ReplicaSupervisor._kill

    def slow_kill(self, r):
        kill(self, r)
        time.sleep(0.35)

    m = fleet_metrics()
    crashes0 = m.restarts.labels(reason="crash").value
    fleet = start_stub_fleet(make_opts(replicas=2))
    try:
        r = fleet.supervisor.replicas[0]
        monkeypatch.setattr(ReplicaSupervisor, "_kill", slow_kill)
        fleet.supervisor.restart_replica(r, reason="canary_rollback")
        monkeypatch.undo()
        assert r.down_reason == "canary_rollback" and r.restarts == 1
        assert fleet.supervisor.wait_ready(timeout_s=30.0)
        assert r.restarts == 1  # and no second one of the sweep's own
        assert m.restarts.labels(reason="crash").value == crashes0
    finally:
        fleet.close(drain_timeout_s=0.0)


# ----------------------------------------------------------------------
# HTTP front door
def test_router_http_surface(tmp_path):
    opts = make_opts(replicas=2)
    fleet = ServingFleet(opts, spawn_fn=stub_spawn_fn(), port=0)
    httpd = fleet.start()
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{httpd.server_port}"
    try:
        def post(path, obj, headers=()):
            req = urllib.request.Request(
                base + path, data=json.dumps(obj).encode("utf-8"),
                headers={"Content-Type": "application/json",
                         **dict(headers)})
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        status, body = post("/predict", {"data": [[0.3] * 4]})
        assert status == 200 and body["pred"]

        # priority via header, and the classifier rejects junk
        status, _ = post("/predict", {"data": [[0.3] * 4]},
                         headers=[("X-Priority", "batch")])
        assert status == 200
        assert fleet.router.stats.requests["batch"] == 1
        status, body = post("/predict", {"data": [[0.3] * 4],
                                         "priority": "bulk"})
        assert status == 400 and "unknown priority" in body["error"]

        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            h = json.loads(r.read())
        assert h["fleet"] is True and h["status"] == "ok"
        assert h["replicas"]["healthy"] == 2
        assert isinstance(h["reasons"], list)
        # the aggregate healthz passes the machine-readable shape check
        hz = tmp_path / "healthz.json"
        hz.write_text(json.dumps(h))
        from conftest import run_cli

        r = run_cli([os.path.join(REPO, "tools", "obs_dump.py"),
                     "--check", "--healthz", str(hz)],
                    cwd=str(tmp_path), module=False)
        assert r.returncode == 0, r.stdout + r.stderr

        with urllib.request.urlopen(base + "/statsz", timeout=10) as r:
            st = json.loads(r.read())
        assert len(st["replicas"]) == 2
        assert st["requests"]["interactive"] >= 1
    finally:
        httpd.shutdown()
        thread.join(timeout=10)
        fleet.close(drain_timeout_s=0.0)
