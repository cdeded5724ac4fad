"""Serving-fleet router (tpu_resnet/serve/router.py; docs/SERVING.md
"Serving fleet") + the fleet satellites (loadgen scenarios, supervise
fleet mode, perfwatch ingestion).

Three layers, mirroring the subsystem's own:

- pure units: circuit-breaker state machine (injectable clock),
  discovery parsing, scenario qps schedules, loadgen failure
  classes, supervise fleet/stop-code policies — no sockets;
- in-process fleet: real Router + two PredictServers over FakeBackends
  (millisecond startup): spread, passive-failure failover with zero
  client errors, probe-driven exclusion/readmission, deadline budget,
  lane shedding with Retry-After, hedged sends, admin drain, the
  route_events.jsonl span lane;
- slow tier: ``doctor --fleet-probe`` — the subprocess replica-kill +
  rolling-drain acceptance drill (exit codes, trace lanes, DOCTOR_JSON).
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_resnet.config import load_config
from tpu_resnet.serve.batcher import LANES, MicroBatcher
from tpu_resnet.serve.router import (CircuitBreaker, Router,
                                     discover_replicas, read_route_port,
                                     request_drain, write_route_discovery)
from tpu_resnet.serve.server import PredictServer, write_discovery

SHAPE = (8, 8, 3)


# ------------------------------------------------------------ pure units
def test_circuit_breaker_state_machine():
    clock = [0.0]
    b = CircuitBreaker(fail_threshold=2, open_secs=5.0,
                       clock=lambda: clock[0])
    assert b.state == b.CLOSED
    b.record_failure()
    assert b.state == b.CLOSED          # one strike is not an outage
    b.record_failure()
    assert b.state == b.OPEN            # threshold met -> excluded
    clock[0] = 4.9
    assert b.state == b.OPEN            # still holding
    clock[0] = 5.1
    assert b.state == b.HALF_OPEN       # one trial allowed
    b.record_failure()
    assert b.state == b.OPEN            # trial failed: fresh hold
    clock[0] = 10.2
    assert b.state == b.HALF_OPEN
    b.record_success()
    assert b.state == b.CLOSED and b.closed
    b.record_failure()
    assert b.state == b.CLOSED          # success reset the streak


def test_discovery_parses_fleet_and_skips_torn_files(tmp_path):
    d = str(tmp_path)
    write_discovery(d, 8001, run_id="rid1", name="r0")
    write_discovery(d, 8002, run_id="rid1", name="r1")
    write_discovery(d, 8003, run_id="rid1")          # bare serve.json
    (tmp_path / "serve-torn.json").write_text('{"port": 80')  # mid-write
    (tmp_path / "serve_other.txt").write_text("not discovery")
    recs = {r["name"]: r for r in discover_replicas(d)}
    assert set(recs) == {"r0", "r1", "default"}
    assert recs["r0"]["port"] == 8001 and recs["r0"]["run_id"] == "rid1"
    assert recs["default"]["port"] == 8003
    assert all(r["pid"] == os.getpid() for r in recs.values())


def test_route_discovery_roundtrip(tmp_path):
    assert read_route_port(str(tmp_path)) is None
    write_route_discovery(str(tmp_path), 8500, run_id="rid")
    assert read_route_port(str(tmp_path)) == 8500
    with open(tmp_path / "route.json") as f:
        rec = json.load(f)
    assert rec["pid"] == os.getpid() and rec["run_id"] == "rid"


def test_loadgen_qps_schedules():
    from tools.loadgen import qps_factor

    # steady is flat
    assert all(qps_factor("steady", f) == 1.0 for f in (0, 0.5, 1))
    # burst alternates calm/burst quarters
    assert qps_factor("burst", 0.1) == 0.25
    assert qps_factor("burst", 0.3) == 2.0
    assert qps_factor("burst", 0.6) == 0.25
    assert qps_factor("burst", 0.9) == 2.0
    # ramp: trough -> peak -> trough (diurnal half-sine)
    assert qps_factor("ramp", 0.0) == pytest.approx(0.2)
    assert qps_factor("ramp", 0.5) == pytest.approx(1.0)
    assert qps_factor("ramp", 1.0) == pytest.approx(0.2, abs=1e-9)
    assert qps_factor("ramp", 0.25) > qps_factor("ramp", 0.05)


def test_loadgen_fire_classifies_failures():
    """connect-refused and a slow reply are DIFFERENT fleet bugs — the
    satellite contract that they land in distinct result fields."""
    from tools.loadgen import _fire

    # nothing listening -> connect failure (-1)
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()  # port now known-dead
    assert _fire(f"http://127.0.0.1:{port}", b"x", "1,8,8,3", 2.0) == -1

    # accepts but never answers -> client-side timeout (-2)
    silent = socket.socket()
    silent.bind(("127.0.0.1", 0))
    silent.listen(1)
    try:
        assert _fire(f"http://127.0.0.1:{silent.getsockname()[1]}",
                     b"x", "1,8,8,3", 0.5) == -2
    finally:
        silent.close()


def test_supervise_fleet_substitutes_index_and_policies():
    from tools.supervise import supervise_fleet

    calls = []
    lock = threading.Lock()

    def fake_run(cmd):
        with lock:
            calls.append(list(cmd))
        return 0

    rc = supervise_fleet(["serve", "serve.replica_name=r{i}"], 3,
                         run=fake_run, sleep=lambda s: None)
    assert rc == 0
    names = sorted(c[1] for c in calls)
    assert names == ["serve.replica_name=r0", "serve.replica_name=r1",
                     "serve.replica_name=r2"]


def test_supervise_stop_codes_end_supervision_without_restart():
    """Exit 3 (colocation admission denied) must NOT be retried on the
    same host — the placement layer owns the next move."""
    from tools.supervise import supervise

    rcs = iter([3])
    runs = []

    def fake_run(cmd):
        runs.append(cmd)
        return next(rcs)

    rc = supervise(["serve"], stop_codes=(3,), run=fake_run,
                   sleep=lambda s: None)
    assert rc == 3 and len(runs) == 1  # no restart attempt


def test_supervise_restart_clean_brings_drained_replicas_back():
    """Rolling-upgrade fleet semantics: a replica's exit 0 means it was
    DRAINED (route --drain) and must come back so the router readmits
    it — restart_clean=True restarts it without crash backoff; the
    default ('0 = done', trainer semantics) is unchanged."""
    from tools.supervise import supervise

    runs, sleeps = [], []
    rcs = iter([0, 0, 3])  # drained, drained again, then placed elsewhere

    def fake_run(cmd):
        runs.append(cmd)
        return next(rcs)

    rc = supervise(["serve"], restart_clean=True, stop_codes=(3,),
                   preempt_delay=0.5, run=fake_run,
                   sleep=sleeps.append)
    assert rc == 3 and len(runs) == 3      # both clean exits restarted
    assert sleeps == [0.5, 0.5]            # preempt-style fixed delay


def test_batcher_lane_priority():
    """Interactive work coalesces ahead of queued batch work even when
    the batch lane enqueued first."""
    entered, release = threading.Event(), threading.Event()
    order = []

    def infer(images):
        if not entered.is_set():
            entered.set()
            release.wait(10.0)
        else:
            order.append(int(images[0, 0, 0, 0]))
        return np.zeros((images.shape[0], 7), np.float32)

    b = MicroBatcher(infer, SHAPE, max_batch=1, max_wait_ms=1.0,
                     max_queue=16)
    b.start()
    first = b.submit(_img(0))
    assert entered.wait(5.0)            # worker pinned mid-batch
    got = [b.submit(_img(1), lane="batch"),
           b.submit(_img(2), lane="batch"),
           b.submit(_img(3), lane="interactive")]
    release.set()
    for r in [first] + got:
        r.wait(5.0)
    assert order == [3, 1, 2]           # interactive jumped the queue
    stats = b.stats()
    assert stats["lane_interactive"] == 2 and stats["lane_batch"] == 2
    with pytest.raises(ValueError):
        b.submit(_img(0), lane="bulk")
    assert b.drain(5.0)
    assert set(LANES) == {"interactive", "batch"}


# ------------------------------------------------------ in-process fleet
def _img(px, n=1):
    imgs = np.zeros((n,) + SHAPE, np.uint8)
    imgs[:, 0, 0, 0] = px
    return imgs


class FakeBackend:
    def __init__(self, image_size=8, num_classes=7, delay=0.0):
        self.image_size = image_size
        self.num_classes = num_classes
        self.fixed_batch = 0
        self.model_step = 7
        self.reloads = 0
        self.delay = delay
        self.batches = 0

    def constrain_buckets(self, buckets):
        return tuple(buckets)

    def warmup(self, buckets):
        pass

    def infer(self, images):
        self.batches += 1
        if self.delay:
            time.sleep(self.delay)
        n = images.shape[0]
        logits = np.zeros((n, self.num_classes), np.float32)
        logits[np.arange(n), images[:, 0, 0, 0] % self.num_classes] = 1.0
        return logits

    def maybe_reload(self):
        return False


def _mk_replica(train_dir, name, delay=0.0):
    cfg = load_config()
    cfg.serve.port = 0
    cfg.serve.host = "127.0.0.1"
    cfg.serve.max_batch = 8
    cfg.serve.max_wait_ms = 5.0
    cfg.serve.reload_interval_secs = 0
    cfg.serve.replica_name = name
    cfg.train.train_dir = train_dir
    backend = FakeBackend(delay=delay)
    srv = PredictServer(cfg, backend=backend).start()
    write_discovery(train_dir, srv.port, name=name)
    return srv


def _mk_router(train_dir, **route_overrides):
    cfg = load_config()
    cfg.route.host = "127.0.0.1"
    cfg.route.discover_dir = train_dir
    cfg.route.probe_interval_secs = 0.15
    cfg.route.probe_timeout_secs = 2.0
    cfg.route.fail_threshold = 1
    cfg.route.open_secs = 0.5
    for k, v in route_overrides.items():
        setattr(cfg.route, k, v)
    return Router(cfg)


def _post(port, body, shape, headers=None, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict", data=body,
        headers={"Content-Type": "application/octet-stream",
                 "X-Shape": shape, **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture()
def fleet(tmp_path):
    d = str(tmp_path)
    from tpu_resnet.obs.manifest import ensure_run_id

    rid = ensure_run_id(d)
    replicas = [_mk_replica(d, "r0"), _mk_replica(d, "r1")]
    router = _mk_router(d).start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        # healthy AND probed: image_shape arrives with the first /info
        # probe round, which loadgen-through-the-router needs.
        if sum(1 for r in router.replicas()
               if r.healthy and r.image_shape) == 2:
            break
        time.sleep(0.05)
    yield router, replicas, d, rid
    router.close()
    for srv in replicas:
        srv.batcher.drain(2.0)
        srv.close()


def test_router_spreads_and_reports(fleet):
    router, (s0, s1), d, rid = fleet
    assert router.run_id == rid  # correlated from the fleet's train_dir
    for i in range(12):
        code, out, headers = _post(router.port, _img(i % 7).tobytes(),
                                   "1,8,8,3")
        assert code == 200 and out["predictions"] == [i % 7]
        assert headers.get("X-Replica") in ("r0", "r1")
    # both replicas saw work (least-loaded + rr tiebreak spreads)
    assert s0.backend.batches > 0 and s1.backend.batches > 0
    code, health = _get(router.port, "/healthz")
    assert code == 200 and health["replicas_healthy"] == 2
    code, info = _get(router.port, "/info")
    assert info["counters"]["ok"] == 12
    assert info["image_shape"] == [8, 8, 3]
    # /metrics renders the route_* series
    with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/metrics", timeout=5) as r:
        text = r.read().decode()
    assert "tpu_resnet_route_requests_total" in text
    assert "tpu_resnet_route_latency_ms_bucket" in text


def test_failover_retry_covers_passive_death(fleet):
    """A replica that dies WITHOUT the prober noticing first: the
    in-flight connect failure must retry on the survivor — zero client
    errors, retries counter ticks, circuit opens."""
    router, (s0, s1), d, rid = fleet
    router._stop.set()          # freeze the prober: passive path only
    time.sleep(0.3)
    victim = s0
    victim.batcher.drain(2.0)
    victim.close()              # connection refused from now on
    ok = 0
    for i in range(30):
        code, out, _ = _post(router.port, _img(1).tobytes(), "1,8,8,3")
        assert code == 200, out
        ok += 1
    assert ok == 30
    with router._lock:
        counters = dict(router._counters)
    assert counters["retries"] >= 1          # the failover fired
    assert counters["replica_errors"] >= 1
    dead = next(r for r in router.replicas() if r.name == "r0")
    assert not dead.healthy                  # passive failure opened it


def test_probe_excludes_and_readmits(fleet):
    """Probe-driven exclusion within one interval; a replica that comes
    back (same port) is readmitted through half-open."""
    router, (s0, s1), d, rid = fleet
    s1.registry.mark_unhealthy("wedged for the drill")
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        r1 = next(r for r in router.replicas() if r.name == "r1")
        if not r1.healthy:
            break
        time.sleep(0.05)
    assert not r1.healthy
    # recovery: healthz healthy again -> half-open probe readmits
    s1.registry.clear_unhealthy()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if next(r for r in router.replicas() if r.name == "r1").healthy:
            break
        time.sleep(0.05)
    assert next(r for r in router.replicas() if r.name == "r1").healthy
    # the transitions landed as spans for the trace-export router lane
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.obs.trace import ROUTE_EVENTS_FILE

    router.spans.close()
    spans = load_spans(os.path.join(d, ROUTE_EVENTS_FILE))
    kinds = [s["span"] for s in spans]
    assert "replica_down" in kinds and "replica_up" in kinds
    assert all(s["run_id"] == rid for s in spans)


def test_deadline_budget_bounds_failover(tmp_path):
    """A hung fleet answers 504 at the client's deadline — the retry
    never blows the budget."""
    d = str(tmp_path)
    slow = _mk_replica(d, "slow", delay=5.0)
    router = _mk_router(d).start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not any(
                r.healthy for r in router.replicas()):
            time.sleep(0.05)
        t0 = time.monotonic()
        code, out, _ = _post(router.port, _img(0).tobytes(), "1,8,8,3",
                             headers={"X-Deadline-Ms": "400"})
        elapsed = time.monotonic() - t0
        assert code == 504 and "deadline" in out["error"]
        assert elapsed < 3.0  # nowhere near the 5s infer
    finally:
        router.close()
        slow.batcher._stop.set()
        slow.close()


def test_no_healthy_replicas_is_503_retryable(tmp_path):
    router = _mk_router(str(tmp_path)).start()
    try:
        code, out, headers = _post(router.port, _img(0).tobytes(),
                                   "1,8,8,3")
        assert code == 503 and out["retryable"]
        assert "Retry-After" in headers
    finally:
        router.close()


def _prime_ring(router, values):
    with router._lat_lock:
        router._latencies[:] = values
        router._last_latency_at = router._clock()  # signal is fresh
    router._p_cache = (0.0, 0.0, 0.0)              # bust the cache


def test_slo_shedding_batch_lane_first(fleet):
    router, replicas, d, rid = fleet
    router.cfg.route.slo_ms = 50.0
    router.cfg.route.shed_hard_factor = 100.0  # interactive never sheds
    _prime_ring(router, [200.0] * 64)          # rolling p99 over SLO
    code, out, headers = _post(router.port, _img(0).tobytes(), "1,8,8,3",
                               headers={"X-Lane": "batch"})
    assert code == 429 and out["lane"] == "batch"
    assert headers.get("Retry-After") == "1"
    # interactive still admitted below the hard threshold
    code, out, _ = _post(router.port, _img(2).tobytes(), "1,8,8,3")
    assert code == 200
    # past slo*hard_factor the interactive lane sheds too
    router.cfg.route.shed_hard_factor = 1.5
    _prime_ring(router, [200.0] * 64)
    code, out, _ = _post(router.port, _img(2).tobytes(), "1,8,8,3")
    assert code == 429 and out["lane"] == "interactive"
    with router._lock:
        c = dict(router._counters)
    assert c["shed_batch"] == 1 and c["shed_interactive"] == 1


def test_slo_shed_releases_when_signal_goes_stale(fleet):
    """A batch-only workload being 100% shed records no new latencies —
    the stale ring must release the shed instead of latching forever."""
    router, replicas, d, rid = fleet
    router.cfg.route.slo_ms = 50.0
    _prime_ring(router, [200.0] * 64)
    code, out, _ = _post(router.port, _img(1).tobytes(), "1,8,8,3",
                         headers={"X-Lane": "batch"})
    assert code == 429                         # shedding engaged
    with router._lat_lock:                     # signal goes stale
        router._last_latency_at = router._clock() - 10.0
    code, out, _ = _post(router.port, _img(1).tobytes(), "1,8,8,3",
                         headers={"X-Lane": "batch"})
    assert code == 200                         # released, admitted
    with router._lat_lock:
        assert len(router._latencies) <= 2     # ring was reset


def test_hedged_send_wins_on_slow_primary(tmp_path):
    d = str(tmp_path)
    slow = _mk_replica(d, "slow", delay=1.0)
    fast = _mk_replica(d, "fast")
    router = _mk_router(d, hedge_ms=60.0).start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and sum(
                1 for r in router.replicas() if r.healthy) < 2:
            time.sleep(0.05)
        r_slow = next(r for r in router.replicas() if r.name == "slow")
        t0 = time.monotonic()
        used = []
        status, payload, _, answered = router._attempt(
            r_slow, _img(4).tobytes(),
            {"Content-Type": "application/octet-stream",
             "X-Shape": "1,8,8,3"}, remaining=10.0, exclude=(),
            used=used)
        elapsed = time.monotonic() - t0
        assert status == 200
        assert json.loads(payload)["predictions"] == [4]
        assert elapsed < 0.9            # hedge answered, not the primary
        assert answered.name == "fast"  # attribution goes to the winner
        assert set(used) == {"slow", "fast"}  # both legs join exclusion
        with router._lock:
            c = dict(router._counters)
        assert c["hedges"] == 1 and c["hedge_wins"] == 1
    finally:
        router.close()
        fast.batcher.drain(2.0)
        fast.close()
        slow.batcher._stop.set()
        slow.close()


def test_admin_drain_excludes_and_spans(fleet):
    """kill=False path (in-process replicas share our pid): exclusion +
    quiesce + route_drain span; the survivor keeps answering."""
    router, (s0, s1), d, rid = fleet
    result = router.drain_replica("r0", kill=False, timeout=5.0)
    assert result["ok"] and result["replica"] == "r0"
    assert result["inflight_at_signal"] == 0
    assert not next(r for r in router.replicas()
                    if r.name == "r0").healthy
    for i in range(6):
        code, _, headers = _post(router.port, _img(1).tobytes(),
                                 "1,8,8,3")
        assert code == 200 and headers.get("X-Replica") == "r1"
    # unknown replica is a structured error, not a 500
    code, out = _get(router.port, "/healthz")
    assert code == 200
    bad = request_drain(f"http://127.0.0.1:{router.port}", "nope")
    assert not bad["ok"] and "unknown replica" in bad["error"]
    router.spans.close()
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.obs.trace import ROUTE_EVENTS_FILE

    spans = load_spans(os.path.join(d, ROUTE_EVENTS_FILE))
    drain = next(s for s in spans if s["span"] == "route_drain")
    assert drain["replica"] == "r0" and drain["run_id"] == rid


def test_restarted_replica_re_resolved_from_discovery(fleet):
    """A replica that comes back on a NEW port (restart) is picked up by
    the discovery refresh within a probe round — fresh breaker, fresh
    url."""
    router, (s0, s1), d, rid = fleet
    old_url = next(r for r in router.replicas() if r.name == "r0").url
    s0.batcher.drain(2.0)
    s0.close()
    replacement = _mk_replica(d, "r0")  # new ephemeral port, same name
    try:
        deadline = time.monotonic() + 6
        ok = False
        while time.monotonic() < deadline:
            r0 = next(r for r in router.replicas() if r.name == "r0")
            if r0.url != old_url and r0.healthy:
                ok = True
                break
            time.sleep(0.1)
        assert ok, router.info()["replicas"]
        code, out, _ = _post(router.port, _img(5).tobytes(), "1,8,8,3")
        assert code == 200
    finally:
        replacement.batcher.drain(2.0)
        replacement.close()


# --------------------------------------------- loadgen scenario results
def test_loadgen_mixed_lane_scenario_reports_lanes(fleet):
    router, replicas, d, rid = fleet
    from tools.loadgen import run_load

    result = run_load(f"http://127.0.0.1:{router.port}", clients=4,
                      duration=1.2, scenario="mixed_lane")
    assert result["scenario"] == "mixed_lane"
    assert result["failed"] == 0 and result["timeouts"] == 0
    assert result["connect_failures"] == 0
    assert set(result["lanes"]) == {"interactive", "batch"}
    assert result["lanes"]["batch"]["requests_ok"] > 0
    assert result["router"]["replicas_healthy"] == 2
    # the sweep-shaped point perfwatch ingests
    (point,) = result["points"]
    assert point["id"] == "scenario=mixed_lane"
    assert point["status"] == "ok" and point["steps_per_sec"] > 0


def test_loadgen_scenario_points_ingested_by_perfwatch(fleet, tmp_path):
    router, replicas, d, rid = fleet
    import subprocess
    import sys

    from tools.loadgen import run_load

    out = tmp_path / "steady.json"
    result = run_load(f"http://127.0.0.1:{router.port}", clients=2,
                      duration=1.0, scenario="steady")
    out.write_text(json.dumps(result))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pw = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "perfwatch.py"),
         "--sweep", str(out)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=60)
    assert pw.returncode == 0, pw.stdout
    assert "sweep:scenario=steady" in pw.stdout


def test_loadgen_deadline_ms_counts_timeouts(tmp_path):
    """A hung replica + --deadline-ms: the run reports timeouts, not
    conflated 'failed', and the RESULT_JSON point gates as error."""
    d = str(tmp_path)
    slow = _mk_replica(d, "hung", delay=5.0)
    try:
        from tools.loadgen import run_load

        result = run_load(f"http://127.0.0.1:{slow.port}", clients=2,
                          duration=1.5, deadline_ms=300.0)
        assert result["timeouts"] > 0
        assert result["failed"] == 0 and result["connect_failures"] == 0
        assert result["points"][0]["status"] == "error"
        assert result["deadline_ms"] == 300.0
    finally:
        slow.batcher._stop.set()
        slow.close()


# ------------------------------------------------------------- slow tier
@pytest.mark.slow
def test_doctor_fleet_probe_contract():
    """The acceptance drill: 2 subprocess replicas + router, SIGKILL one
    mid-traffic (zero client failures, circuit opens), hot-reload on the
    survivor, rolling admin drain (replica exits 0), router exits 0, and
    the merged trace carries run_id-correlated router+replica lanes."""
    from tpu_resnet.tools.doctor import _check_fleet_probe

    out = _check_fleet_probe()
    assert out["ok"], out
    assert out["client_failures"] == 0 and out["requests_ok"] > 0
    assert out["excluded_in_sec"] is not None
    assert out["r1_rc"] == 0 and out["router_rc"] == 0
    assert out["drain"]["ok"] and out["drain"]["replica_gone"]


@pytest.mark.slow
def test_loadgen_replica_kill_scenario_end_to_end(tmp_path):
    """The headline chaos scenario driven through loadgen itself:
    in-process fleet, SIGKILL delivered to a subprocess replica... —
    covered at subprocess scale by the doctor probe; here the loadgen
    rolling_drain scenario drains an in-process fleet's replicas through
    the router admin endpoint with kill disabled per-replica pid absent
    (static-style), proving the scenario plumbing + RESULT_JSON shape."""
    d = str(tmp_path)
    from tpu_resnet.obs.manifest import ensure_run_id

    ensure_run_id(d)
    r0, r1 = _mk_replica(d, "r0"), _mk_replica(d, "r1")
    # strip pids from discovery so the drain path excludes-only (the
    # subprocess SIGTERM half is the doctor probe's job)
    for name in ("r0", "r1"):
        path = os.path.join(d, f"serve-{name}.json")
        with open(path) as f:
            rec = json.load(f)
        rec["pid"] = None
        with open(path, "w") as f:
            json.dump(rec, f)
    router = _mk_router(d).start()
    # In-process "supervisor": the real rolling drain SIGTERMs the
    # replica and supervise --fleet restarts it (probe readmits). With
    # in-process replicas nothing dies, so emulate the restart by
    # clearing the admin exclusion shortly after each drain.
    stop_supervisor = threading.Event()

    def supervisor():
        while not stop_supervisor.is_set():
            for r in router.replicas():
                if r.draining and r.inflight == 0:
                    time.sleep(0.3)   # the "restart" window
                    r.draining = False
            time.sleep(0.05)

    sup = threading.Thread(target=supervisor, daemon=True)
    sup.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and sum(
                1 for r in router.replicas()
                if r.healthy and r.image_shape) < 2:
            # healthy AND probed: run_load needs the router's /info to
            # forward the image_shape its probes learned
            time.sleep(0.05)
        from tools.loadgen import run_load

        result = run_load(f"http://127.0.0.1:{router.port}", clients=4,
                          duration=4.0, scenario="rolling_drain",
                          fleet_dir=d, drain_interval=1.0)
        assert result["failed"] == 0
        assert result["connect_failures"] == 0
        drains = result["chaos"]["drains"]
        assert [x["replica"] for x in drains] == ["r0", "r1"]
        assert all(x["ok"] for x in drains)
    finally:
        stop_supervisor.set()
        router.close()
        for srv in (r0, r1):
            srv.batcher.drain(2.0)
            srv.close()


def test_hung_replica_healthz_goes_stale_and_stays_excluded(tmp_path):
    """A wedged batcher stops ticking the serve heartbeat; with the
    serve-scoped staleness the replica's own /healthz flips 503 within
    seconds, so the router's half-open probe can NOT flap a hung
    replica back into rotation (the accept-then-hang drill)."""
    d = str(tmp_path)
    cfg = load_config()
    cfg.serve.port = 0
    cfg.serve.host = "127.0.0.1"
    cfg.serve.healthz_stale_sec = 0.4
    cfg.train.train_dir = d
    hang, release = threading.Event(), threading.Event()

    class HangingBackend(FakeBackend):
        def infer(self, images):
            if hang.is_set():
                release.wait(30.0)  # pinned: the heartbeat stops ticking
            return super().infer(images)

    srv = PredictServer(cfg, backend=HangingBackend()).start()
    write_discovery(d, srv.port, name="r0")
    router = _mk_router(d, open_secs=0.3).start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not any(
                r.healthy for r in router.replicas()):
            time.sleep(0.05)
        code, _, _ = _post(router.port, _img(1).tobytes(), "1,8,8,3")
        assert code == 200
        hang.set()
        srv.batcher.submit(_img(0))       # wedge the worker
        # staleness (0.4s) must beat the open/half-open flap window:
        # once 503, every half-open trial fails and the replica stays out
        deadline = time.monotonic() + 5
        stale = False
        while time.monotonic() < deadline:
            code, _ = _get(srv.port, "/healthz")
            if code == 503:
                stale = True
                break
            time.sleep(0.1)
        assert stale
        time.sleep(1.0)                   # several probe + open cycles
        r0 = next(r for r in router.replicas() if r.name == "r0")
        assert not r0.healthy             # no flapping readmission
    finally:
        router.close()
        release.set()
        srv.batcher._stop.set()
        srv.close()


def test_hedged_attempt_failure_is_attributed_once(tmp_path):
    """Both-legs-fail under hedging: every failed leg's breaker is
    charged exactly once inside _attempt (_AttributedError), never the
    primary twice — one real failure can't open a breaker with
    fail_threshold=2."""
    from tpu_resnet.serve.router import _AttributedError

    d = str(tmp_path)
    dead = _mk_replica(d, "dead")
    dead.batcher.drain(2.0)
    dead.close()                          # connection refused from now on
    router = _mk_router(d, hedge_ms=30.0, fail_threshold=2)
    router._stop.set()                    # freeze the prober: passive only
    router.start()
    try:
        r_dead = next(r for r in router.replicas() if r.name == "dead")
        used = []
        with pytest.raises(_AttributedError):
            router._attempt(r_dead, _img(0).tobytes(),
                            {"Content-Type": "application/octet-stream",
                             "X-Shape": "1,8,8,3"},
                            remaining=2.0, exclude=(), used=used)
        assert r_dead.breaker._failures == 1   # charged once, inside
        # end-to-end: one route_predict = at most one charge per leg
        code, out, _ = _post(router.port, _img(0).tobytes(), "1,8,8,3")
        assert code in (502, 503)
    finally:
        router.close()


# ------------------------------------------------- distributed tracing
def test_trace_id_minted_echoed_and_spanned(fleet):
    """The router is a minting authority: a client-supplied X-Trace-Id
    echoes verbatim, an absent one is minted; after the tail-sampler's
    first baseline period a route_request span lands with per-leg
    attribution under that id."""
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.obs.trace import ROUTE_EVENTS_FILE

    router, (s0, s1), d, rid = fleet
    code, out, headers = _post(router.port, _img(2).tobytes(), "1,8,8,3",
                               headers={"X-Trace-Id": "cli-abc"})
    assert code == 200
    assert headers.get("X-Trace-Id") == "cli-abc"
    code, out, headers = _post(router.port, _img(2).tobytes(), "1,8,8,3")
    assert code == 200
    minted = headers.get("X-Trace-Id")
    assert minted and len(minted) == 16
    # drive past the sampler's base period: a baseline keep is
    # deterministic within 50 observations
    for i in range(60):
        _post(router.port, _img(i % 7).tobytes(), "1,8,8,3")
    spans = [s for s in load_spans(os.path.join(d, ROUTE_EVENTS_FILE))
             if s.get("span") == "route_request"]
    assert spans, "no route_request span after 62 requests"
    s = spans[0]
    assert s["trace_id"] and s["status"] == 200
    assert s["lane"] == "interactive"
    assert s["replica"] in ("r0", "r1")
    assert s["sampled"] in ("sampled", "slow")
    assert s["legs"] and s["legs"][-1]["answered"] == s["replica"]
    assert s["run_id"] == rid


def test_trace_id_echoed_on_shed_and_error_paths(fleet):
    """Every response path carries the trace id back — including 429
    shed and 5xx — and sheds/errors are always-keep span classes."""
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.obs.trace import ROUTE_EVENTS_FILE

    router, (s0, s1), d, rid = fleet
    router.cfg.route.slo_ms = 50.0
    _prime_ring(router, [200.0] * 64)    # rolling p99 over the SLO
    code, out, headers = _post(router.port, _img(1).tobytes(), "1,8,8,3",
                               headers={"X-Lane": "batch",
                                        "X-Trace-Id": "shed-1"})
    assert code == 429
    assert headers.get("X-Trace-Id") == "shed-1"
    spans = [s for s in load_spans(os.path.join(d, ROUTE_EVENTS_FILE))
             if s.get("span") == "route_request"
             and s.get("trace_id") == "shed-1"]
    assert len(spans) == 1          # always-keep: shed
    assert spans[0]["sampled"] == "shed" and spans[0]["status"] == 429
    assert spans[0]["decision"] == "shed"


# ------------------------------------------- watch-discovery probation

def test_watch_discovery_probation_admits_on_first_probe(tmp_path):
    """Deterministic probation walk (no probe thread): a replica that
    appears AFTER router boot under --watch-discovery enters rotation
    pending (excluded), and the first successful probe admits it with a
    replica_admitted span. Boot-time replicas are never on probation."""
    d = str(tmp_path)
    from tpu_resnet.obs.manifest import ensure_run_id
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.obs.trace import ROUTE_EVENTS_FILE

    ensure_run_id(d)
    s0 = _mk_replica(d, "r0")
    router = _mk_router(d, watch_discovery=True)  # NOT started
    try:
        r0 = next(r for r in router.replicas() if r.name == "r0")
        assert not r0.pending        # boot scan: admitted on faith
        s1 = _mk_replica(d, "r1")
        router.refresh_discovery()
        r1 = next(r for r in router.replicas() if r.name == "r1")
        assert r1.pending and not r1.healthy
        assert r1.describe()["pending"] is True
        router.probe_once()          # first healthy probe -> admitted
        assert not r1.pending and r1.healthy
        router.spans.close()
        kinds = [s["span"] for s in
                 load_spans(os.path.join(d, ROUTE_EVENTS_FILE))]
        assert "replica_admitted" in kinds
    finally:
        router.close()
        for srv in (s0, s1):
            srv.batcher.drain(2.0)
            srv.close()


def test_watch_discovery_replica_joins_mid_traffic(tmp_path):
    """End-to-end: traffic flows against one replica, a second joins
    mid-stream and is admitted on merit by the live probe loop; the
    fleet answers 200 throughout and /info reports both healthy."""
    d = str(tmp_path)
    from tpu_resnet.obs.manifest import ensure_run_id

    ensure_run_id(d)
    s0 = _mk_replica(d, "r0")
    router = _mk_router(d, watch_discovery=True).start()
    s1 = None
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(r.healthy and r.image_shape
                   for r in router.replicas()):
                break
            time.sleep(0.05)
        for i in range(4):
            code, out, _ = _post(router.port, _img(i).tobytes(),
                                 "1,8,8,3")
            assert code == 200
        s1 = _mk_replica(d, "r1")       # joins AFTER router boot
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            live = [r for r in router.replicas()
                    if r.healthy and r.image_shape]
            if len(live) == 2:
                break
            time.sleep(0.05)
        assert len(live) == 2, [r.describe() for r in router.replicas()]
        for i in range(8):
            code, out, _ = _post(router.port, _img(i).tobytes(),
                                 "1,8,8,3")
            assert code == 200
        code, info = _get(router.port, "/info")
        by_name = {r["name"]: r for r in info["replicas"]}
        assert by_name["r1"]["pending"] is False
        assert by_name["r1"]["state"] == "closed"
    finally:
        router.close()
        for srv in (s0,) + ((s1,) if s1 is not None else ()):
            srv.batcher.drain(2.0)
            srv.close()


def test_without_watch_discovery_postboot_join_is_not_probationed(tmp_path):
    """Default-off regression guard: with watch_discovery false a
    post-boot discovery arrival is upserted exactly as before — never
    pending."""
    d = str(tmp_path)
    from tpu_resnet.obs.manifest import ensure_run_id

    ensure_run_id(d)
    s0 = _mk_replica(d, "r0")
    router = _mk_router(d)               # watch_discovery defaults off
    try:
        s1 = _mk_replica(d, "r1")
        router.refresh_discovery()
        r1 = next(r for r in router.replicas() if r.name == "r1")
        assert not r1.pending
    finally:
        router.close()
        for srv in (s0, s1):
            srv.batcher.drain(2.0)
            srv.close()
