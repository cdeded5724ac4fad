"""Fault-tolerance layer units (tpu_resnet/resilience): shutdown
coordinator, NaN sentinel policy, hang watchdog, fault-injection plan/
injector, corrupt-checkpoint restore fallback, eval restore retry, and the
supervisor restart policy. End-to-end drills that run a real train() live
in tests/test_resilience_drills.py (slow tier)."""

import os
import signal
import threading
import time

import numpy as np
import pytest

from tpu_resnet import obs, resilience
from tpu_resnet.config import load_config
from tpu_resnet.obs.server import TelemetryRegistry
from tpu_resnet.obs.spans import load_spans
from tpu_resnet.resilience import faultinject
from tpu_resnet.resilience.watchdog import HangWatchdog


# ------------------------------------------------------------- shutdown

def test_shutdown_coordinator_catches_sigterm_and_restores_handlers():
    prev = signal.getsignal(signal.SIGTERM)
    coord = resilience.ShutdownCoordinator().install()
    try:
        assert not coord.requested
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not coord.requested and time.time() < deadline:
            time.sleep(0.01)
        assert coord.requested
        assert coord.signum == signal.SIGTERM
    finally:
        coord.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_shutdown_second_signal_escalates():
    coord = resilience.ShutdownCoordinator()
    coord._handle(signal.SIGTERM, None)
    assert coord.requested
    with pytest.raises(KeyboardInterrupt):
        coord._handle(signal.SIGINT, None)
    # the stop request itself survives the escalation
    assert coord.requested and coord.signum == signal.SIGTERM


def test_shutdown_install_noop_off_main_thread_and_when_disabled():
    prev = signal.getsignal(signal.SIGTERM)
    results = {}

    def worker():
        c = resilience.ShutdownCoordinator().install()
        results["installed"] = bool(c._previous)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert results["installed"] is False
    assert signal.getsignal(signal.SIGTERM) is prev

    off = resilience.ShutdownCoordinator(enabled=False).install()
    assert not off._previous
    assert signal.getsignal(signal.SIGTERM) is prev


def test_preempted_exception_and_exit_code_contract():
    e = resilience.Preempted(120, signum=signal.SIGTERM)
    assert e.step == 120
    assert "SIGTERM" in str(e) and "120" in str(e)
    # CLI default and the module constant must agree (tools/supervise.py
    # carries its own copy — keep all three in sync).
    assert resilience.PREEMPT_EXIT_CODE == 42
    assert load_config("smoke").resilience.preempt_exit_code == 42


# -------------------------------------------------------------- sentinel

def test_nan_sentinel_policy():
    s = resilience.NaNSentinel(max_retries=2)
    assert s.check(10, 1.25) is False  # finite: no rollback
    assert s.check(10, float("nan")) is True
    assert s.check(20, float("inf")) is True
    assert s.rollbacks == 2
    with pytest.raises(resilience.DivergenceError, match="nan_max_retries"):
        s.check(30, float("nan"))
    # disabled sentinel never triggers
    off = resilience.NaNSentinel(max_retries=2, enabled=False)
    assert off.check(10, float("nan")) is False
    # the no-checkpoint error is loud and explains itself
    err = s.no_checkpoint(5, float("nan"))
    assert isinstance(err, resilience.DivergenceError)
    assert "no checkpoint" in str(err)


# -------------------------------------------------------------- watchdog

def test_watchdog_fires_dumps_stacks_and_recovers(tmp_path):
    reg = TelemetryRegistry(stale_after_sec=1000.0)
    reg.heartbeat(0)
    tr = obs.SpanTracer(str(tmp_path))
    wd = HangWatchdog(0.15, str(tmp_path), telemetry=reg, spans=tr,
                      poll_sec=0.05)
    wd.start()
    try:
        # Not armed until the first progress(): a long first compile can
        # never false-trigger the watchdog.
        time.sleep(0.4)
        assert wd.stalls == 0
        wd.progress(5)
        deadline = time.time() + 5
        while wd.stalls == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert wd.stalls == 1
        (dump,) = wd.dumps
        content = open(dump).read()
        assert "MainThread" in content and "watchdog" in content.lower()
        health = reg.health()
        assert health["ok"] is False
        assert "no step progress" in health["unhealthy_reason"]
        assert "tpu_resnet_fault_watchdog_stalls 1.0" in reg.render()
        # progress resumes → unhealthy clears
        wd.progress(6)
        deadline = time.time() + 5
        while not reg.health()["ok"] and time.time() < deadline:
            time.sleep(0.02)
        assert reg.health()["ok"] is True
    finally:
        wd.close()
        tr.close()
    kinds = [s["span"] for s in load_spans(str(tmp_path / "events.jsonl"))]
    assert kinds == ["watchdog_stall", "watchdog_recovered"]


def test_watchdog_hang_dump_writes_the_loops_phase_ring(tmp_path):
    """A stall calls ``on_stall`` once: the train loop hands it the
    recorder's flush, so a hang leaves the last phases in events.jsonl
    (and the closer chain, coming later, writes none of them twice)."""
    from tpu_resnet.obs.spans import load_spans

    tr = obs.SpanTracer(str(tmp_path))
    bd = obs.StepBreakdown()
    with bd.data_wait(3):
        pass
    with bd.dispatch(3, 2):
        pass
    wd = HangWatchdog(0.1, str(tmp_path), spans=tr, poll_sec=0.03,
                      on_stall=lambda: bd.flush(tr, ring=True))
    wd.start()
    try:
        wd.progress(5)
        deadline = time.time() + 5
        while wd.stalls == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert wd.stalls == 1
    finally:
        wd.close()
    bd.close()
    bd.flush(tr, ring=True)  # the closer chain: nothing is left to write
    tr.close()
    kinds = [s["span"] for s in load_spans(str(tmp_path / "events.jsonl"))]
    assert kinds.count("train.data_wait") == 1
    assert kinds.count("train.dispatch") == 1
    assert "watchdog_stall" in kinds


def test_watchdog_maybe_start_disabled():
    assert HangWatchdog.maybe_start(0, "/nonexistent") is None
    assert HangWatchdog.maybe_start(-1, "/nonexistent") is None


# ---------------------------------------------------------- faultinject

def test_fault_plan_defaults_inactive_and_env_overrides():
    rcfg = load_config("smoke").resilience
    plan = faultinject.FaultPlan.from_config(rcfg, env={})
    assert plan.active is False
    env = {"TPU_RESNET_FAULT_NAN_STEP": "7",
           "TPU_RESNET_FAULT_STALL_STEP": "3",
           "TPU_RESNET_FAULT_STALL_SEC": "1.5",
           "TPU_RESNET_FAULT_SIGTERM_STEP": "9",
           "TPU_RESNET_FAULT_CORRUPT_CKPT": "true"}
    plan = faultinject.FaultPlan.from_config(rcfg, env=env)
    assert plan == faultinject.FaultPlan(
        nan_at_step=7, stall_at_step=3, stall_seconds=1.5,
        sigterm_at_step=9, corrupt_ckpt_at_start=True)
    assert plan.active
    # config fields drive the plan when the env is silent
    rcfg.inject_nan_at_step = 4
    plan = faultinject.FaultPlan.from_config(rcfg, env={})
    assert plan.nan_at_step == 4 and plan.active


def test_fault_injector_inactive_is_zero_overhead():
    inj = resilience.FaultInjector(faultinject.FaultPlan())
    batches = iter([(np.ones((2, 4, 4, 3), np.uint8),
                     np.zeros((2,), np.int32))])
    assert inj.wrap_host_batches(batches) is batches  # untouched object
    inj.maybe_sigterm(100)  # no-op, no signal
    inj.maybe_corrupt_checkpoint("/nonexistent")  # no-op


def _batches(n):
    return [(np.full((2, 4, 4, 3), i, np.uint8),
             np.full((2,), i, np.int32)) for i in range(n)]


def test_fault_injector_nan_batch_is_one_shot():
    inj = resilience.FaultInjector(faultinject.FaultPlan(nan_at_step=3))
    out = list(inj.wrap_host_batches(iter(_batches(5)), start_step=0))
    assert np.isnan(out[3][0]).all()
    for i in (0, 1, 2, 4):
        assert not np.isnan(np.asarray(out[i][0], np.float32)).any()
    # rebuilt stream (post-rollback) passes step 3 clean: already fired
    out2 = list(inj.wrap_host_batches(iter(_batches(5)), start_step=2))
    assert all(not np.isnan(np.asarray(im, np.float32)).any()
               for im, _ in out2)


def test_fault_injector_stall():
    inj = resilience.FaultInjector(
        faultinject.FaultPlan(stall_at_step=6, stall_seconds=0.3))
    it = inj.wrap_host_batches(iter(_batches(3)), start_step=5)
    t0 = time.perf_counter()
    next(it)  # step 5: no stall
    assert time.perf_counter() - t0 < 0.25
    t0 = time.perf_counter()
    next(it)  # step 6: stalls
    assert time.perf_counter() - t0 >= 0.3


def test_corrupt_checkpoint_helper_empty_dir(tmp_path):
    assert faultinject.corrupt_checkpoint(str(tmp_path)) is None
    assert faultinject.corrupt_checkpoint(str(tmp_path / "missing")) is None


# ------------------------------------- corrupt-checkpoint restore fallback

@pytest.fixture
def ckpt_dir_with_three_steps(tmp_path):
    from tpu_resnet.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=5)
    for s in (1, 2, 3):
        mgr.save(s, {"w": np.full((4,), float(s), np.float32)})
    mgr.wait()
    return tmp_path, mgr


def test_restore_falls_back_past_corrupt_latest(ckpt_dir_with_three_steps):
    tmp_path, mgr = ckpt_dir_with_three_steps
    assert faultinject.corrupt_checkpoint(str(tmp_path)) == 3
    template = {"w": np.zeros((4,), np.float32)}
    restored = mgr.restore(template)  # falls back 3 → 2
    np.testing.assert_array_equal(restored["w"],
                                  np.full((4,), 2.0, np.float32))
    # a read-only caller (export, notebook) must NOT destroy checkpoints
    # that merely failed to restore for it
    assert 3 in mgr.all_steps()
    # the trainer's resume path (discard_failed=True) does discard, so
    # pollers and its own future saves can't trip on the corrupt step
    restored = mgr.restore(template, discard_failed=True)
    np.testing.assert_array_equal(restored["w"],
                                  np.full((4,), 2.0, np.float32))
    assert 3 not in mgr.all_steps()
    assert mgr.latest_step() == 2


def test_restore_fallback_order_is_newest_first(ckpt_dir_with_three_steps):
    tmp_path, mgr = ckpt_dir_with_three_steps
    faultinject.corrupt_checkpoint(str(tmp_path), step=3)
    faultinject.corrupt_checkpoint(str(tmp_path), step=2)
    restored = mgr.restore({"w": np.zeros((4,), np.float32)})
    np.testing.assert_array_equal(restored["w"],
                                  np.full((4,), 1.0, np.float32))


def test_restore_all_corrupt_raises(ckpt_dir_with_three_steps):
    tmp_path, mgr = ckpt_dir_with_three_steps
    for s in (1, 2, 3):
        faultinject.corrupt_checkpoint(str(tmp_path), step=s)
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        mgr.restore({"w": np.zeros((4,), np.float32)})


def test_restore_explicit_step_fails_loudly(ckpt_dir_with_three_steps):
    """An explicitly requested step (evaluator, export) must not silently
    serve an older step."""
    tmp_path, mgr = ckpt_dir_with_three_steps
    faultinject.corrupt_checkpoint(str(tmp_path), step=3)
    with pytest.raises(Exception):
        mgr.restore({"w": np.zeros((4,), np.float32)}, step=3)
    # steps are only discarded by the fallback path, never the loud one
    assert 3 in mgr.all_steps()


# ------------------------------------------------------ eval restore retry

class _FlakyCkpt:
    def __init__(self, fail_times):
        self.fail_times = fail_times
        self.calls = 0

    def restore(self, template, step=None):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise OSError("checkpoint still committing")
        return {"restored": step}


def test_eval_restore_retry_transient_then_success():
    from tpu_resnet.train.checkpoint import restore_with_retry

    sleeps = []
    ckpt = _FlakyCkpt(2)
    out = restore_with_retry(ckpt, None, 7, retries=3, backoff_sec=0.5,
                              sleep=sleeps.append)
    assert out == {"restored": 7}
    assert ckpt.calls == 3
    assert sleeps == [0.5, 1.0]  # exponential backoff between attempts


def test_eval_restore_retry_gives_up_returns_none():
    from tpu_resnet.train.checkpoint import restore_with_retry

    sleeps = []
    out = restore_with_retry(_FlakyCkpt(99), None, 7, retries=3,
                              backoff_sec=0.1, sleep=sleeps.append)
    assert out is None
    assert sleeps == [0.1, 0.2]  # no sleep after the final failure


# ------------------------------------------------------------- supervisor

def test_supervise_restart_policy():
    from tools.supervise import supervise

    codes = iter([42, 1, 1, 42, 0])
    calls, sleeps = [], []
    rc = supervise(["job"], max_restarts=10, backoff_base=1.0,
                   backoff_cap=4.0, preempt_delay=0.5, jitter=False,
                   run=lambda c: (calls.append(list(c)), next(codes))[1],
                   sleep=sleeps.append)
    assert rc == 0
    assert calls == [["job"]] * 5
    # preempt: fixed delay; crashes: 1, 2 (exponential); preempt resets
    # the crash streak back to the fixed delay
    assert sleeps == [0.5, 1.0, 2.0, 0.5]


def test_supervise_backoff_cap_and_give_up():
    from tools.supervise import supervise

    sleeps = []
    rc = supervise(["job"], max_restarts=5, backoff_base=1.0,
                   backoff_cap=4.0, jitter=False, run=lambda c: 7,
                   sleep=sleeps.append)
    assert rc == 7
    assert sleeps == [1.0, 2.0, 4.0, 4.0, 4.0]  # capped, then gives up


def test_supervise_crash_backoff_decorrelated_jitter():
    """Default backoff is decorrelated-jitter (fleet restarts after a
    shared fault must not stampede): each crash delay is uniform in
    [base, 3 * previous], capped — and every delay is logged."""
    import random

    from tools.supervise import supervise

    sleeps = []
    rc = supervise(["job"], max_restarts=6, backoff_base=1.0,
                   backoff_cap=40.0, rng=random.Random(7),
                   run=lambda c: 9, sleep=sleeps.append)
    assert rc == 9
    assert len(sleeps) == 6
    prev = 1.0
    for d in sleeps:
        assert 1.0 <= d <= min(40.0, max(1.0, prev) * 3), (d, prev)
        prev = d
    # jitter actually jitters: the deterministic schedule is 1,2,4,8...
    assert sleeps != [1.0, 2.0, 4.0, 8.0, 16.0, 32.0]


def test_supervise_cli_requires_command(capsys):
    from tools.supervise import main

    with pytest.raises(SystemExit):
        main(["--max-restarts", "1"])


# ------------------------------------------------------ config round-trip

def test_resilience_config_overrides_and_serialization():
    cfg = load_config("smoke", overrides=[
        "resilience.inject_sigterm_at_step=20",
        "resilience.nan_max_retries=5",
        "resilience.watchdog_stall_sec=7.5",
        "resilience.graceful_shutdown=false",
    ])
    assert cfg.resilience.inject_sigterm_at_step == 20
    assert cfg.resilience.nan_max_retries == 5
    assert cfg.resilience.watchdog_stall_sec == 7.5
    assert cfg.resilience.graceful_shutdown is False
    from tpu_resnet.config import RunConfig

    round_tripped = RunConfig.from_dict(cfg.to_dict())
    assert round_tripped.resilience == cfg.resilience


# --------------------------------------------- serve-side faults (fleet)

def test_serve_fault_plan_env_and_config():
    rcfg = load_config("smoke").resilience
    env = {"TPU_RESNET_FAULT_SERVE_SLOW_MS": "25",
           "TPU_RESNET_FAULT_SERVE_HANG_REQ": "4",
           "TPU_RESNET_FAULT_SERVE_KILL_REQ": "9"}
    plan = faultinject.FaultPlan.from_config(rcfg, env=env)
    assert plan.serve_slow_ms == 25.0
    assert plan.serve_hang_at_request == 4
    assert plan.serve_kill_at_request == 9
    assert plan.serves_faults and plan.active
    rcfg.inject_serve_slow_ms = 10.0
    plan = faultinject.FaultPlan.from_config(rcfg, env={})
    assert plan.serve_slow_ms == 10.0 and plan.active


def test_serve_fault_wrap_is_identity_when_off():
    inj = resilience.FaultInjector(faultinject.FaultPlan())

    def infer(x):
        return x

    assert inj.wrap_serve_infer(infer) is infer  # zero overhead when off


def test_serve_fault_slow_injects_latency():
    import time as _time

    inj = resilience.FaultInjector(
        faultinject.FaultPlan(serve_slow_ms=60.0))
    wrapped = inj.wrap_serve_infer(lambda x: x * 2)
    t0 = _time.monotonic()
    assert wrapped(21) == 42
    assert _time.monotonic() - t0 >= 0.05


def test_serve_fault_kill_fires_at_request_k(monkeypatch):
    kills = []
    monkeypatch.setattr(faultinject.os, "kill",
                        lambda pid, sig: kills.append((pid, sig)))
    inj = resilience.FaultInjector(
        faultinject.FaultPlan(serve_kill_at_request=3))
    inj.note_serve_request()
    inj.note_serve_request()
    assert kills == []          # requests 1-2 sail through
    inj.note_serve_request()
    import signal as _signal

    assert kills == [(faultinject.os.getpid(), _signal.SIGKILL)]


def test_serve_fault_hang_pins_the_infer_thread(monkeypatch):
    """accept-then-hang: the wrapped infer loops in sleep forever (the
    batcher thread is the one that hangs). The test breaks the loop by
    making the injected sleep raise."""

    class _Escape(Exception):
        pass

    def boom(sec):
        raise _Escape(f"slept {sec}")

    monkeypatch.setattr(faultinject.time, "sleep", boom)
    inj = resilience.FaultInjector(
        faultinject.FaultPlan(serve_hang_at_request=2))
    wrapped = inj.wrap_serve_infer(lambda x: x)
    inj.note_serve_request()
    assert wrapped(1) == 1      # request 1: before the hang point
    inj.note_serve_request()
    with pytest.raises(_Escape):
        wrapped(2)              # request 2: hung (sleep loop entered)
