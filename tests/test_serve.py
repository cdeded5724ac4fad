"""Online inference serving (tpu_resnet/serve; docs/SERVING.md).

Three layers, mirroring the subsystem's own:

- batcher core: pure-function tests with a fake ``infer_fn`` — no
  sockets, no jax: coalescing under ``max_wait_ms``, bucket
  selection/padding, bounded-queue rejection, reload-between-batches
  ordering, drain-on-shutdown;
- HTTP layer: a real ``PredictServer`` over a fake backend (millisecond
  startup) — wire formats, error mapping (400/429/503), /metrics +
  /healthz readiness, hot-reload gauge flow, loadgen driving it;
- model layer: export/serve parity (frozen StableHLO vs live-checkpoint
  serving vs the predict tool's bundle — bit-identical logits), and the
  slow-tier CPU e2e: real model, concurrent clients, a mid-traffic
  checkpoint hot-reload with zero failed requests, clean drain.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_resnet.config import load_config
from tpu_resnet.serve.batcher import (Draining, MicroBatcher, QueueFull,
                                      default_buckets, percentile,
                                      pick_bucket)
from tpu_resnet.serve.server import PredictServer, parse_predict_body

SHAPE = (8, 8, 3)


def _images(n, first_pixel=0):
    imgs = np.zeros((n,) + SHAPE, np.uint8)
    imgs[:, 0, 0, 0] = first_pixel
    return imgs


def _echo_infer(record=None, delay=0.0, classes=7):
    """Fake infer: class = first pixel value %% classes (padding rows get
    class 0 — sliced off by the batcher, which the tests verify)."""

    def infer(images):
        if record is not None:
            record.append(int(images.shape[0]))
        if delay:
            time.sleep(delay)
        n = images.shape[0]
        logits = np.zeros((n, classes), np.float32)
        logits[np.arange(n), images[:, 0, 0, 0] % classes] = 1.0
        return logits

    return infer


def _mk(infer, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_wait_ms", 50.0)
    kw.setdefault("max_queue", 64)
    return MicroBatcher(infer, SHAPE, **kw)


# ------------------------------------------------------------ pure helpers
def test_default_buckets_powers_of_two_plus_max():
    assert default_buckets(16) == (1, 2, 4, 8, 16)
    assert default_buckets(12) == (1, 2, 4, 8, 12)
    assert default_buckets(1) == (1,)
    with pytest.raises(ValueError):
        default_buckets(0)


def test_pick_bucket_smallest_fit():
    assert pick_bucket(3, (1, 2, 4, 8)) == 4
    assert pick_bucket(8, (1, 2, 4, 8)) == 8
    with pytest.raises(ValueError):
        pick_bucket(9, (1, 2, 4, 8))


def test_percentile_nearest_rank():
    lat = [float(x) for x in range(101)]  # 0..100
    assert percentile(lat, 0.50) == 50.0
    assert percentile(lat, 0.99) == 99.0
    assert percentile(lat, 1.0) == 100.0
    assert percentile([], 0.5) == 0.0


def test_checkpoint_poller_reports_each_step_once(tmp_path):
    """The shared poll half of the eval sidecar / serve hot-reload."""
    from tpu_resnet.train.checkpoint import CheckpointPoller

    p = CheckpointPoller(str(tmp_path))
    assert p.poll() is None
    os.mkdir(tmp_path / "5")
    assert p.poll() == 5
    assert p.poll() == 5          # not marked yet: still reported
    p.mark_seen(5)
    assert p.poll() is None       # seen (restored OR skipped): silent
    os.mkdir(tmp_path / "10")
    assert p.poll() == 10


# ------------------------------------------------------------ batcher core
def test_coalesces_queued_requests_into_one_bucketed_batch():
    sizes = []
    b = _mk(_echo_infer(sizes))
    reqs = [b.submit(_images(1, i)) for i in (1, 2, 3)]  # queued pre-start
    b.start()
    outs = [r.wait(5.0) for r in reqs]
    # one dispatch: 3 images padded up to bucket 4
    assert sizes == [4]
    # each request got ITS rows back, not the padding's
    for i, out in zip((1, 2, 3), outs):
        assert out.shape == (1, 7)
        assert np.argmax(out[0]) == i
    st = b.stats()
    assert st["batches"] == 1 and st["batched_images"] == 3
    assert st["padded_images"] == 1
    assert st["pad_fraction"] == pytest.approx(0.25)
    assert b.drain(5.0)


def test_coalesces_across_max_wait_window():
    sizes = []
    b = _mk(_echo_infer(sizes), max_wait_ms=500.0).start()
    r1 = b.submit(_images(1))
    time.sleep(0.1)  # well inside the 500ms window
    r2 = b.submit(_images(1))
    r1.wait(5.0), r2.wait(5.0)
    assert sizes == [2]  # second request joined the first's batch
    assert b.drain(5.0)


def test_lone_request_dispatches_after_max_wait():
    sizes = []
    b = _mk(_echo_infer(sizes), max_wait_ms=30.0).start()
    t0 = time.monotonic()
    b.submit(_images(1)).wait(5.0)
    assert time.monotonic() - t0 < 2.0
    assert sizes == [1]
    assert b.drain(5.0)


def test_queue_full_rejects_with_backpressure():
    entered, release = threading.Event(), threading.Event()

    def slow_infer(images):
        entered.set()
        release.wait(10.0)
        return np.zeros((images.shape[0], 7), np.float32)

    b = _mk(slow_infer, max_queue=2, max_wait_ms=1.0).start()
    r1 = b.submit(_images(1))
    assert entered.wait(5.0)      # worker is mid-batch with r1
    r2 = b.submit(_images(1))
    r3 = b.submit(_images(1))     # queue now at capacity (2)
    with pytest.raises(QueueFull):
        b.submit(_images(1))
    assert b.stats()["rejected"] == 1
    release.set()
    for r in (r1, r2, r3):
        r.wait(5.0)
    assert b.drain(5.0)


def test_split_request_admission_is_atomic():
    """An oversize request split into chunks is admitted all-or-nothing:
    a partial admission would run the admitted chunks' inference only to
    throw the results away when the client retries the whole request."""
    entered, release = threading.Event(), threading.Event()

    def slow_infer(images):
        entered.set()
        release.wait(10.0)
        return np.zeros((images.shape[0], 7), np.float32)

    b = _mk(slow_infer, max_queue=3, max_wait_ms=1.0).start()
    first = b.submit(_images(1))
    assert entered.wait(5.0)          # worker mid-batch; queue now empty
    b.submit(_images(1))
    b.submit(_images(1))              # 2 of 3 slots taken
    with pytest.raises(QueueFull):
        b.submit_many([_images(1), _images(1)])  # needs 2, only 1 free
    assert b.stats()["rejected"] == 2
    assert b._queue.qsize() == 2      # nothing partially admitted
    release.set()
    first.wait(5.0)
    assert b.drain(5.0)


def test_submit_validates_shape_and_size():
    b = _mk(_echo_infer())
    with pytest.raises(ValueError):
        b.submit(np.zeros((9,) + SHAPE, np.uint8))  # > max_batch
    with pytest.raises(ValueError):
        b.submit(np.zeros((1, 4, 4, 3), np.uint8))  # wrong H,W
    with pytest.raises(ValueError):
        b.submit(np.zeros(SHAPE, np.uint8))         # missing batch dim


def test_oversize_request_carried_not_split_mid_batch():
    """A request that would overflow the forming batch starts the next
    one — its images stay contiguous in a single inference."""
    sizes = []
    b = _mk(_echo_infer(sizes), max_batch=4, max_wait_ms=50.0)
    b.submit(_images(3, 1))
    big = b.submit(_images(3, 2))
    b.start()
    big.wait(5.0)
    assert sizes == [4, 4]  # 3(+1 pad), then 3(+1 pad) — never 1+2 split
    assert b.drain(5.0)


def test_drain_flushes_queue_then_rejects_new_work():
    b = _mk(_echo_infer(delay=0.01), max_wait_ms=1.0).start()
    reqs = [b.submit(_images(1, i)) for i in range(10)]
    assert b.drain(10.0) is True
    for i, r in enumerate(reqs):
        assert np.argmax(r.wait(0.1)[0]) == i % 7  # all served pre-exit
    with pytest.raises(Draining):
        b.submit(_images(1))


def test_drain_timeout_fails_leftovers_instead_of_hanging():
    release = threading.Event()

    def stuck_infer(images):
        release.wait(30.0)
        return np.zeros((images.shape[0], 7), np.float32)

    b = _mk(stuck_infer, max_wait_ms=1.0).start()
    r1 = b.submit(_images(1))
    time.sleep(0.1)               # r1 into the stuck batch
    r2 = b.submit(_images(1))     # r2 still queued
    assert b.drain(0.3) is False
    with pytest.raises(Draining):
        r2.wait(1.0)
    release.set()                 # un-stick; worker finishes r1 and exits
    r1.wait(5.0)


def test_drain_flushes_straggler_that_raced_admission():
    """A submit that read ``_accepting`` just before the drain flip can
    enqueue after the worker's final empty gather — the flush must cover
    it even when the worker exited cleanly, or the client sits on the
    full request-wait timeout instead of an immediate 503."""
    from tpu_resnet.serve.batcher import PendingRequest

    b = _mk(_echo_infer()).start()
    assert b.drain(5.0) is True          # worker exited, queue empty
    straggler = PendingRequest(_images(1))
    b._queue.put_nowait((0, 1, straggler))  # the raced-admission analog
    b.drain(0.1)
    with pytest.raises(Draining):
        straggler.wait(1.0)


def test_reload_hook_runs_strictly_between_batches():
    events = []

    def infer(images):
        events.append("batch_start")
        time.sleep(0.005)
        events.append("batch_end")
        return np.zeros((images.shape[0], 7), np.float32)

    b = MicroBatcher(infer, SHAPE, max_batch=4, max_wait_ms=5.0,
                     max_queue=64,
                     between_batches=lambda: events.append("reload"))
    b.start()
    reqs = [b.submit(_images(1)) for _ in range(6)]
    for r in reqs:
        r.wait(5.0)
    assert b.drain(5.0)
    depth = 0
    for e in events:
        if e == "batch_start":
            depth += 1
        elif e == "batch_end":
            depth -= 1
        else:
            assert depth == 0, f"reload inside a batch: {events}"
    assert "reload" in events and events.count("batch_start") >= 2


def test_infer_failure_fails_batch_not_server():
    calls = []

    def flaky(images):
        calls.append(images.shape[0])
        if len(calls) == 1:
            raise RuntimeError("transient backend failure")
        return np.zeros((images.shape[0], 7), np.float32)

    b = _mk(flaky, max_wait_ms=1.0).start()
    r1 = b.submit(_images(1))
    with pytest.raises(RuntimeError):
        r1.wait(5.0)
    r2 = b.submit(_images(1))
    r2.wait(5.0)  # the worker survived the failed batch
    assert b.stats()["failed"] == 1
    assert b.drain(5.0)


# ------------------------------------------------------------ wire parsing
def test_parse_octet_stream_with_and_without_count():
    body = _images(2, 9).tobytes()
    out = parse_predict_body(body, "application/octet-stream",
                             "2,8,8,3", SHAPE)
    assert out.shape == (2, 8, 8, 3) and out[0, 0, 0, 0] == 9
    out = parse_predict_body(body, "application/octet-stream",
                             "8,8,3", SHAPE)   # N inferred
    assert out.shape == (2, 8, 8, 3)
    out = parse_predict_body(body, "application/octet-stream", None, SHAPE)
    assert out.shape == (2, 8, 8, 3)


def test_parse_json_instances_single_and_batch():
    img = _images(1, 5)
    out = parse_predict_body(
        json.dumps({"instances": img[0].tolist()}).encode(),
        "application/json", None, SHAPE)
    assert out.shape == (1, 8, 8, 3) and out[0, 0, 0, 0] == 5
    out = parse_predict_body(
        json.dumps({"instances": img.tolist()}).encode(),
        "application/json", None, SHAPE)
    assert out.shape == (1, 8, 8, 3)


@pytest.mark.parametrize("body,ctype,shape_hdr", [
    (b"abc", "application/octet-stream", None),          # partial image
    (_images(2).tobytes(), "application/octet-stream", "3,8,8,3"),
    (_images(1).tobytes(), "application/octet-stream", "1,4,4,3"),
    (b"not json", "application/json", None),
    (json.dumps({"nope": []}).encode(), "application/json", None),
    (json.dumps({"instances": [[1, 2]]}).encode(), "application/json",
     None),                                              # wrong rank
    (_images(1).tobytes(), "text/plain", None),          # bad ctype
])
def test_parse_rejects_malformed(body, ctype, shape_hdr):
    with pytest.raises(ValueError):
        parse_predict_body(body, ctype, shape_hdr, SHAPE)


# ------------------------------------------------------------ HTTP layer
class FakeBackend:
    """Millisecond-startup backend for HTTP-layer tests: class = first
    pixel %% num_classes; reload succeeds when ``reload_armed``."""

    def __init__(self, image_size=8, num_classes=7):
        self.image_size = image_size
        self.num_classes = num_classes
        self.fixed_batch = 0
        self.model_step = 7
        self.reloads = 0
        self.warmed = None
        self.batch_sizes = []
        self.reload_armed = False

    def constrain_buckets(self, buckets):
        return tuple(buckets)

    def warmup(self, buckets):
        self.warmed = list(buckets)

    def infer(self, images):
        self.batch_sizes.append(int(images.shape[0]))
        n = images.shape[0]
        logits = np.zeros((n, self.num_classes), np.float32)
        logits[np.arange(n), images[:, 0, 0, 0] % self.num_classes] = 1.0
        return logits

    def maybe_reload(self):
        if self.reload_armed:
            self.reload_armed = False
            self.model_step += 1
            self.reloads += 1
            return True
        return False


def _serve_cfg(**serve_overrides):
    cfg = load_config()
    cfg.serve.port = 0
    cfg.serve.host = "127.0.0.1"
    cfg.serve.max_batch = 8
    cfg.serve.max_wait_ms = 20.0
    cfg.serve.reload_interval_secs = 0.05
    for k, v in serve_overrides.items():
        setattr(cfg.serve, k, v)
    return cfg


def _post(port, body, ctype="application/octet-stream", shape=None,
          query=""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict{query}", data=body,
        headers={"Content-Type": ctype,
                 **({"X-Shape": shape} if shape else {})})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture()
def fake_server():
    backend = FakeBackend()
    srv = PredictServer(_serve_cfg(), backend=backend).start()
    yield srv, backend
    srv.batcher.drain(5.0)
    srv.close()


def test_http_predict_readiness_metrics_and_reload(fake_server):
    srv, backend = fake_server
    assert backend.warmed == list(srv.buckets)  # compiled pre-readiness

    code, health = _get(srv.port, "/healthz")
    assert code == 200 and json.loads(health)["ok"] is True

    # octet-stream predict: per-request rows come back, padding doesn't
    code, out = _post(srv.port, _images(3, 5).tobytes(), shape="3,8,8,3")
    assert code == 200
    assert out["predictions"] == [5, 5, 5] and out["count"] == 3
    assert out["model_step"] == 7

    # JSON + logits echo path
    code, out = _post(srv.port,
                      json.dumps({"instances": _images(1, 2)[0].tolist()}
                                 ).encode(),
                      ctype="application/json", query="?logits=1")
    assert code == 200 and np.argmax(out["logits"][0]) == 2

    # malformed input → 400 with an explanation, not a 500
    code, out = _post(srv.port, b"abc", shape="1,8,8,3")
    assert code == 400 and "error" in out

    # concurrent clients: dynamic batching engages, nothing fails
    errors = []

    def client(i):
        try:
            for _ in range(5):
                code, out = _post(srv.port, _images(1, i).tobytes(),
                                  shape="1,8,8,3")
                assert code == 200 and out["predictions"] == [i % 7]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert errors == []
    stats = srv.batcher.stats()
    assert stats["failed"] == 0 and stats["rejected"] == 0
    assert stats["batch_size_mean"] > 1.0, stats

    # hot reload flows through to the gauges
    backend.reload_armed = True
    deadline = time.monotonic() + 5.0
    while backend.reloads == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert backend.reloads == 1 and backend.model_step == 8
    time.sleep(0.3)  # let the batcher's next idle tick publish gauges

    code, metrics_body = _get(srv.port, "/metrics")
    from tpu_resnet.obs.server import parse_prometheus
    metrics = parse_prometheus(metrics_body.decode())
    assert metrics["tpu_resnet_serve_requests_total"] >= 41
    assert metrics["tpu_resnet_serve_batch_size_mean"] > 1.0
    assert metrics["tpu_resnet_serve_model_step"] == 8.0
    assert metrics["tpu_resnet_serve_reloads_total"] == 1.0

    code, info = _get(srv.port, "/info")
    info = json.loads(info)
    assert info["buckets"] == list(srv.buckets)
    assert info["model_step"] == 8

    # drain: healthz flips, predicts get 503, nothing hangs
    assert srv.drain(5.0) is True
    code, _ = _get(srv.port, "/healthz")
    assert code == 503
    code, out = _post(srv.port, _images(1).tobytes(), shape="1,8,8,3")
    assert code == 503


def test_large_request_split_across_batches(fake_server):
    srv, backend = fake_server
    code, out = _post(srv.port, _images(20, 3).tobytes(), shape="20,8,8,3")
    assert code == 200
    assert out["predictions"] == [3] * 20  # split 8+8+4, reassembled


def test_loadgen_drives_the_server(fake_server, capsys, tmp_path):
    srv, _ = fake_server
    from tools.loadgen import main as loadgen_main

    out_file = tmp_path / "load.json"
    rc = loadgen_main(["--url", f"http://127.0.0.1:{srv.port}",
                       "--clients", "4", "--duration", "1.5",
                       "--out", str(out_file)])
    assert rc == 0
    # the emit must round-trip through the sweep harness's parser (the
    # one RESULT_JSON reader — truncated lines are skipped there)
    from tpu_resnet.tools.sweep import _parse_result

    result = _parse_result(capsys.readouterr().out)
    assert result == json.loads(out_file.read_text())
    assert result["failed"] == 0 and result["requests_ok"] > 0
    assert result["latency_ms"]["p99"] >= result["latency_ms"]["p50"] > 0
    assert result["server"]["observed_mean_batch"] > 1.0
    assert result["throughput_rps"] > 0


def test_loadgen_open_loop_paces_arrivals(fake_server):
    srv, _ = fake_server
    from tools.loadgen import run_load

    result = run_load(f"http://127.0.0.1:{srv.port}", clients=4,
                      duration=1.5, mode="open", qps=40.0)
    assert result["failed"] == 0 and result["requests_ok"] > 0
    # offered 40 qps for ~1.5s: the closed-loop rate (1000s/s against a
    # fake backend) is impossible; pacing must hold roughly to offered.
    assert result["requests_ok"] <= 40 * 1.5 * 1.5 + 4


# ------------------------------------------------------- model-layer tests
def _tiny_train(tmp_path, steps=4, name="mlp"):
    cfg = load_config("smoke")
    cfg.train.train_dir = str(tmp_path / "run")
    cfg.train.train_steps = steps
    cfg.train.checkpoint_every = 2
    cfg.train.log_every = 2
    cfg.train.summary_every = 4
    cfg.train.image_summary_every = 0
    cfg.train.steps_per_call = 2
    cfg.train.global_batch_size = 16
    cfg.model.name = name
    cfg.data.device_resident = "off"
    cfg.data.transfer_stage = 1
    return cfg


def test_export_serve_parity(tmp_path):
    """Satellite lock on export/serve drift, at two strictnesses:

    - the frozen StableHLO bundle served via ``ExportBackend``, the
      predict tool's bundle call, and a live apply with the SAME
      baked-weights structure (``export.make_inference_fn``) must be
      BIT-identical — this is the lock on ``save_inference``'s baked-in
      preprocessing: any drift there shows up as large diffs, not ulps;
    - the serve checkpoint backend passes weights as *arguments* (so
      hot-reload never recompiles); XLA constant-folds the frozen
      program's BN affine slightly differently (measured: 1.2e-6 max on
      this box — reassociation, not drift), so that pair is locked to
      identical argmax + ulp-scale allclose instead.
    """
    import jax
    import jax.numpy as jnp

    from tpu_resnet.export import (export_from_checkpoint, load_inference,
                                   make_inference_fn)
    from tpu_resnet.serve.backend import CheckpointBackend, ExportBackend
    from tpu_resnet.train import build_schedule, init_state
    from tpu_resnet.train.checkpoint import CheckpointManager

    cfg = _tiny_train(tmp_path, name="resnet")  # real BN path
    # A checkpoint with non-trivial weights AND batch_stats, without
    # paying for a training run: perturbed init reproduces the BN
    # constant-folding sensitivity trained stats have (var != 1).
    from tpu_resnet.models import build_model

    model = build_model(cfg)
    sched = build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, sched, jax.random.PRNGKey(0),
                       jnp.zeros((1, 32, 32, 3)))
    state = state.replace(
        step=jnp.asarray(4, jnp.int32),
        params=jax.tree_util.tree_map(lambda x: x * 1.01 + 0.003,
                                      state.params),
        batch_stats=jax.tree_util.tree_map(lambda x: x * 1.37 + 0.05,
                                           state.batch_stats))
    mgr = CheckpointManager(cfg.train.train_dir)
    assert mgr.save(4, state)
    mgr.close()

    cfg.serve.export_dir = str(tmp_path / "export")
    export_from_checkpoint(cfg, cfg.serve.export_dir)

    live = CheckpointBackend(cfg)
    # The initial restore runs on a background thread (overlapped with
    # warmup by design); join it before touching _variables directly —
    # reading the published reference without the join is exactly the
    # race the concurrency engine flags in production code.
    live._ensure_restored()
    frozen = ExportBackend(cfg.serve.export_dir)
    bundle = load_inference(cfg.serve.export_dir)  # tools/predict's path

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (4, 32, 32, 3)).astype(np.uint8)
    frozen_logits = frozen.infer(imgs)
    predict_logits = bundle(imgs)
    baked = make_inference_fn(
        cfg, jax.device_get(live._variables["params"]),
        jax.device_get(live._variables["batch_stats"]))
    baked_logits = np.asarray(jax.jit(baked)(jnp.asarray(imgs)))
    assert np.array_equal(frozen_logits, predict_logits)
    assert np.array_equal(frozen_logits, baked_logits)
    assert frozen.model_step == 4  # manifest carries the exported step

    live_logits = live.infer(imgs)
    np.testing.assert_allclose(live_logits, frozen_logits,
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.argmax(live_logits, -1),
                          np.argmax(frozen_logits, -1))
    assert live.model_step == 4
    assert not np.array_equal(live_logits[0], live_logits[1])  # real model
    live.close()


@pytest.mark.slow
def test_serve_e2e_concurrent_clients_hot_reload_drain(tmp_path):
    """The acceptance drill, in-process: real model server + 8 concurrent
    clients on CPU; a checkpoint lands mid-traffic and is hot-reloaded;
    zero failed requests across the swap; observed mean batch > 1; clean
    drain with no orphaned threads."""
    from tpu_resnet.train import train

    cfg = _tiny_train(tmp_path, steps=4, name="mlp")
    train(cfg)

    cfg.serve.port = 0
    cfg.serve.host = "127.0.0.1"
    cfg.serve.max_batch = 8
    cfg.serve.max_wait_ms = 20.0
    cfg.serve.reload_interval_secs = 0.1
    srv = PredictServer(cfg).start()
    assert srv.backend.model_step == 4

    stop = threading.Event()
    errors, ok = [], [0]

    def client(i):
        body = _images_32(1, i).tobytes()
        while not stop.is_set():
            try:
                code, out = _post(srv.port, body, shape="1,32,32,3")
                assert code == 200, out
                ok[0] += 1
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def _images_32(n, px):
        imgs = np.zeros((n, 32, 32, 3), np.uint8)
        imgs[:, 0, 0, 0] = px
        return imgs

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    try:
        # land a newer checkpoint mid-traffic (resume 4 → 8)
        cfg2 = _tiny_train(tmp_path, steps=8, name="mlp")
        train(cfg2)
        deadline = time.monotonic() + 30.0
        while srv.backend.model_step < 8 and time.monotonic() < deadline:
            time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join(30)

    assert errors == []
    assert srv.backend.model_step == 8 and srv.backend.reloads >= 1
    stats = srv.batcher.stats()
    assert stats["failed"] == 0 and stats["rejected"] == 0
    assert stats["batch_size_mean"] > 1.0, stats
    assert ok[0] > 50

    assert srv.drain(10.0) is True
    srv.close()
    time.sleep(0.2)
    leftovers = [t.name for t in threading.enumerate()
                 if t.name.startswith("tpu-resnet-serve")
                 and t.is_alive()]
    assert leftovers == []


@pytest.mark.slow
def test_doctor_serve_probe_contract():
    """doctor --serve-probe: subprocess CLI server comes ready, answers
    predicts, SIGTERM-drains to exit 0."""
    from tpu_resnet.tools.doctor import _check_serve_probe

    out = _check_serve_probe()
    assert out["ok"], out
    assert out["requests_ok"] == 5 and out["drain_rc"] == 0
    assert out["served_total"] >= 5


def test_serve_latency_histograms_and_run_id(fake_server, tmp_path):
    """The histogram exposition replaces the scalar-gauge-only view:
    after real traffic, /metrics carries serve_latency_ms /
    serve_queue_wait_ms / serve_pad_fraction histogram series with
    consistent counts, and /info + serve.json expose the run_id of the
    served train_dir."""
    from tpu_resnet.obs.server import (histogram_quantile,
                                       parse_histograms)
    from tpu_resnet.serve.server import write_discovery

    srv, backend = fake_server
    # pre-traffic: series pre-declared, empty — present, not absent
    _, body = _get(srv.port, "/metrics")
    hists = parse_histograms(body.decode())
    assert hists["tpu_resnet_serve_latency_ms"]["count"] == 0
    n_req = 6
    for i in range(n_req):
        img = np.full((1, 8, 8, 3), i, np.uint8)
        status, _ = _post(srv.port, img.tobytes(), shape="1,8,8,3")
        assert status == 200
    _, body = _get(srv.port, "/metrics")
    text = body.decode()
    hists = parse_histograms(text)
    lat = hists["tpu_resnet_serve_latency_ms"]
    wait = hists["tpu_resnet_serve_queue_wait_ms"]
    pad = hists["tpu_resnet_serve_pad_fraction"]
    assert lat["count"] == n_req == wait["count"]
    assert pad["count"] >= 1  # one sample per dispatched batch
    assert 0 < histogram_quantile(lat, 0.5) <= \
        histogram_quantile(lat, 0.99)
    # queue wait is bounded by latency for every request
    assert histogram_quantile(wait, 0.5) <= histogram_quantile(lat, 0.99)
    assert lat["sum"] >= wait["sum"] >= 0

    # run_id: no train run in this dir → honest null in /info, and
    # write_discovery records whatever the server resolved
    _, body = _get(srv.port, "/info")
    info = json.loads(body)
    assert "run_id" in info and info["run_id"] is None
    write_discovery(str(tmp_path), srv.port, run_id="abc123def456")
    with open(tmp_path / "serve.json") as f:
        assert json.load(f)["run_id"] == "abc123def456"


def test_serve_spans_written_with_run_id(tmp_path):
    """serve() components write serve_events.jsonl spans (warmup, drain)
    stamped with the train_dir's run_id — the serve lane trace-export
    renders."""
    from tpu_resnet.obs import ensure_run_id
    from tpu_resnet.obs.spans import SpanTracer, load_spans
    from tpu_resnet.obs.trace import SERVE_EVENTS_FILE

    cfg = _serve_cfg()
    cfg.train.train_dir = str(tmp_path)
    rid = ensure_run_id(str(tmp_path))
    spans = SpanTracer(str(tmp_path), filename=SERVE_EVENTS_FILE,
                       run_id=rid)
    srv = PredictServer(cfg, backend=FakeBackend(), spans=spans).start()
    assert srv.run_id == rid  # resolved from the served train_dir
    img = np.zeros((1, 8, 8, 3), np.uint8)
    assert _post(srv.port, img.tobytes(), shape="1,8,8,3")[0] == 200
    srv.drain(5.0)
    srv.close()
    spans.close()
    recs = load_spans(str(tmp_path / SERVE_EVENTS_FILE))
    kinds = [r["span"] for r in recs]
    assert kinds[0] == "serve_warmup" and "serve_drain" in kinds
    assert all(r["run_id"] == rid for r in recs)
    drain = next(r for r in recs if r["span"] == "serve_drain")
    assert drain["clean"] is True


# ------------------------------------------- fleet satellites (ISSUE 13)

def test_429_carries_retry_after_and_queue_depth_in_info():
    """Backpressure responses carry Retry-After (the router/client
    backoff hint) and /info exposes queue_depth top-level so the
    router's passive signal is one scrape."""
    entered, release = threading.Event(), threading.Event()

    class StuckBackend(FakeBackend):
        def infer(self, images):
            entered.set()
            release.wait(10.0)
            return super().infer(images)

    backend = StuckBackend()
    srv = PredictServer(_serve_cfg(max_queue=1, max_wait_ms=1.0),
                        backend=backend).start()
    try:
        first = srv.batcher.submit(_images(1))
        assert entered.wait(5.0)            # worker pinned mid-batch
        srv.batcher.submit(_images(1))      # queue now full (1 slot)
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/predict",
            data=_images(1).tobytes(),
            headers={"Content-Type": "application/octet-stream",
                     "X-Shape": "1,8,8,3"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 429
        assert exc.value.headers.get("Retry-After") is not None
        payload = json.loads(exc.value.read())
        assert payload["retryable"] and "retry_after_secs" in payload

        code, body = _get(srv.port, "/info")
        info = json.loads(body)
        assert info["queue_depth"] >= 1           # top-level, one scrape
        assert info["queue_depth"] == info["stats"]["queue_depth"]
        assert info["replica_name"] == ""
    finally:
        release.set()
        first.wait(5.0)
        srv.batcher.drain(5.0)
        srv.close()


def test_x_lane_header_routes_to_batch_lane(fake_server):
    srv, backend = fake_server
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/predict",
        data=_images(1, 2).tobytes(),
        headers={"Content-Type": "application/octet-stream",
                 "X-Shape": "1,8,8,3", "X-Lane": "batch"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    # unknown lanes degrade to interactive (strict lane), not a 500
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/predict",
        data=_images(1, 2).tobytes(),
        headers={"Content-Type": "application/octet-stream",
                 "X-Shape": "1,8,8,3", "X-Lane": "bulk"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    stats = srv.batcher.stats()
    assert stats["lane_batch"] == 1 and stats["lane_interactive"] >= 1


def test_named_discovery_for_fleets(tmp_path):
    from tpu_resnet.serve.server import read_serve_port, write_discovery

    write_discovery(str(tmp_path), 8001, name="r0")
    write_discovery(str(tmp_path), 8002, name="r1")
    write_discovery(str(tmp_path), 8003)
    assert (tmp_path / "serve-r0.json").exists()
    assert (tmp_path / "serve-r1.json").exists()
    # the bare serve.json single-replica contract is untouched
    assert read_serve_port(str(tmp_path)) == 8003
    with open(tmp_path / "serve-r0.json") as f:
        assert json.load(f)["name"] == "r0"


def test_drain_during_reload_finishes_swap_before_teardown():
    """The drain-during-reload lock contract at the server level: a
    drain landing while maybe_reload() is mid-swap waits for the swap to
    complete (the batcher finishes its between-batches hook before
    exiting); the model is never observable half-swapped."""
    reload_started, finish_reload = threading.Event(), threading.Event()

    class SlowReloadBackend(FakeBackend):
        def maybe_reload(self):
            if self.reload_armed:
                self.reload_armed = False
                reload_started.set()
                finish_reload.wait(10.0)   # mid-swap window
                self.model_step += 1
                self.reloads += 1
                return True
            return False

    backend = SlowReloadBackend()
    srv = PredictServer(_serve_cfg(), backend=backend).start()
    assert _post(srv.port, _images(1, 3).tobytes(),
                 shape="1,8,8,3")[0] == 200
    backend.reload_armed = True
    assert reload_started.wait(5.0)        # batcher is inside the swap
    drained = []
    t = threading.Thread(target=lambda: drained.append(srv.drain(10.0)))
    t.start()
    time.sleep(0.2)
    assert not drained                     # drain is waiting on the swap
    assert backend.model_step == 7         # never half-swapped
    finish_reload.set()
    t.join(10.0)
    assert drained == [True]
    assert backend.model_step == 8 and backend.reloads == 1
    srv.close()


def test_checkpoint_backend_close_blocks_until_swap_completes(
        monkeypatch):
    """The backend-level lock ordering (serve/backend.py): close() must
    wait out an in-flight restore+swap, and a swap that loses the race
    aborts cleanly instead of touching a closed manager."""
    from tpu_resnet.serve.backend import CheckpointBackend

    restore_entered, release_restore = threading.Event(), threading.Event()

    class FakeState:
        params = {"w": 1}
        batch_stats = {"m": 2}

    def slow_restore(ckpt, template, step, retries, backoff_sec):
        restore_entered.set()
        release_restore.wait(10.0)
        return FakeState()

    monkeypatch.setattr("tpu_resnet.train.checkpoint.restore_with_retry",
                        slow_restore)

    class FakeCkpt:
        closed = False

        def close(self):
            # the lock contract: never closed while a swap is mid-flight
            assert restore_entered.is_set() and release_restore.is_set()
            self.closed = True

    class FakePoller:
        seen = []

        def mark_seen(self, step):
            self.seen.append(step)

    b = CheckpointBackend.__new__(CheckpointBackend)
    b._cfg = load_config("smoke")
    b._ckpt = FakeCkpt()
    b._poller = FakePoller()
    b._template = object()   # opaque: the mocked restore ignores it
    b._swap_lock = threading.Lock()
    b._closed = False
    b._variables = None
    b.model_step = -1
    b.quantize = "off"

    results = []
    loader = threading.Thread(target=lambda: results.append(b._load(5)))
    loader.start()
    assert restore_entered.wait(5.0)       # swap is mid-restore
    closer = threading.Thread(target=b.close)
    closer.start()
    time.sleep(0.2)
    assert not b._ckpt.closed              # close() is blocked on the lock
    release_restore.set()
    loader.join(5.0)
    closer.join(5.0)
    assert results == [True]
    assert b.model_step == 5               # swap completed before close
    assert b._variables == {"params": {"w": 1}, "batch_stats": {"m": 2}}
    assert b._ckpt.closed
    # post-close reload attempts abort cleanly (no manager access)
    assert b._load(6) is False


def test_serve_request_trace_spans_and_echo(tmp_path):
    """Replica-side hop of a distributed trace: X-Trace-Id echoes on the
    response, untraced requests never enter the tail sampler, errors are
    always-keep spans, and kept spans carry the batcher's timing
    segments (queue wait / inference / pad fraction)."""
    from tpu_resnet.obs.spans import SpanTracer, load_spans
    from tpu_resnet.obs.trace import SERVE_EVENTS_FILE

    cfg = _serve_cfg(replica_name="r7", max_wait_ms=5.0)
    cfg.train.train_dir = str(tmp_path)
    spans = SpanTracer(str(tmp_path), filename=SERVE_EVENTS_FILE)
    srv = PredictServer(cfg, backend=FakeBackend(), spans=spans).start()

    def post(body, shape=None, trace=None):
        headers = {"Content-Type": "application/octet-stream",
                   **({"X-Shape": shape} if shape else {}),
                   **({"X-Trace-Id": trace} if trace else {})}
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/predict", data=body,
            headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
                return r.status, dict(r.headers)
        except urllib.error.HTTPError as e:
            e.read()
            return e.code, dict(e.headers)

    try:
        code, headers = post(_images(2, 3).tobytes(), "2,8,8,3",
                             trace="t-ok")
        assert code == 200 and headers.get("X-Trace-Id") == "t-ok"
        # no client trace id -> no echo, no sampler observation (the
        # router and loadgen are the minting authorities, not the hop)
        code, headers = post(_images(1, 3).tobytes(), "1,8,8,3")
        assert code == 200 and "X-Trace-Id" not in headers
        # a traced parse error is an always-keep span class
        code, headers = post(b"bogus", "9,9", trace="t-err")
        assert code == 400 and headers.get("X-Trace-Id") == "t-err"
        assert srv.sampler.stats()["observed"] == 2
        err = [s for s in load_spans(str(tmp_path / SERVE_EVENTS_FILE))
               if s.get("span") == "serve_request"
               and s.get("trace_id") == "t-err"]
        assert len(err) == 1
        assert err[0]["sampled"] == "error" and err[0]["status"] == 400
        assert err[0]["replica"] == "r7"
        # past the sampler's base period a kept 200 span lands with the
        # batcher's segment attribution
        for i in range(60):
            post(_images(1, i % 7).tobytes(), "1,8,8,3", trace=f"t-{i}")
        kept = [s for s in load_spans(str(tmp_path / SERVE_EVENTS_FILE))
                if s.get("span") == "serve_request"
                and s.get("status") == 200]
        assert kept, "no 200 serve_request span after 62 requests"
        for key in ("queue_wait_ms", "infer_ms", "pad_fraction",
                    "batch_size", "n", "latency_ms", "lane"):
            assert key in kept[0], key
    finally:
        srv.batcher.drain(5.0)
        srv.close()
