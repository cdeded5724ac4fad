"""Multi-process distributed-backend integration test.

The reference's only multi-node test was a localhost fake cluster: N OS
processes forming a real ps/worker cluster over local ports
(mkl-scripts/submit_mac_dist.sh, SURVEY.md §4). This is the TPU-native
analog: two OS processes rendezvous through ``jax.distributed.initialize``
on 127.0.0.1, each owning 4 virtual CPU devices, and run real data-parallel
training steps over the resulting 8-device global mesh — exercising the
launcher env protocol (TPU_COORDINATOR_ADDRESS/TPU_NUM_PROCESSES/
TPU_PROCESS_ID), per-process input sharding, global-batch assembly via
``make_array_from_process_local_data``, and cross-process gradient
all-reduce.
"""

import os
import socket
import subprocess
import sys

import pytest

PREAMBLE = r"""
import os, sys, json
import jax
jax.config.update("jax_platforms", "cpu")

from tpu_resnet import parallel
"""

WORKER = PREAMBLE + r"""
parallel.initialize()  # from TPU_* env vars (launcher protocol)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert jax.local_device_count() == 4

import jax.numpy as jnp
import numpy as np
from tpu_resnet.config import load_config
from tpu_resnet.data import pipeline
from tpu_resnet.data.cifar import synthetic_data
from tpu_resnet.models import build_model
from tpu_resnet.train import build_schedule, init_state
from tpu_resnet.train.step import make_train_step, shard_step

cfg = load_config("smoke")
cfg.train.global_batch_size = 16
mesh = parallel.create_mesh(cfg.mesh)
model = build_model(cfg)
sched = build_schedule(cfg.optim, cfg.train)
state = init_state(model, cfg.optim, sched, jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3)))
state = jax.device_put(state, parallel.replicated(mesh))
step_fn = shard_step(
    make_train_step(model, cfg.optim, sched, 10, augment_fn=None,
                    base_rng=jax.random.PRNGKey(1)), mesh)

images, labels = synthetic_data(64, 32, 10, seed=0)
local_bs = parallel.local_batch_size(cfg.train.global_batch_size, mesh)
assert local_bs == 8
batcher = pipeline.ShardedBatcher(images, labels.astype(np.int32), local_bs,
                                  seed=0)
it = pipeline.device_prefetch(iter(batcher), parallel.batch_sharding(mesh))
for i in range(4):
    gi, gl = next(it)
    assert gi.shape[0] == 16  # global batch
    state, metrics = step_fn(state, gi, gl)
loss = float(jax.device_get(metrics["loss"]))
print(json.dumps({"process": jax.process_index(), "loss": loss,
                  "step": int(jax.device_get(state.step))}))
"""


EVAL_WORKER = PREAMBLE + r"""
parallel.initialize()  # from TPU_* env vars (launcher protocol)
assert jax.process_count() == 2

import jax.numpy as jnp
from tpu_resnet.config import load_config
from tpu_resnet.evaluation.evaluator import (build_eval_step,
                                             run_eval_pass,
                                             _template_state)

cfg = load_config("smoke")
# 256 synthetic eval examples with local batch 12: the 128-record stripes
# end in a partial (padded) batch, and the run terminates via the
# padding-round lockstep signal.
cfg.train.eval_batch_size = 24
mesh = parallel.create_mesh(cfg.mesh)
model, eval_step_fn = build_eval_step(cfg, mesh)
state = _template_state(cfg, model, mesh)
precision, loss, count = run_eval_pass(cfg, state, mesh, eval_step_fn)
print(json.dumps({"process": jax.process_index(),
                  "precision": precision, "loss": loss, "count": count}))
"""


IMAGENET_WORKER = PREAMBLE + r"""
import io
import numpy as np
from PIL import Image

from tpu_resnet.config import load_config
from tpu_resnet.data import tfrecord
from tpu_resnet.train.loop import train

data_dir = os.path.join(os.getcwd(), "shards")
# Process 0 generates the shards; both rendezvous afterwards.
if os.environ["TPU_PROCESS_ID"] == "0":
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    for s in range(4):
        records = []
        for _ in range(12):
            arr = rng.integers(0, 256, (40, 48, 3), np.uint8)
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, "JPEG")
            records.append(tfrecord.encode_example({
                "image/encoded": [buf.getvalue()],
                "image/class/label": [int(rng.integers(1, 1001))],
            }))
        tfrecord.write_records(
            os.path.join(data_dir, f"train-{s:05d}-of-00004"), records)
    open(os.path.join(os.getcwd(), "shards.done"), "w").close()
else:
    import time
    deadline = time.time() + 120
    while not os.path.exists(os.path.join(os.getcwd(), "shards.done")):
        if time.time() > deadline:
            sys.exit("timed out waiting for process 0's shards")
        time.sleep(0.5)

parallel.initialize()
assert jax.process_count() == 2

cfg = load_config("imagenet")
cfg.data.data_dir = data_dir
cfg.data.image_size = 32
cfg.data.eval_resize = 36
cfg.data.resize_min, cfg.data.resize_max = 36, 48
cfg.data.num_workers = 1
cfg.data.transfer_stage = 2      # staged superbatches + fused dispatch
cfg.data.shuffle_buffer = 16
cfg.model.resnet_size = 18
cfg.model.compute_dtype = "float32"
cfg.optim.schedule = "constant"
cfg.train.global_batch_size = 8  # 4 per process
cfg.train.train_steps = 4
cfg.train.checkpoint_every = 4
cfg.train.log_every = 2
cfg.train.train_dir = os.path.join(os.getcwd(), "run")

state = train(cfg)
loss = None
mfile = os.path.join(cfg.train.train_dir, "metrics.jsonl")
if jax.process_index() == 0:  # MetricsWriter is primary-only
    with open(mfile) as f:
        for line in f:
            loss = json.loads(line).get("loss", loss)
print(json.dumps({"process": jax.process_index(),
                  "step": int(jax.device_get(state.step)),
                  "loss": loss}))
"""


def _run_two_process(script, tmp_path):
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{port.getsockname()[1]}"
    port.close()

    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["TPU_COORDINATOR_ADDRESS"] = coord
        env["TPU_NUM_PROCESSES"] = "2"
        env["TPU_PROCESS_ID"] = str(pid)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env, cwd=str(tmp_path),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=560)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:  # never leak the sibling worker when one fails
        for p in procs:
            if p.poll() is None:
                p.kill()

    import json
    return [json.loads(o.strip().splitlines()[-1]) for o in outs]


# ----------------------------------------------------- fast unit tier
# parallel/multihost.py joins the SPMD-lint scope this PR; its env
# protocol gets direct unit coverage (the two-process integration tests
# below stay slow-tier).
def _clear_tpu_env(monkeypatch):
    for var in ("TPU_COORDINATOR_ADDRESS", "TPU_NUM_PROCESSES",
                "TPU_PROCESS_ID", "TPU_PROCS_PER_NODE",
                "TPU_LOCAL_RANK", "TPU_CHIPS_PER_NODE"):
        monkeypatch.delenv(var, raising=False)


def test_initialize_single_process_is_noop(monkeypatch):
    """No coordinator configured → the serial branch: never calls
    jax.distributed.initialize (the reference's serial path analog)."""
    import jax

    from tpu_resnet.parallel import multihost

    _clear_tpu_env(monkeypatch)
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    multihost.initialize()
    assert calls == []


def test_initialize_env_resolution_order(monkeypatch):
    """Explicit args beat the TPU_* launcher env vars, which beat
    auto-detection — the documented resolution order."""
    import jax

    from tpu_resnet.parallel import multihost

    _clear_tpu_env(monkeypatch)
    monkeypatch.setenv("TPU_COORDINATOR_ADDRESS", "10.0.0.1:8476")
    monkeypatch.setenv("TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("TPU_PROCESS_ID", "3")
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    multihost.initialize()
    assert calls[-1]["coordinator_address"] == "10.0.0.1:8476"
    assert calls[-1]["num_processes"] == 4
    assert calls[-1]["process_id"] == 3
    # explicit args override the env protocol
    multihost.initialize(coordinator_address="127.0.0.1:9",
                         num_processes=2, process_id=1)
    assert calls[-1]["coordinator_address"] == "127.0.0.1:9"
    assert calls[-1]["num_processes"] == 2
    assert calls[-1]["process_id"] == 1


def test_initialize_multi_proc_per_node_device_slices(monkeypatch):
    """TPU_PROCS_PER_NODE > 1: each colocated process claims a disjoint
    chip slice from its node-local rank; an over-subscribed node raises
    the named ValueError."""
    import jax

    from tpu_resnet.parallel import multihost

    _clear_tpu_env(monkeypatch)
    monkeypatch.setenv("TPU_COORDINATOR_ADDRESS", "127.0.0.1:9")
    monkeypatch.setenv("TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("TPU_PROCESS_ID", "1")
    monkeypatch.setenv("TPU_PROCS_PER_NODE", "2")
    monkeypatch.setenv("TPU_LOCAL_RANK", "1")
    monkeypatch.setenv("TPU_CHIPS_PER_NODE", "4")
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    multihost.initialize()
    assert calls[-1]["local_device_ids"] == [2, 3]
    monkeypatch.setenv("TPU_PROCS_PER_NODE", "8")
    with pytest.raises(ValueError, match="TPU_PROCS_PER_NODE"):
        multihost.initialize()


def test_is_primary_is_process_index_zero(monkeypatch):
    import jax

    from tpu_resnet.parallel import multihost

    monkeypatch.setattr(jax, "process_index", lambda: 0)
    assert multihost.is_primary() is True
    monkeypatch.setattr(jax, "process_index", lambda: 2)
    assert multihost.is_primary() is False


@pytest.mark.slow
def test_two_process_data_parallel(tmp_path):
    results = _run_two_process(WORKER, tmp_path)
    assert {r["process"] for r in results} == {0, 1}
    assert all(r["step"] == 4 for r in results)
    # SPMD: both processes computed the identical global loss.
    assert abs(results[0]["loss"] - results[1]["loss"]) < 1e-6


@pytest.mark.slow
def test_two_process_imagenet_streaming_train(tmp_path):
    """The ImageNet input edge end-to-end across processes: shard files
    striped per process, staged superbatch transfers, fused multi-step
    dispatch, cross-process gradient allreduce, and a multi-host orbax
    checkpoint at the end — the combination no single-process test
    covers."""
    results = _run_two_process(IMAGENET_WORKER, tmp_path)
    assert {r["process"] for r in results} == {0, 1}
    assert all(r["step"] == 4 for r in results)
    p0 = next(r for r in results if r["process"] == 0)
    assert p0["loss"] is not None and float(p0["loss"]) > 0
    # the final checkpoint exists and is complete
    assert (tmp_path / "run" / "4").is_dir()


@pytest.mark.slow
def test_two_process_eval_pass(tmp_path):
    """Standalone multi-host eval (VERDICT round 1 item 4): both processes
    stream disjoint stripes, agree on the global precision, and count every
    example exactly once."""
    results = _run_two_process(EVAL_WORKER, tmp_path)
    assert {r["process"] for r in results} == {0, 1}
    assert all(r["count"] == 256 for r in results)
    assert abs(results[0]["precision"] - results[1]["precision"]) < 1e-9
    assert abs(results[0]["loss"] - results[1]["loss"]) < 1e-6
