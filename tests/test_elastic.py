"""Elastic-capacity subsystem tests (tpu_resnet/resilience/elastic.py):
mesh fitting on whatever devices exist, topology records + reshape
detection, THE cross-mesh restore matrix (mesh8→4 / 4→8, each ×
replicated/zero1, value-identical), topology-naming restore errors, the
supervisor's decorrelated-jitter + downsize policy, the preemption-burst
injector, HBM colocation admission — and the slow-tier drills: a real
in-loop reshape resume and the train+serve colocation scenario."""

import json
import os
import signal
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet import parallel
from tpu_resnet.config import load_config
from tpu_resnet.data import pipeline
from tpu_resnet.models import build_model
from tpu_resnet.resilience import elastic
from tpu_resnet.train import build_schedule
from tpu_resnet.train.state import init_partitioned_state
from tpu_resnet.train.step import make_train_step, shard_step

P = jax.sharding.PartitionSpec

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke_cfg(n=8, partition="replicated", train_dir=""):
    cfg = load_config("smoke")
    cfg.data.dataset = "synthetic"
    cfg.data.device_resident = "off"
    cfg.data.transfer_stage = 1
    cfg.model.name = "mlp"
    cfg.train.global_batch_size = 16
    cfg.mesh.data = n
    cfg.mesh.partition = partition
    if train_dir:
        cfg.train.train_dir = str(train_dir)
    return cfg


# ------------------------------------------------------------- mesh fitting
def test_fit_mesh():
    cfg = _smoke_cfg(8)
    assert parallel.fit_mesh(cfg.mesh, 8) == (8, 1, False)
    # Explicit data that no longer fits shrinks to what does (8 chips
    # requested, 4 exist) — downsized=True is the reshape signal.
    assert parallel.fit_mesh(cfg.mesh, 4) == (4, 1, True)
    assert parallel.fit_mesh(cfg.mesh, 2) == (2, 1, True)
    # Explicit data that fits is honored exactly (no implicit growth).
    cfg.mesh.data = 4
    assert parallel.fit_mesh(cfg.mesh, 8) == (4, 1, False)
    # -1 follows the hardware in both directions.
    cfg.mesh.data = -1
    assert parallel.fit_mesh(cfg.mesh, 8) == (8, 1, False)
    assert parallel.fit_mesh(cfg.mesh, 2) == (2, 1, False)
    # A device count the model axis doesn't divide drops the remainder
    # (7 devices at model=2 train on 6) instead of dying.
    cfg.mesh.model = 2
    assert parallel.fit_mesh(cfg.mesh, 7) == (3, 2, True)
    # The model axis is a hard constraint, never elastic.
    cfg.mesh.model = 4
    with pytest.raises(ValueError, match="model axis"):
        parallel.fit_mesh(cfg.mesh, 2)
    # A nonsense data size is an actionable error, not a 0-device mesh
    # that dies later in a ZeroDivisionError.
    cfg.mesh.model = 1
    cfg.mesh.data = 0
    with pytest.raises(ValueError, match="mesh.data must be"):
        parallel.fit_mesh(cfg.mesh, 8)


def test_topology_record_roundtrip(tmp_path):
    mesh = parallel.create_mesh(_smoke_cfg(8).mesh,
                                devices=jax.devices()[:8])
    path = elastic.write_topology(str(tmp_path), mesh, "zero1", 16)
    assert path and os.path.exists(path)
    rec = elastic.read_topology(str(tmp_path))
    assert rec["mesh_shape"] == {"data": 8, "model": 1}
    assert rec["partition"] == "zero1"
    assert rec["global_batch"] == 16
    assert rec["devices"] == 8
    assert "mesh" in elastic.describe(rec)
    assert elastic.read_topology(str(tmp_path / "missing")) is None


def test_resolve_detects_reshape(tmp_path):
    """A prior mesh8/replicated record + a mesh4/zero1 restart = a
    detected topology change with both sides named in the span attrs."""
    cfg8 = _smoke_cfg(8, train_dir=tmp_path)
    mesh8 = parallel.create_mesh(cfg8.mesh, devices=jax.devices()[:8])
    elastic.write_topology(str(tmp_path), mesh8, "replicated", 16)

    cfg4 = _smoke_cfg(4, partition="zero1", train_dir=tmp_path)
    resume = elastic.resolve(cfg4)
    assert dict(resume.mesh.shape) == {"data": 4, "model": 1}
    assert resume.changed and resume.stream_compatible
    attrs = resume.attrs()
    assert attrs["from_mesh"] == {"data": 8, "model": 1}
    assert attrs["to_mesh"] == {"data": 4, "model": 1}
    assert attrs["from_partition"] == "replicated"
    assert attrs["to_partition"] == "zero1"
    assert attrs["stream_compatible"] is True

    # Same topology again: no change, nothing to announce.
    elastic.write_topology(str(tmp_path), resume.mesh, "zero1", 16)
    again = elastic.resolve(cfg4)
    assert not again.changed


def test_resolve_downsizes_explicit_mesh(tmp_path):
    """mesh.data=8 on a 4-device host resumes on a 4-way mesh instead of
    dying — the elastic headline."""
    cfg = _smoke_cfg(8, train_dir=tmp_path)
    resume = elastic.resolve(cfg, devices=jax.devices()[:4])
    assert resume.downsized and resume.requested_data == 8
    assert dict(resume.mesh.shape) == {"data": 4, "model": 1}
    assert resume.attrs()["downsized_from_requested_data"] == 8


def test_resolve_global_batch_error_names_topology(tmp_path):
    """The global batch is the determinism invariant: a data axis it
    cannot divide is a topology-naming error, never a silent rescale."""
    cfg8 = _smoke_cfg(8, train_dir=tmp_path)
    mesh8 = parallel.create_mesh(cfg8.mesh, devices=jax.devices()[:8])
    elastic.write_topology(str(tmp_path), mesh8, "replicated", 16)
    cfg = _smoke_cfg(3, train_dir=tmp_path)
    with pytest.raises(ValueError) as e:
        elastic.resolve(cfg, devices=jax.devices()[:3])
    msg = str(e.value)
    assert "16" in msg and "3-way" in msg
    assert "checkpoint topology" in msg and "'data': 8" in msg


def test_resolve_marks_changed_global_batch_stream_incompatible(tmp_path):
    cfg8 = _smoke_cfg(8, train_dir=tmp_path)
    mesh8 = parallel.create_mesh(cfg8.mesh, devices=jax.devices()[:8])
    elastic.write_topology(str(tmp_path), mesh8, "replicated", 16)
    cfg = _smoke_cfg(8, train_dir=tmp_path)
    cfg.train.global_batch_size = 32
    resume = elastic.resolve(cfg)
    assert resume.changed and not resume.stream_compatible
    assert resume.attrs()["stream_compatible"] is False


# ------------------------------------------------- cross-mesh restore matrix
def _built_state(n, partition, steps=1):
    """A partitioned MLP TrainState on an n-way mesh with non-trivial
    momentum (``steps`` real updates)."""
    cfg = _smoke_cfg(n, partition)
    mesh = parallel.create_mesh(cfg.mesh, devices=jax.devices()[:n])
    part = parallel.make_partitioner(cfg.mesh, mesh)
    model = build_model(cfg)
    sched = build_schedule(cfg.optim, cfg.train)
    state = init_partitioned_state(model, cfg.optim, sched,
                                   jax.random.PRNGKey(0),
                                   jnp.zeros((1, 32, 32, 3)), part)
    base = make_train_step(model, cfg.optim, sched, 10, None,
                           base_rng=jax.random.PRNGKey(1), mesh=mesh,
                           partitioner=part)
    fn = shard_step(base, mesh,
                    state_sharding=(part.state_shardings(state)
                                    if part.is_sharded else None))
    rng = np.random.default_rng(5)
    bs = parallel.batch_sharding(mesh)
    for _ in range(steps):
        gi, gl = pipeline.to_global_arrays(
            (rng.integers(0, 255, (16, 32, 32, 3)).astype(np.uint8),
             rng.integers(0, 10, 16).astype(np.int32)), bs)
        state, _ = fn(state, gi, gl)
    return cfg, mesh, state


def test_cross_mesh_restore_matrix(tmp_path):
    """THE acceptance matrix: a checkpoint saved on one (mesh, partition)
    restores on the other mesh shape in EITHER partition mode with
    value-identical params/opt_state — mesh8→4 from a replicated save,
    mesh4→8 from a zero1 save, templates built by partitioned_template
    on the target topology (the explicit cross-topology reshard)."""
    from tpu_resnet.train.checkpoint import (CheckpointManager,
                                             partitioned_template)

    for src_n, src_part, dst_n in ((8, "replicated", 4),
                                   (4, "zero1", 8)):
        _, _, state = _built_state(src_n, src_part)
        want = [np.asarray(x) for x in
                jax.tree_util.tree_leaves(jax.device_get(state))]
        d = tmp_path / f"{src_part}{src_n}"
        ckpt = CheckpointManager(str(d))
        ckpt.save(1, state)
        ckpt.wait()
        for dst_part in ("replicated", "zero1"):
            t_cfg = _smoke_cfg(dst_n, dst_part)
            dst_mesh = parallel.create_mesh(t_cfg.mesh,
                                            devices=jax.devices()[:dst_n])
            template = partitioned_template(t_cfg, dst_mesh)
            restored = ckpt.restore(template, step=1)
            got_leaves = jax.tree_util.tree_leaves(restored)
            # The restored leaves genuinely live on the TARGET mesh.
            devs = set()
            for leaf in got_leaves:
                if hasattr(leaf, "sharding"):
                    devs |= set(leaf.sharding.device_set)
            assert len(devs) == dst_n, (src_n, src_part, dst_n, dst_part)
            for w, g in zip(want,
                            jax.tree_util.tree_leaves(
                                jax.device_get(restored))):
                np.testing.assert_array_equal(w, np.asarray(g))
        ckpt.close()


def test_restore_error_names_both_topologies(tmp_path):
    """Satellite: a restore that fails in a directory with a topology
    record names the checkpoint's mesh/partition vs the requested one —
    not just a raw orbax error."""
    from tpu_resnet.resilience import corrupt_checkpoint
    from tpu_resnet.train.checkpoint import (CheckpointManager,
                                             partitioned_template)

    cfg, mesh, state = _built_state(8, "zero1", steps=0)
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, state)
    ckpt.wait()
    ckpt.close()
    elastic.write_topology(str(tmp_path), mesh, "zero1", 16)
    corrupt_checkpoint(str(tmp_path))

    t_cfg = _smoke_cfg(4)
    mesh4 = parallel.create_mesh(t_cfg.mesh, devices=jax.devices()[:4])
    reader = CheckpointManager(
        str(tmp_path),
        topology={"devices": 4, "mesh_shape": dict(mesh4.shape),
                  "partition": "replicated", "global_batch": 16})
    with pytest.raises(RuntimeError) as e:
        reader.restore(partitioned_template(t_cfg, mesh4), step=1,
                       fallback=False)
    msg = str(e.value)
    assert "checkpoint topology" in msg and "requested topology" in msg
    assert "zero1" in msg and "replicated" in msg
    assert "'data': 8" in msg and "'data': 4" in msg
    assert "topologies differ" in msg
    reader.close()


# --------------------------------------------- deterministic stream contract
def test_batch_stream_continues_bit_compatibly_across_reshape():
    """The host batch stream is a pure function of (seed, step) and the
    per-process batch — the mesh never enters it. A resume at step k
    (any mesh) yields exactly the batches an uninterrupted run sees."""
    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (64, 4, 4, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, 64).astype(np.int32)

    def take(start, n):
        it = iter(pipeline.ShardedBatcher(images, labels, 16, seed=3,
                                          process_index=0, process_count=1,
                                          start_step=start))
        return [next(it) for _ in range(n)]

    straight = take(0, 12)
    resumed = take(7, 5)  # "the mesh4 leg", steps 7..11
    for (si, sl), (ri, rl) in zip(straight[7:], resumed):
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(sl, rl)


# ----------------------------------------------------- supervisor policies
def test_downsize_policy_units():
    from tools.supervise import DownsizePolicy

    now = [1000.0]
    p = DownsizePolicy(threshold=3, window_sec=60.0, ladder=(4, 2),
                       clock=lambda: now[0])
    assert p.note_preempt() is None
    now[0] += 10
    assert p.note_preempt() is None
    now[0] += 10
    assert p.note_preempt() == 4      # 3 inside the window → first rung
    now[0] += 10
    assert p.note_preempt() is None   # history cleared on downsize
    now[0] += 10
    assert p.note_preempt() is None
    now[0] += 10
    assert p.note_preempt() == 2      # next rung
    now[0] += 10
    for _ in range(5):
        assert p.note_preempt() is None  # ladder exhausted: ride it out
    # Events older than the window never accumulate to a trigger.
    p2 = DownsizePolicy(threshold=2, window_sec=5.0, ladder=(4,),
                        clock=lambda: now[0])
    assert p2.note_preempt() is None
    now[0] += 100
    assert p2.note_preempt() is None  # first event expired
    now[0] += 1
    assert p2.note_preempt() == 4


def test_supervise_downsize_appends_mesh_override():
    """After N preemptions inside the window the supervisor restarts the
    SAME command with mesh.data=<rung> appended — later overrides win in
    the config system, so the trainer's elastic resume takes it."""
    from tools.supervise import supervise

    codes = iter([42, 42, 42, 0])
    calls = []
    rc = supervise(["python", "-m", "tpu_resnet", "train"],
                   max_restarts=10, preempt_delay=0.0, jitter=False,
                   downsize_after=2, downsize_window=600.0,
                   mesh_ladder=(4, 2),
                   run=lambda c: (calls.append(list(c)), next(codes))[1],
                   sleep=lambda s: None)
    assert rc == 0
    base = ["python", "-m", "tpu_resnet", "train"]
    assert calls[0] == base
    assert calls[1] == base                      # 1st preempt: no trigger
    assert calls[2] == base + ["mesh.data=4"]    # 2nd preempt: rung 1
    assert calls[3] == base + ["mesh.data=4"]    # sticky until next rung


# ------------------------------------------------------- preemption burst
def test_preempt_burst_plan_sources():
    from tpu_resnet.resilience import FaultPlan

    cfg = load_config("smoke", overrides=[
        "resilience.inject_preempt_burst=3",
        "resilience.inject_preempt_burst_every=7"])
    plan = FaultPlan.from_config(cfg.resilience, env={})
    assert plan.preempt_burst == 3 and plan.preempt_burst_every == 7
    assert plan.active
    env = {"TPU_RESNET_FAULT_PREEMPT_BURST": "2",
           "TPU_RESNET_FAULT_PREEMPT_BURST_EVERY": "5"}
    plan = FaultPlan.from_config(load_config("smoke").resilience, env=env)
    assert plan.preempt_burst == 2 and plan.preempt_burst_every == 5
    assert FaultPlan.from_config(load_config("smoke").resilience,
                                 env={}).active is False


def test_preempt_burst_fires_k_across_restarts(tmp_path, monkeypatch):
    """K SIGTERMs total, each S steps after its child's first boundary,
    counted in the train_dir (the firing kills the process that would
    remember it) — then the burst is spent and resumed children run
    clean."""
    from tpu_resnet.resilience import FaultInjector, FaultPlan

    kills = []
    monkeypatch.setattr(os, "kill",
                        lambda pid, sig: kills.append((pid, sig)))
    plan = FaultPlan(preempt_burst=2, preempt_burst_every=5)

    def child(resume_step):
        """One supervised child: boundaries every 5 steps from resume."""
        inj = FaultInjector(plan, train_dir=str(tmp_path))
        for step in range(resume_step, resume_step + 20, 5):
            before = len(kills)
            inj.maybe_sigterm(step)
            if len(kills) > before:
                return step, inj  # a real SIGTERM would stop the child
        return None, inj

    fired_at, inj = child(0)
    assert fired_at == 5 and inj.burst_fired == 1  # start 0 + every 5
    fired_at, inj = child(5)
    assert fired_at == 10 and inj.burst_fired == 2
    fired_at, inj = child(10)   # burst spent: the third child runs clean
    assert fired_at is None and inj.burst_fired == 2
    assert [s for _, s in kills] == [signal.SIGTERM] * 2
    with open(tmp_path / "fault_burst_state.json") as f:
        assert json.load(f) == {"fired": 2, "of": 2}


# --------------------------------------------------- colocation admission
def test_colocation_admission_verdicts(monkeypatch):
    from tpu_resnet.obs import memory as memory_obs

    fake_dev = [types.SimpleNamespace(device_kind="faketpu")]
    monkeypatch.setattr(memory_obs, "HBM_BYTES_BY_KIND",
                        (("faketpu", 1_000_000),))
    ok = elastic.colocation_admission(500_000, devices=fake_dev)
    assert ok["admit"] and ok["limit_bytes"] == 1_000_000
    assert ok["headroom_bytes"] == 950_000  # 5% reserve held back
    deny = elastic.colocation_admission(960_000, devices=fake_dev)
    assert not deny["admit"] and "denied" in deny["reason"]
    # No limit from anywhere: admit, but say it was not arbitrated.
    monkeypatch.setattr(memory_obs, "HBM_BYTES_BY_KIND", ())
    open_v = elastic.colocation_admission(10, devices=fake_dev)
    assert open_v["admit"] and "not arbitrated" in open_v["reason"]


def test_manifest_carries_topology_change():
    from tpu_resnet.obs.manifest import build_manifest

    cfg = _smoke_cfg(8)
    mesh = parallel.create_mesh(cfg.mesh, devices=jax.devices()[:8])
    m = build_manifest(cfg, mesh, run_id="abc",
                       extra={"topology_change": {"from_devices": 8,
                                                  "to_devices": 4}})
    assert m["topology_change"]["to_devices"] == 4
    assert m["run_id"] == "abc"  # extra merges, never clobbers the rest


def test_elastic_config_fields_round_trip():
    cfg = load_config("smoke", overrides=[
        "resilience.inject_preempt_burst=2",
        "serve.admission_hbm_bytes=1048576"])
    from tpu_resnet.config import RunConfig

    rt = RunConfig.from_dict(cfg.to_dict())
    assert rt.resilience.inject_preempt_burst == 2
    assert rt.serve.admission_hbm_bytes == 1048576


# ------------------------------------------------------------- slow drills
@pytest.mark.slow  # several in-process train() runs (~60s)
def test_in_loop_reshape_resume_matches_reference(tmp_path):
    """The tentpole, in-process: a mesh8/replicated run preempted at the
    step-4 checkpoint resumes as mesh4/zero1 and must log the SAME loss
    stream (≤1e-6) as an uninterrupted mesh8 run — plus the
    topology_change span, manifest entry, gauge-visible record and the
    rewritten topology.json."""
    from tpu_resnet.obs.spans import load_spans
    from tpu_resnet.train.loop import train

    def _cfg(n, partition, train_dir):
        cfg = _smoke_cfg(n, partition, train_dir)
        cfg.train.train_steps = 8
        cfg.train.log_every = 2
        cfg.train.summary_every = 2
        cfg.train.checkpoint_every = 4
        cfg.train.image_summary_every = 0
        cfg.train.steps_per_call = 1
        cfg.train.telemetry_port = -1
        return cfg

    def _losses(train_dir):
        out = {}
        with open(os.path.join(str(train_dir), "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if "loss" in rec:
                    out[rec["step"]] = rec["loss"]
        return out

    train(_cfg(8, "replicated", tmp_path / "ref"))
    train(_cfg(8, "replicated", tmp_path / "elastic"), max_steps=4)
    train(_cfg(4, "zero1", tmp_path / "elastic"))  # the reshape resume

    l_ref = _losses(tmp_path / "ref")
    l_e = _losses(tmp_path / "elastic")
    assert set(l_ref) == set(l_e) == {2, 4, 6, 8}
    for step in sorted(l_ref):
        assert l_ref[step] == pytest.approx(l_e[step], rel=1e-6,
                                            abs=1e-6), step

    reshapes = [s for s in load_spans(str(tmp_path / "elastic"
                                          / "events.jsonl"))
                if s["span"] == "topology_change"]
    assert len(reshapes) == 1
    assert reshapes[0]["from_mesh"] == {"data": 8, "model": 1}
    assert reshapes[0]["to_mesh"] == {"data": 4, "model": 1}
    assert reshapes[0]["to_partition"] == "zero1"
    assert reshapes[0]["step"] == 4  # resumed exactly at the checkpoint
    with open(tmp_path / "elastic" / "manifest.json") as f:
        assert json.load(f)["topology_change"]["to_devices"] == 4
    topo = elastic.read_topology(str(tmp_path / "elastic"))
    assert topo["mesh_shape"] == {"data": 4, "model": 1}
    assert topo["partition"] == "zero1"


@pytest.mark.slow  # supervisor driving real trainer children (~90s)
def test_supervise_burst_drives_downsize_end_to_end(tmp_path):
    """The full composition: a preemption burst (K=2 SIGTERMs, each 5
    steps after its child's first boundary) preempts two supervised
    children in a row; the downsize policy (threshold 2) reacts by
    restarting with mesh.data=4; the third child resumes the mesh8
    checkpoint on the smaller mesh (elastic reshard) and — the burst
    spent — trains to completion. Supervisor exits 0; the train_dir
    records the reshape and the burst count."""
    from tools.supervise import supervise
    from tpu_resnet.hostenv import scrubbed_cpu_env
    from tpu_resnet.obs.spans import load_spans

    d = str(tmp_path)
    env = scrubbed_cpu_env(8)
    cmd = [sys.executable, "-m", "tpu_resnet", "train",
           "--preset", "smoke", f"train.train_dir={d}",
           "train.train_steps=30", "train.checkpoint_every=5",
           "train.log_every=5", "train.summary_every=10",
           "train.image_summary_every=0", "train.steps_per_call=5",
           "train.global_batch_size=16", "model.name=mlp",
           "data.device_resident=off", "data.transfer_stage=1",
           "resilience.inject_preempt_burst=2",
           "resilience.inject_preempt_burst_every=5"]
    log_path = os.path.join(d, "supervised_children.log")

    def run(c):
        with open(log_path, "a") as log_fh:
            return subprocess.call(c, env=env, cwd=REPO_ROOT,
                                   stdout=log_fh,
                                   stderr=subprocess.STDOUT)

    rc = supervise(cmd, max_restarts=5, preempt_delay=0.0,
                   downsize_after=2, downsize_window=600.0,
                   mesh_ladder=(4,), run=run, sleep=lambda s: None)
    assert rc == 0, _file_tail(log_path)
    with open(tmp_path / "fault_burst_state.json") as f:
        assert json.load(f) == {"fired": 2, "of": 2}
    topo = elastic.read_topology(d)
    assert topo["mesh_shape"] == {"data": 4, "model": 1}
    reshapes = [s for s in load_spans(os.path.join(d, "events.jsonl"))
                if s["span"] == "topology_change"]
    assert reshapes and reshapes[-1]["to_mesh"] == {"data": 4, "model": 1}
    runs = [(s.get("start_step"), s.get("stop_step"))
            for s in load_spans(os.path.join(d, "events.jsonl"))
            if s["span"] == "run"]
    assert runs[-1][1] == 30  # the downsized child finished the job


def _file_tail(path, n=8):
    try:
        with open(path) as f:
            return f.read().strip().splitlines()[-n:]
    except OSError:
        return []


@pytest.mark.slow  # two live subprocesses sharing the fakepod (~90s)
def test_colocation_drill_trainer_and_serve_share_fakepod(tmp_path):
    """The colocation scenario: a trainer holds the fakepod, a serve
    replica asks admission before joining — denied (exit 3, a scheduler
    signal, not a crash) when its footprint exceeds the arbitrated
    headroom, admitted and serving beside the live trainer when it fits;
    then each tenant drains per its own contract (serve: drain → 0,
    trainer: SIGTERM → final checkpoint → 42)."""
    from tpu_resnet.hostenv import scrubbed_cpu_env
    from tpu_resnet.resilience.shutdown import PREEMPT_EXIT_CODE
    from tpu_resnet.serve.server import read_serve_port

    d = str(tmp_path)
    base_overrides = ["--preset", "smoke", f"train.train_dir={d}",
                      "train.image_summary_every=0", "model.name=mlp",
                      "data.device_resident=off", "data.transfer_stage=1",
                      "train.global_batch_size=16"]
    env = scrubbed_cpu_env(8)
    # Arbitration needs a limit the CPU backend cannot report, and the
    # product has no knob that assumes one: the serve children run the
    # CLI with a 1 GiB "cpu" row patched into the capacity table.
    serve_cli = [sys.executable, "-c",
                 "import sys; from tpu_resnet.obs import memory; "
                 "memory.HBM_BYTES_BY_KIND = (('cpu', 1 << 30),); "
                 "from tpu_resnet.main import main; "
                 "sys.exit(main(sys.argv[1:]))"]

    # Child output goes to FILES, not pipes: the long-running trainer
    # would fill a 64K pipe and deadlock (the doctor probes' rule).
    trainer_log = open(os.path.join(d, "trainer_child.log"), "w")
    serve_log = open(os.path.join(d, "serve_child.log"), "w")

    def _tail(path):
        try:
            with open(path) as f:
                return f.read().strip().splitlines()[-8:]
        except OSError:
            return []

    trainer = subprocess.Popen(
        [sys.executable, "-m", "tpu_resnet", "train"] + base_overrides
        + ["train.train_steps=100000", "train.checkpoint_every=10",
           "train.log_every=10", "train.summary_every=20",
           "train.steps_per_call=5"],
        env=env, cwd=REPO_ROOT, stdout=trainer_log,
        stderr=subprocess.STDOUT, text=True)
    serve_proc = None
    try:
        deadline = time.time() + 120
        while time.time() < deadline:  # serve needs a checkpoint
            if any(n.isdigit() for n in os.listdir(d)):
                break
            assert trainer.poll() is None, \
                _tail(os.path.join(d, "trainer_child.log"))
            time.sleep(0.5)
        else:
            pytest.fail("trainer wrote no checkpoint within 120s")

        serve_cmd = serve_cli + ["serve"] + base_overrides + ["serve.port=0", "serve.max_batch=4",
                                "serve.reload_interval_secs=0"]
        # Denied: asks for more than the arbitrated headroom → exit 3.
        denied = subprocess.run(
            serve_cmd + [f"serve.admission_hbm_bytes={2 << 30}"],
            env=env, cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, timeout=120)
        assert denied.returncode == 3, denied.stdout[-2000:]
        assert "admission denied" in denied.stdout

        # Admitted: fits beside the trainer → starts, becomes ready.
        serve_proc = subprocess.Popen(
            serve_cmd + [f"serve.admission_hbm_bytes={64 << 20}"],
            env=env, cwd=REPO_ROOT, stdout=serve_log,
            stderr=subprocess.STDOUT, text=True)
        import urllib.request

        ready = False
        deadline = time.time() + 180
        while time.time() < deadline and serve_proc.poll() is None:
            port = read_serve_port(d)
            if port is not None:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/healthz",
                            timeout=2) as r:
                        if json.loads(r.read()).get("ok"):
                            ready = True
                            break
                except (OSError, ValueError):
                    pass
            time.sleep(0.5)
        assert ready, (serve_proc.poll(),
                       _tail(os.path.join(d, "serve_child.log")))
        assert trainer.poll() is None  # colocated: both alive

        # Drain contracts: serve exits 0, trainer checkpoints and exits 42.
        serve_proc.send_signal(signal.SIGTERM)
        assert serve_proc.wait(timeout=120) == 0
        trainer.send_signal(signal.SIGTERM)
        assert trainer.wait(timeout=120) == PREEMPT_EXIT_CODE
    finally:
        for p in (serve_proc, trainer):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        trainer_log.close()
        serve_log.close()
