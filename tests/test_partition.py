"""State-partitioner subsystem tests (tpu_resnet/parallel/partition.py +
zero.py): the ZeRO-1 rule set, zero1-vs-replicated step parity on the
8-device fakepod, the cross-partition restore contract, and the golden
memory-budget acceptance gate — the mesh8 zero1 twin's optimizer-slot
argument bytes must stay ≤ 0.15x the replicated twin's with donation
intact (arXiv:2004.13336's ~1/8 cut, regression-locked)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet import parallel
from tpu_resnet.config import load_config
from tpu_resnet.data import pipeline
from tpu_resnet.models import build_model
from tpu_resnet.parallel.partition import (StatePartitioner,
                                           ZERO1_SMALL_LEAF_BYTES,
                                           check_partition_mode)
from tpu_resnet.train import build_schedule
from tpu_resnet.train.state import init_partitioned_state
from tpu_resnet.train.step import (check_step_config, make_train_step,
                                   shard_step)

P = jax.sharding.PartitionSpec

ANALYSIS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tpu_resnet", "analysis")


def _mesh(n=8, partition="replicated"):
    cfg = load_config("smoke")
    cfg.mesh.data = n
    cfg.mesh.partition = partition
    return cfg, parallel.create_mesh(cfg.mesh,
                                     devices=jax.devices()[:n])


# ------------------------------------------------------------- rule set
def test_partition_mode_validation():
    assert check_partition_mode("replicated") == "replicated"
    assert check_partition_mode("zero1") == "zero1"
    with pytest.raises(ValueError, match="mesh.partition must be one of"):
        check_partition_mode("zero2")  # a typo must not mean 'replicated'
    cfg, mesh = _mesh(8)
    assert parallel.make_partitioner(cfg.mesh, mesh).mode == "replicated"
    assert parallel.make_partitioner(None, mesh).mode == "replicated"


def test_zero1_slot_spec_rules():
    """The per-leaf rule: scalars and small indivisible leaves stay
    replicated, everything else shards on its FIRST data-divisible axis,
    a LARGE indivisible leaf is a validation error naming the leaf."""
    _, mesh = _mesh(8)
    part = StatePartitioner(mesh, "zero1")
    assert part.is_sharded
    assert part.slot_spec(()) == P()                      # step counts
    assert part.slot_spec((16, 16)) == P("data")          # first axis wins
    assert part.slot_spec((3, 3, 16, 16)) == P(None, None, "data")
    assert part.slot_spec((10,)) == P()                   # small head bias
    big = ZERO1_SMALL_LEAF_BYTES  # (bytes/4 floats) * 4B > threshold, odd
    assert part.slot_spec((big + 1,), nbytes=4 * (big + 1)) is None

    class FakeState:
        def __init__(self, opt):
            self.step = jnp.zeros((), jnp.int32)
            self.params = {}
            self.batch_stats = {}
            self.opt_state = opt

        def replace(self, **kw):
            out = FakeState(kw.get("opt_state", self.opt_state))
            out.__dict__.update({k: v for k, v in kw.items()})
            return out

    bad = FakeState({"huge_odd": jax.ShapeDtypeStruct((100003,),
                                                      jnp.float32)})
    with pytest.raises(ValueError) as e:
        part.validate(bad)
    msg = str(e.value)
    assert "huge_odd" in msg and "100003" in msg and "8-way" in msg


def test_zero1_is_identity_on_1way_data_axis():
    """zero1 over a 1-way data axis must take the replicated path
    everywhere (is_sharded False → plain optax chain, replicated
    placement) — pinned structurally here and as the config-matrix
    same_program_as twin (cifar10_rn8_f32_zero1_mesh1)."""
    import optax

    from tpu_resnet.parallel import zero

    _, mesh = _mesh(1, partition="zero1")
    part = StatePartitioner(mesh, "zero1")
    assert not part.is_sharded
    tx = optax.sgd(0.1, momentum=0.9)
    grads = {"w": jnp.ones((8, 4))}
    opt = tx.init(grads)
    plain = zero.make_update_fn(tx, None)
    ident = zero.make_update_fn(tx, part)
    j1 = str(jax.make_jaxpr(plain)(grads, opt, grads))
    j2 = str(jax.make_jaxpr(ident)(grads, opt, grads))
    assert j1 == j2


# --------------------------------------------------- fakepod step parity
def _build(partition, n=8, batch=16):
    cfg = load_config("smoke")
    cfg.data.dataset = "synthetic"
    cfg.model.name = "mlp"
    cfg.train.global_batch_size = batch
    cfg.mesh.data = n
    cfg.mesh.partition = partition
    mesh = parallel.create_mesh(cfg.mesh, devices=jax.devices()[:n])
    check_step_config(cfg, mesh.shape["data"])
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
    return cfg, mesh, part, state, fn


def test_zero1_replicated_step_parity_on_fakepod():
    """zero1 and replicated must produce bit-identical loss streams and
    parameters within 1e-6 over real steps on the 8-device fakepod —
    sharding the weight update changes WHERE math runs, never what it
    computes (the documented tolerance covers reduce-scatter reduction-
    order drift; observed bit-identical on this backend)."""
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (3, 16, 32, 32, 3)).astype(np.uint8)
    labs = rng.integers(0, 10, (3, 16)).astype(np.int32)
    out = {}
    for partition in ("replicated", "zero1"):
        _, mesh, part, state, fn = _build(partition)
        bs = parallel.batch_sharding(mesh)
        losses = []
        for i in range(3):
            gi, gl = pipeline.to_global_arrays((imgs[i], labs[i]), bs)
            state, m = fn(state, gi, gl)
            losses.append(float(jax.device_get(m["loss"])))
        out[partition] = (losses, jax.device_get(state))
        if partition == "zero1":
            # The slots genuinely live sharded: the hidden-layer momentum
            # carries a 'data' spec, the small head bias stays replicated.
            specs = {
                tuple(leaf.shape): leaf.sharding.spec
                for leaf in jax.tree_util.tree_leaves(state.opt_state)
                if hasattr(leaf, "sharding")}
            assert any("data" in str(s) for s in specs.values()), specs
            assert specs.get((10,)) == P()
    l_rep, s_rep = out["replicated"]
    l_z, s_z = out["zero1"]
    assert l_rep == l_z  # loss stream bit-identical on this backend
    for a, b in zip(jax.tree_util.tree_leaves(s_rep.params),
                    jax.tree_util.tree_leaves(s_z.params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_step_returns_the_state_in_the_layout_it_was_given():
    """The compiled step's out_shardings pin every state leaf to its
    input layout, so the next dispatch accepts it by construction. Left
    to the SPMD partitioner this held on the CPU but not on a four-chip
    TPU host: zero1 came back with a 16-element replicated leaf sharded
    over 'data' and the second dispatch raised."""
    for partition in ("replicated", "zero1"):
        _, mesh, part, state, fn = _build(partition)
        bs = parallel.batch_sharding(mesh)
        gi, gl = pipeline.to_global_arrays(
            (np.zeros((16, 32, 32, 3), np.uint8),
             np.zeros((16,), np.int32)), bs)
        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (state, gi, gl))
        out_state_sh, out_metrics_sh = fn.lower(*avals).compile() \
            .output_shardings
        for got, leaf in zip(jax.tree_util.tree_leaves(out_state_sh),
                             jax.tree_util.tree_leaves(state)):
            assert got.is_equivalent_to(leaf.sharding, leaf.ndim), \
                (leaf.shape, got, leaf.sharding)
        assert all(sh.spec == P() for sh in
                   jax.tree_util.tree_leaves(out_metrics_sh))


def test_state_argument_bytes_breakdown():
    """The analytic per-component breakdown the ledger/goldens record:
    zero1 cuts ONLY the optimizer slots; params and BN stats stay
    replicated (the forward/backward sees gathered weights)."""
    _, _, part_r, state, _ = _build("replicated")
    rep = part_r.state_argument_bytes(state)
    _, _, part_z, state_z, _ = _build("zero1")
    z = part_z.state_argument_bytes(state_z)
    assert z["params_argument_bytes"] == rep["params_argument_bytes"]
    assert z["batch_stats_argument_bytes"] == \
        rep["batch_stats_argument_bytes"]
    assert 0 < z["opt_state_argument_bytes"] \
        < 0.3 * rep["opt_state_argument_bytes"]


# --------------------------------------------------- restore contracts
def test_partitioned_template_is_abstract_and_sharded():
    from tpu_resnet.train.checkpoint import partitioned_template

    cfg, mesh = _mesh(8, partition="zero1")
    cfg.model.name = "mlp"
    cfg.data.dataset = "synthetic"
    template = partitioned_template(cfg, mesh)
    leaves = jax.tree_util.tree_leaves(template)
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    opt_specs = [x.sharding.spec
                 for x in jax.tree_util.tree_leaves(template.opt_state)]
    assert any("data" in str(s) for s in opt_specs)
    # params replicated for the forward — every partition mode
    assert all(s == P() for s in
               (x.sharding.spec
                for x in jax.tree_util.tree_leaves(template.params)))


def test_cross_partition_restore_reshards_never_corrupts(tmp_path):
    """A checkpoint saved under one partition restores under the other
    with identical global values — orbax stores global logical arrays,
    so a cross-partition restore is an explicit reshard into the
    template's layout, never a silent corruption (docs/PARALLELISM.md
    restore-compat matrix)."""
    from tpu_resnet.train.checkpoint import (CheckpointManager,
                                             partitioned_template)

    cfg, mesh, part, state, fn = _build("zero1")
    rng = np.random.default_rng(3)
    bs = parallel.batch_sharding(mesh)
    gi, gl = pipeline.to_global_arrays(
        (rng.integers(0, 255, (16, 32, 32, 3)).astype(np.uint8),
         rng.integers(0, 10, 16).astype(np.int32)), bs)
    state, _ = fn(state, gi, gl)  # non-trivial momentum in the slots
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, state)
    ckpt.wait()
    for target in ("replicated", "zero1"):
        t_cfg = load_config("smoke")
        t_cfg.data.dataset = "synthetic"
        t_cfg.model.name = "mlp"
        t_cfg.train.global_batch_size = 16
        t_cfg.mesh.data = 8
        t_cfg.mesh.partition = target
        template = partitioned_template(t_cfg, mesh)
        restored = ckpt.restore(template, step=1)
        for want, got in zip(jax.tree_util.tree_leaves(
                jax.device_get(state)),
                jax.tree_util.tree_leaves(jax.device_get(restored))):
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got))
    ckpt.close()


# ------------------------------------------------ golden acceptance gate
def test_golden_memory_zero1_twin_gate():
    """THE acceptance artifact: analysis/golden_memory.json must carry
    the mesh8 replicated/zero1 twin where the zero1 optimizer-slot
    argument bytes are ≤ 0.15x the replicated twin's (≈1/8 + slack) with
    the donation credit intact on both — a PR that voids the ZeRO-1
    memory win (or breaks donation under it) fails this gate until the
    goldens are intentionally regenerated."""
    with open(os.path.join(ANALYSIS_DIR, "golden_memory.json")) as f:
        entries = json.load(f)["entries"]
    rep = entries["cifar10_rn8_f32_mesh8"]
    z = entries["cifar10_rn8_f32_mesh8_zero1"]
    assert z["partition"] == "zero1"
    assert z["opt_state_argument_bytes"] <= \
        0.15 * rep["opt_state_argument_bytes"]
    # no alias collapse: donation still credits the sharded slots
    assert rep["alias_bytes"] > 0 and z["alias_bytes"] > 0
    # the cut shows up in XLA's own aggregate too, not just our analytic
    assert z["argument_bytes"] < rep["argument_bytes"]
    # params stay replicated — zero1 must not have quietly sharded them
    assert z["params_argument_bytes"] == rep["params_argument_bytes"]


def test_golden_jaxprs_pin_zero1_entries():
    with open(os.path.join(ANALYSIS_DIR, "golden_jaxprs.json")) as f:
        entries = json.load(f)["entries"]
    for name in ("cifar10_rn8_f32_mesh8_zero1",
                 "imagenet_rn18_bf16_mesh8_zero1",
                 "cifar10_rn8_f32_zero1_mesh1"):
        assert name in entries, f"golden jaxpr missing for {name}"


def test_sweep_space_has_partition_axis():
    from tpu_resnet.tools.sweep import DEFAULT_SPACE

    assert DEFAULT_SPACE["partition"][0] == "replicated"  # base point
    assert "zero1" in DEFAULT_SPACE["partition"]


def test_zero1_rejects_per_replica_bn():
    cfg = load_config("smoke")
    cfg.mesh.partition = "zero1"
    cfg.model.sync_bn = False
    with pytest.raises(ValueError, match="sync_bn"):
        check_step_config(cfg, 8)
    check_step_config(cfg, 1)  # 1-way axis: per-replica BN is moot


# --------------------------------------------------------- slow drills
@pytest.mark.slow  # several in-process train() runs (~60s)
def test_zero1_train_resume_parity_and_restore_consumers(tmp_path):
    """Partition-parity across a REAL resume boundary, then both
    read-only consumers on the zero1 checkpoint: the replicated
    straight-through run and the zero1 preempt-at-4/resume-to-8 run must
    log loss streams equal within 1e-6 at the same steps, and the
    evaluator-template restore and the serve CheckpointBackend must
    produce argmax-identical predictions from the zero1 checkpoint."""
    from tpu_resnet.serve.backend import CheckpointBackend
    from tpu_resnet.serve.infer import make_serve_infer
    from tpu_resnet.train.checkpoint import (CheckpointManager,
                                             partitioned_template)
    from tpu_resnet.train.loop import train

    def _cfg(partition, train_dir):
        cfg = load_config("smoke")
        cfg.data.dataset = "synthetic"
        cfg.data.device_resident = "off"
        cfg.data.transfer_stage = 1
        cfg.model.name = "mlp"
        cfg.train.global_batch_size = 16
        cfg.train.train_steps = 8
        cfg.train.log_every = 2
        cfg.train.summary_every = 2
        cfg.train.checkpoint_every = 4
        cfg.train.image_summary_every = 0
        cfg.train.steps_per_call = 1
        cfg.train.telemetry_port = -1
        cfg.mesh.data = 8
        cfg.mesh.partition = partition
        cfg.train.train_dir = str(train_dir)
        return cfg

    def _losses(train_dir):
        out = {}
        with open(os.path.join(str(train_dir), "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if "loss" in rec:
                    out[rec["step"]] = rec["loss"]
        return out

    rep_cfg = _cfg("replicated", tmp_path / "rep")
    train(rep_cfg)
    z_cfg = _cfg("zero1", tmp_path / "zero1")
    train(z_cfg, max_steps=4)   # stop at the checkpoint boundary
    train(z_cfg)                # resume 4 -> 8 from the zero1 checkpoint
    l_rep, l_z = _losses(tmp_path / "rep"), _losses(tmp_path / "zero1")
    assert set(l_rep) == set(l_z) == {2, 4, 6, 8}
    for step in sorted(l_rep):
        assert l_rep[step] == pytest.approx(l_z[step], rel=1e-6,
                                            abs=1e-6), step

    # Both restore consumers on the zero1 checkpoint.
    mesh = parallel.create_mesh(z_cfg.mesh,
                                devices=jax.devices()[:8])
    template = partitioned_template(z_cfg, mesh)
    ckpt = CheckpointManager(z_cfg.train.train_dir)
    state = ckpt.restore(template)
    ckpt.close()
    rng = np.random.default_rng(7)
    images = rng.integers(0, 255, (4, 32, 32, 3)).astype(np.uint8)
    infer = make_serve_infer(z_cfg)
    eval_logits = np.asarray(infer({"params": state.params,
                                    "batch_stats": state.batch_stats},
                                   jnp.asarray(images)))
    backend = CheckpointBackend(z_cfg, mesh=mesh)
    serve_logits = backend.infer(images)
    backend.close()
    np.testing.assert_array_equal(eval_logits.argmax(-1),
                                  serve_logits.argmax(-1))
    assert backend.model_step == 8
