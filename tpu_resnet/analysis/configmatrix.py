"""Config-matrix abstract verifier — engine 2 of `tpu-resnet check`.

For the cross-product of supported run configurations (models × datasets
× mesh shapes × dtypes × fused/remat × data engine) this module traces
the REAL train and eval steps — the same ``make_train_step`` /
``make_eval_step`` / ``per_replica_shard_map`` objects the loop compiles
— on an abstract mesh via ``jax.make_jaxpr``/``jax.eval_shape``. No
hardware, no FLOPs, no buffers: every check runs on a laptop CPU in
seconds per config, which is what makes it a merge gate instead of a
cluster job (config-space correctness is what breaks first at scale —
MLPerf TPU-pod experience, arXiv:1909.09756; pjit LM training,
arXiv:2204.06514).

Checks per combination:

- **dtype discipline** — no float64/complex/int64 anywhere in the traced
  program (a silent x64 leak doubles memory and halves MXU throughput),
  no float16 (this codebase is bf16-or-f32 by design), metrics all
  float32.
- **stable donated-buffer layout** — the train step must map state in ->
  state out with an IDENTICAL pytree layout (paths, shapes, dtypes);
  donation of every state leaf is verified against the lowered program's
  ``args_info`` on a concrete mesh when enough local devices exist.
- **sharding contract** — state replicated, batch split over the mesh's
  ``data`` axis, exactly as ``shard_step`` declares.
- **golden jaxpr hashes** — the canonicalized jaxpr text of each config
  hashes to a value checked into ``analysis/golden_jaxprs.json``. A PR
  that silently changes any compiled program (the PR-1 "wrong cached
  executable" incident class) fails review until the golden is
  regenerated intentionally (``python -m tpu_resnet check
  --update-golden``; see docs/CHECKS.md).
- **unsupported combinations raise** — the guard contracts (fused +
  sync-BN multi-chip, fused + Wide-ResNet widths, fused + bn_axis_name
  at the constructor) are exercised as must-raise entries, so the
  fail-loud guards are themselves regression-tested per config.
- **engine invariance** — ``data.engine`` (thread vs process) must not
  change the compiled program: process-engine entries assert
  hash-equality with their thread twins.

Golden hashes are defined over the CPU abstract trace (the tier-1/CI
environment). On a non-CPU default backend the hash comparison is
skipped with a warning — Pallas kernel call sites legitimately embed
backend-dependent parameters — while every structural check still runs.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from tpu_resnet.analysis.findings import Finding

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden_jaxprs.json")
GOLDEN_FORMAT = 1

# bf16 is spelled 'bf16[' — the lookbehind keeps it from matching 'f16['.
_FORBIDDEN_DTYPES = (
    ("float64", re.compile(r"(?<![a-z0-9_])f64\[")),
    ("float16", re.compile(r"(?<![a-z0-9_])f16\[")),
    ("int64", re.compile(r"(?<![a-z0-9_])i64\[")),
    ("uint64", re.compile(r"(?<![a-z0-9_])u64\[")),
    ("complex64", re.compile(r"(?<![a-z0-9_])c64\[")),
    ("complex128", re.compile(r"(?<![a-z0-9_])c128\[")),
)

_ADDR = re.compile(r"0x[0-9a-f]+")
_FROZENSET = re.compile(r"frozenset\(\{([^{}]*)\}\)")


def canonicalize(jaxpr_text: str) -> str:
    """Jaxpr text with process-varying tokens normalized, so the sha256
    is stable across processes and machines: object addresses in embedded
    function reprs, and the element order of printed frozensets
    (shard_map's ``manual_axes=frozenset({'data', 'model'})`` prints in
    string-hash order, which PYTHONHASHSEED reshuffles per process)."""
    text = _ADDR.sub("0xX", jaxpr_text)
    return _FROZENSET.sub(
        lambda m: "frozenset({" + ", ".join(sorted(
            part.strip() for part in m.group(1).split(","))) + "})", text)


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


@dataclasses.dataclass
class MatrixEntry:
    """One point of the supported-config cross-product."""

    name: str
    dataset: str = "cifar10"
    model: str = "resnet"
    size: int = 8
    width: int = 1
    dtype: str = "float32"
    fused: bool = False
    remat: bool = False
    epilogue: str = "off"          # model.fused_epilogue (off | on | auto)
    sync_bn: bool = True
    s2d: bool = True               # model.stem_space_to_depth
    data_axis: int = 1
    model_axis: int = 1
    engine: str = "thread"
    batch: int = 16
    # mesh.partition: replicated | zero1 (parallel/partition.py). zero1
    # rows pin the cross-replica weight-update structure — the sharding
    # constraints the SPMD partitioner turns into reduce-scatter +
    # all-gather are part of the traced program, so they golden-hash
    # like any other op.
    partition: str = "replicated"
    classes: int = 0               # synthetic only; 0 = dataset default
    # Must-raise entries: regex the ValueError message must match.
    expect_error: Optional[str] = None
    # "config" entries build through RunConfig/build_model; "ctor-bn-axis"
    # calls the public constructor directly with bn_axis_name+fused (the
    # ADVICE r4 bypass path); "staged-chunk" traces the fused multi-step
    # chunk program over a staged superbatch (device_data.make_chunk_fn
    # — the program the double-buffered H2D path dispatches) instead of
    # the single step; "serve" traces the bucket inference program
    # (serve/infer.make_serve_infer — what the CheckpointBackend warms
    # per bucket), with ``batch`` as the bucket size.
    builder: str = "config"
    # serve builder only: serve.quantize (off | int8). int8 rows trace
    # the quantized program over the int8 argument tree of ops/quant.py
    # and spell under the registry's `_q8` key family.
    quantize: str = "off"
    # staged-chunk only: steps fused per dispatch / superbatch stage rows.
    chunk_steps: int = 4
    stage_rows: int = 8
    # Assert hash-equality with another entry (e.g. engine must not
    # change the compiled program).
    same_program_as: Optional[str] = None
    # Run the concrete-mesh lowering check (donation + sharding) on this
    # entry when the host has enough local devices.
    check_lowering: bool = False

    def to_config(self):
        from tpu_resnet.config import RunConfig

        cfg = RunConfig()
        cfg.data.dataset = self.dataset
        cfg.data.engine = self.engine
        if self.classes:
            cfg.data.synthetic_classes = self.classes
        cfg.model.name = self.model
        cfg.model.resnet_size = self.size
        cfg.model.width_multiplier = self.width
        cfg.model.compute_dtype = self.dtype
        cfg.model.fused_blocks = self.fused
        cfg.model.remat = self.remat
        cfg.model.fused_epilogue = self.epilogue
        cfg.model.sync_bn = self.sync_bn
        cfg.model.stem_space_to_depth = self.s2d
        cfg.mesh.data = self.data_axis
        cfg.mesh.model = self.model_axis
        cfg.mesh.partition = self.partition
        cfg.train.global_batch_size = self.batch
        cfg.serve.quantize = self.quantize
        return cfg


def _e(name, **kw) -> MatrixEntry:
    return MatrixEntry(name=name, **kw)


# The supported-config matrix. Kept explicit (not a programmatic product)
# so every entry is a deliberate, named, golden-hashed contract; adding a
# config feature means adding its row(s) here.
MATRIX: Tuple[MatrixEntry, ...] = (
    # --- CIFAR basic-block nets: dtypes × fused × remat ---------------
    _e("cifar10_rn8_f32"),
    _e("cifar10_rn8_bf16", dtype="bfloat16"),
    _e("cifar10_rn8_f32_fused", fused=True),
    _e("cifar10_rn8_bf16_fused", dtype="bfloat16", fused=True),
    _e("cifar10_rn8_f32_remat", remat=True),
    _e("cifar10_rn8_f32_fused_remat", fused=True, remat=True),
    # --- mesh shapes: sync-BN jit vs per-replica shard_map ------------
    _e("cifar10_rn8_f32_mesh8", data_axis=8, check_lowering=True),
    _e("cifar10_rn8_f32_mesh8_perreplica", data_axis=8, sync_bn=False,
       check_lowering=True),
    _e("cifar10_rn8_f32_mesh8_perreplica_fused", data_axis=8,
       sync_bn=False, fused=True),
    _e("cifar10_rn8_f32_mesh4x2", data_axis=4, model_axis=2),
    # 2-D ("batch","model") pod shape with cross-replica optimizer
    # sharding — ROADMAP item 1 pre-work: the pod-shaped program (zero1
    # reduce-scatter/all-gather over the 4-way data axis of a 4x2 mesh)
    # is golden-pinned (jaxpr + memory budget) and donation-verified on
    # the concrete 8-device mesh, so pod correctness is check-reviewable
    # before any pod exists.
    _e("cifar10_rn8_f32_mesh4x2_zero1", data_axis=4, model_axis=2,
       partition="zero1", check_lowering=True),
    _e("imagenet_rn18_bf16_mesh4x2", dataset="imagenet", size=18,
       dtype="bfloat16", data_axis=4, model_axis=2),
    # --- depth / width ------------------------------------------------
    _e("cifar10_rn20_bf16", size=20, dtype="bfloat16"),
    _e("cifar10_rn50_bf16", size=50, dtype="bfloat16"),
    # Non-headline dimension arms ride on shallow nets: tracing cost is
    # depth-proportional and the dimension under test (mesh/dtype/stem)
    # is depth-independent; the deep headline programs are pinned by the
    # rn50 rows above/below.
    _e("cifar10_rn20_bf16_mesh8", size=20, dtype="bfloat16", data_axis=8),
    _e("cifar100_rn8_f32", dataset="cifar100"),
    _e("cifar100_wrn28_10_bf16", dataset="cifar100", size=28, width=10,
       dtype="bfloat16"),
    # --- synthetic (smoke/drill configs) ------------------------------
    _e("synthetic_rn8_f32", dataset="synthetic"),
    _e("synthetic100_rn8_f32", dataset="synthetic", classes=100),
    _e("synthetic_mlp_f32", dataset="synthetic", model="mlp"),
    # --- ImageNet -----------------------------------------------------
    _e("imagenet_rn18_bf16", dataset="imagenet", size=18,
       dtype="bfloat16"),
    _e("imagenet_rn18_bf16_remat", dataset="imagenet", size=18,
       dtype="bfloat16", remat=True),
    _e("imagenet_rn18_bf16_process", dataset="imagenet", size=18,
       dtype="bfloat16", engine="process",
       same_program_as="imagenet_rn18_bf16"),
    _e("imagenet_rn18_f32", dataset="imagenet", size=18),
    _e("imagenet_rn18_bf16_mesh8", dataset="imagenet", size=18,
       dtype="bfloat16", data_axis=8),
    _e("imagenet_rn18_bf16_plain_stem", dataset="imagenet", size=18,
       dtype="bfloat16", s2d=False),
    _e("imagenet_rn50_bf16", dataset="imagenet", size=50,
       dtype="bfloat16"),
    # --- fused Pallas epilogues (ops/epilogue.py, MFU campaign) -------
    # "on" pins the kernel-everywhere program (what a forced run and the
    # CPU parity tests compile); the per-replica row pins the supported
    # multi-chip dispatch. "auto" is probe-dependent by design and so
    # cannot carry a golden — its safety net is that every unprobed
    # shape lowers to the same XLA math as these rows' reference arm.
    _e("cifar10_rn8_f32_epilogue", epilogue="on"),
    _e("imagenet_rn18_bf16_epilogue", dataset="imagenet", size=18,
       dtype="bfloat16", epilogue="on"),
    _e("cifar10_rn8_f32_mesh8_perreplica_epilogue", data_axis=8,
       sync_bn=False, epilogue="on"),
    # --- zero1 cross-replica optimizer sharding (parallel/partition.py,
    # parallel/zero.py, arXiv:2004.13336): the sharded weight update's
    # constraint structure is pinned per config, the mesh1 identity twin
    # asserts zero1 on a 1-way data axis compiles the EXACT replicated
    # program, and the lowering check proves donation survives the
    # per-shard optimizer-slot arguments.
    _e("cifar10_rn8_f32_mesh8_zero1", data_axis=8, partition="zero1",
       check_lowering=True),
    _e("imagenet_rn18_bf16_mesh8_zero1", dataset="imagenet", size=18,
       dtype="bfloat16", data_axis=8, partition="zero1"),
    _e("cifar10_rn8_f32_zero1_mesh1", partition="zero1",
       same_program_as="cifar10_rn8_f32"),
    # --- staged/double-buffered chunk program (device_data.make_chunk_fn)
    # The fused multi-step dispatch both streaming input edges execute —
    # including the new DoubleBufferedH2D path, whose contract is that
    # it changes TRANSFER scheduling only, never the compiled program.
    _e("cifar10_rn8_f32_staged_chunk", builder="staged-chunk"),
    _e("imagenet_rn18_bf16_staged_chunk", dataset="imagenet", size=18,
       dtype="bfloat16", builder="staged-chunk"),
    # --- int8 post-training-quantized serve arm (ops/quant.py,
    # serve/infer.py; docs/SERVING.md "Quantized arm"): each quantized
    # bucket program is golden-pinned NEXT TO its f32 twin — same model,
    # same bucket, weights as int8 arguments + folded dequant — and the
    # memory ledger's twin gate (analysis/memorybudget.py,
    # tests/test_quant.py) holds the quantized row's weight-argument
    # bytes to <= 0.30x of the twin's, the ZeRO-1 0.125x pattern.
    _e("serve_cifar10_rn8_f32_b8", builder="serve", batch=8),
    _e("serve_cifar10_rn8_f32_b8_q8", builder="serve", batch=8,
       quantize="int8"),
    _e("serve_synthetic_mlp_f32_b4", builder="serve", dataset="synthetic",
       model="mlp", batch=4),
    _e("serve_synthetic_mlp_f32_b4_q8", builder="serve",
       dataset="synthetic", model="mlp", batch=4, quantize="int8"),
    # --- guard contracts: unsupported combinations must raise ---------
    _e("raise_fused_wrn", dataset="cifar100", size=28, width=10,
       fused=True,
       expect_error="only measured/tiled for.*width_multiplier"),
    _e("raise_fused_syncbn_mesh8", fused=True, data_axis=8,
       expect_error="multi-chip data axis requires.*sync_bn"),
    _e("raise_epilogue_syncbn_mesh8", epilogue="on", data_axis=8,
       expect_error="fused_epilogue on a multi-chip data axis "
                    "requires.*sync_bn"),
    _e("raise_ctor_fused_bn_axis", builder="ctor-bn-axis",
       expect_error="does not implement sync-BN"),
    _e("raise_zero1_perreplica_mesh8", data_axis=8, sync_bn=False,
       partition="zero1",
       expect_error="zero1 on a multi-chip data axis requires.*sync_bn"),
    _e("raise_bad_partition_mode", partition="zero2",
       expect_error="mesh.partition must be one of"),
    # int8 serving of a per-replica-BN multi-replica config: each
    # replica's folded BN affine differs, so one calibration cannot be
    # parity-gated — must refuse (ops/quant.py check_quantize_config).
    _e("raise_quant_perreplica", builder="serve", quantize="int8",
       data_axis=8, sync_bn=False,
       expect_error="serve.quantize=int8 requires model.sync_bn"),
    # Unknown quant mode strings fail loudly, like fused_epilogue typos.
    _e("raise_bad_quantize_mode", builder="serve", quantize="int4",
       expect_error="serve.quantize must be one of"),
)


def _abstract_mesh(data: int, model: int):
    from jax.sharding import AbstractMesh

    return AbstractMesh((data, model), ("data", "model"))


def _state_layout(state_sds) -> List[Tuple[str, str, Tuple[int, ...]]]:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(state_sds)[0]
    return [(jax.tree_util.keystr(path), str(leaf.dtype),
             tuple(leaf.shape))
            for path, leaf in leaves]


def _abstract_programs(entry: MatrixEntry):
    """Trace the real train/eval steps for one entry on an abstract mesh.

    Returns (train_text, eval_text, state_layout, out_shapes) where the
    texts are canonicalized jaxpr strings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_resnet.data import augment as aug_lib
    from tpu_resnet.models import build_model, cifar_resnet_v2
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_state
    from tpu_resnet.train.step import (check_step_config, make_eval_step,
                                       make_train_step,
                                       per_replica_shard_map)

    if entry.builder == "ctor-bn-axis":
        # The ADVICE r4 bypass: calling the public constructor directly
        # must hit the same guard as build_model.
        cifar_resnet_v2(entry.size, 10, fused_blocks=True,
                        bn_axis_name="data")
        raise AssertionError("constructor guard did not fire")

    if entry.builder == "serve":
        return _abstract_serve_program(entry)

    cfg = entry.to_config()
    check_step_config(cfg, entry.data_axis)  # the loop's own gate
    model = build_model(cfg)                 # constructor guards run here
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    size = cfg.data.resolved_image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)

    def init_fn(rng):
        return init_state(model, cfg.optim, schedule, rng, sample)

    state_sds = jax.eval_shape(init_fn, jax.random.PRNGKey(0))

    augment_fn, eval_pre = aug_lib.get_augment_fns(cfg.data.dataset)
    per_replica = (not cfg.model.sync_bn) and entry.data_axis > 1
    # The partitioner traces over an AbstractMesh — the sharding
    # constraints it injects (the zero1 weight update) carry only axis
    # names/sizes into the jaxpr text, so the golden hash stays
    # machine-independent like every other entry. Replicated entries get
    # a non-sharding partitioner: make_update_fn then returns the plain
    # optax chain, byte-identical to the pre-partitioner trace.
    from tpu_resnet.parallel.partition import StatePartitioner

    partitioner = StatePartitioner(
        _abstract_mesh(entry.data_axis, entry.model_axis), entry.partition)
    step = make_train_step(model, cfg.optim, schedule,
                           cfg.data.num_classes, augment_fn,
                           base_rng=jax.random.PRNGKey(0), mesh=None,
                           grad_axis="data" if per_replica else None,
                           partitioner=partitioner)
    if per_replica:
        step = per_replica_shard_map(
            step, _abstract_mesh(entry.data_axis, entry.model_axis),
            in_specs=(P(), P("data"), P("data")))

    if partitioner.is_sharded:
        # The loop's startup gate, applied to the abstract state tree:
        # an unshardable (model × mesh × partition) combination must be
        # a per-leaf ValueError here too, not a silently replicated slot.
        partitioner.validate(state_sds)

    imgs = jax.ShapeDtypeStruct((entry.batch, size, size, 3), jnp.uint8)
    labels = jax.ShapeDtypeStruct((entry.batch,), jnp.int32)
    if entry.builder == "staged-chunk":
        # The fused multi-step chunk over a staged superbatch — exactly
        # the program compile_staged_stream_steps jits for the streaming
        # (and double-buffered H2D) input edge.
        from tpu_resnet.data.device_data import make_chunk_fn

        chunk = make_chunk_fn(step, entry.chunk_steps)
        gi = jax.ShapeDtypeStruct(
            (entry.stage_rows, entry.batch, size, size, 3), jnp.uint8)
        gl = jax.ShapeDtypeStruct((entry.stage_rows, entry.batch),
                                  jnp.int32)
        off = jax.ShapeDtypeStruct((), jnp.int32)
        train_text = canonicalize(str(jax.make_jaxpr(chunk)(
            state_sds, gi, gl, off)))
        out_shapes = jax.eval_shape(chunk, state_sds, gi, gl, off)
    else:
        train_text = canonicalize(str(jax.make_jaxpr(step)(
            state_sds, imgs, labels)))
        out_shapes = jax.eval_shape(step, state_sds, imgs, labels)

    eval_step = make_eval_step(model, cfg.data.num_classes, eval_pre)
    eval_text = canonicalize(str(jax.make_jaxpr(eval_step)(
        state_sds, imgs, labels)))
    return train_text, eval_text, _state_layout(state_sds), \
        (state_sds, out_shapes)


def _abstract_serve_program(entry: MatrixEntry):
    """Trace the bucket inference program for a serve row — the exact
    ``make_serve_infer`` jit the CheckpointBackend warms per bucket,
    over the exact argument avals it wraps (the int8 quantized tree for
    ``quantize="int8"`` rows — ops/quant.py). Returned in the train-row
    shape (variables stand in for state; empty metrics) so the
    structural checks — forbidden dtypes, layout identity — apply
    unchanged; int8 is deliberately NOT a forbidden dtype."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet.models import build_model
    from tpu_resnet.ops import quant as quant_lib
    from tpu_resnet.serve.infer import make_serve_infer

    cfg = entry.to_config()
    # The serve arm's own config gate — must-raise quant rows fire here.
    quant_lib.check_quantize_config(cfg, entry.data_axis)
    model = build_model(cfg)  # constructor guards run here
    size = cfg.data.resolved_image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)

    def init_vars(rng):
        v = model.init(rng, sample, train=False)
        return {"params": v["params"],
                "batch_stats": v.get("batch_stats", {})}

    var_sds = jax.eval_shape(init_vars, jax.random.PRNGKey(0))
    if cfg.serve.quantize == "int8":
        var_sds = jax.eval_shape(quant_lib.quantize_variables, var_sds)
    infer = make_serve_infer(cfg)
    imgs = jax.ShapeDtypeStruct((entry.batch, size, size, 3), jnp.uint8)
    infer_text = canonicalize(str(jax.make_jaxpr(infer)(var_sds, imgs)))
    # No eval twin and no metrics on the serve path: the empty eval text
    # hashes to a constant and the (vars, (vars, {})) shape tuple makes
    # the layout-identity check trivially true.
    return infer_text, "", _state_layout(var_sds), \
        (var_sds, (var_sds, {}))


def _structural_findings(entry: MatrixEntry, train_text: str,
                         eval_text: str, shapes) -> List[Finding]:
    path = f"<config-matrix>/{entry.name}"
    findings = []
    for which, text in (("train", train_text), ("eval", eval_text)):
        for dtype_name, pat in _FORBIDDEN_DTYPES:
            if pat.search(text):
                findings.append(Finding(
                    "config-matrix", path, 0,
                    f"{dtype_name} appears in the {which} step program — "
                    f"dtype discipline is f32/bf16/i32/u8 only (an x64 "
                    f"leak silently doubles memory and halves MXU "
                    f"throughput)"))
    state_sds, out = shapes
    new_state, metrics = out
    in_layout = _state_layout(state_sds)
    out_layout = _state_layout(new_state)
    if in_layout != out_layout:
        diff = [f"{a} != {b}" for a, b in zip(in_layout, out_layout)
                if a != b][:3]
        findings.append(Finding(
            "config-matrix", path, 0,
            f"train step breaks the donated-buffer layout: state-in and "
            f"state-out trees differ ({len(in_layout)} vs "
            f"{len(out_layout)} leaves; first diffs: {diff}) — donation "
            f"requires identical layout or every step copies"))
    for k, v in metrics.items():
        if str(v.dtype) != "float32":
            findings.append(Finding(
                "config-matrix", path, 0,
                f"metric '{k}' of the train step is {v.dtype}, expected "
                f"float32 (dtype promotion leak)"))
    return findings


def verify_lowering(entry: MatrixEntry) -> List[Finding]:
    """Concrete-mesh contract check: lower (no compile, no execute) the
    exact ``shard_step`` jit the loop uses and assert every state leaf is
    donated and the batch is split over 'data'. Needs >= mesh-size local
    devices; the caller skips otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from tpu_resnet.data import augment as aug_lib
    from tpu_resnet.models import build_model
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_state
    from tpu_resnet.train.step import make_train_step, shard_step

    path = f"<config-matrix>/{entry.name}"
    cfg = entry.to_config()
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    size = cfg.data.resolved_image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)
    state_sds = jax.eval_shape(
        lambda r: init_state(model, cfg.optim, schedule, r, sample),
        jax.random.PRNGKey(0))
    n = entry.data_axis * entry.model_axis
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(
        entry.data_axis, entry.model_axis), ("data", "model"))
    per_replica = (not cfg.model.sync_bn) and entry.data_axis > 1
    augment_fn, _ = aug_lib.get_augment_fns(cfg.data.dataset)
    from tpu_resnet.parallel.partition import StatePartitioner

    partitioner = StatePartitioner(mesh, entry.partition)
    base = make_train_step(model, cfg.optim, schedule,
                           cfg.data.num_classes, augment_fn,
                           base_rng=jax.random.PRNGKey(0), mesh=mesh,
                           grad_axis="data" if per_replica else None,
                           partitioner=partitioner)
    jitted = shard_step(base, mesh, per_replica_bn=per_replica,
                        state_sharding=(partitioner.state_shardings(state_sds)
                                        if partitioner.is_sharded else None))
    imgs = jax.ShapeDtypeStruct((entry.batch, size, size, 3), jnp.uint8)
    labels = jax.ShapeDtypeStruct((entry.batch,), jnp.int32)
    lowered = jitted.lower(state_sds, imgs, labels)
    findings = []
    args_info = lowered.args_info[0] if isinstance(
        lowered.args_info, tuple) else lowered.args_info
    state_info, img_info, label_info = args_info
    not_donated = [
        jax.tree_util.keystr(p) for p, info in
        jax.tree_util.tree_flatten_with_path(state_info)[0]
        if not info.donated]
    if not_donated:
        findings.append(Finding(
            "config-matrix", path, 0,
            f"{len(not_donated)} state leaf/leaves NOT donated in the "
            f"lowered step (e.g. {not_donated[:3]}) — shard_step promises "
            f"donate_argnums=(0,); an undonated state doubles parameter "
            f"HBM"))
    for name, info_tree in (("images", img_info), ("labels", label_info)):
        if any(i.donated for i in jax.tree_util.tree_leaves(info_tree)):
            findings.append(Finding(
                "config-matrix", path, 0,
                f"{name} buffer is donated — only the state may be"))
    text = lowered.as_text()
    if entry.data_axis > 1 and "sharding" not in text:
        findings.append(Finding(
            "config-matrix", path, 0,
            "lowered program carries no sharding annotations on a "
            f"{entry.data_axis}-way mesh — batch is not split over "
            "'data' (the SPMD contract of shard_step)"))
    return findings


# ----------------------------------------------------------------- golden
def load_golden(path: str = GOLDEN_PATH) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        return {"format": GOLDEN_FORMAT, "entries": {}}


def save_golden(golden: dict, path: str = GOLDEN_PATH) -> None:
    golden["entries"] = dict(sorted(golden["entries"].items()))
    with open(path, "w") as fh:
        json.dump(golden, fh, indent=1)
        fh.write("\n")


def verify_matrix(entries: Optional[Tuple[MatrixEntry, ...]] = None,
                  update_golden: bool = False,
                  golden_path: str = GOLDEN_PATH,
                  progress=None) -> Tuple[List[Finding], dict]:
    """Run the matrix. Returns (findings, stats). With ``update_golden``
    the golden file is rewritten from the current programs instead of
    compared (stats['updated'] lists the entries)."""
    import jax

    entries = MATRIX if entries is None else entries
    golden = load_golden(golden_path)
    on_cpu = jax.default_backend() == "cpu"
    findings: List[Finding] = []
    hashes: Dict[str, Tuple[str, str]] = {}
    stats = {"traced": 0, "must_raise": 0, "hash_checked": 0,
             "lowered": 0, "updated": [], "skipped_lowering": 0,
             "registry_keys": 0}

    for entry in entries:
        if progress:
            progress(entry.name)
        path = f"<config-matrix>/{entry.name}"
        if entry.expect_error is not None:
            stats["must_raise"] += 1
            try:
                _abstract_programs(entry)
            except ValueError as e:
                if not re.search(entry.expect_error, str(e)):
                    findings.append(Finding(
                        "config-matrix", path, 0,
                        f"unsupported combination raised, but with the "
                        f"wrong message: {e!r} !~ /{entry.expect_error}/"))
            except AssertionError as e:
                findings.append(Finding(
                    "config-matrix", path, 0,
                    f"guard did not fire: {e}"))
            except Exception as e:  # wrong exception TYPE is a finding,
                findings.append(Finding(  # not a crashed check run
                    "config-matrix", path, 0,
                    f"unsupported combination raised "
                    f"{type(e).__name__} ({e}) instead of a ValueError "
                    f"matching /{entry.expect_error}/ — the fail-loud "
                    f"guard drifted (users now see an obscure error)"))
            else:
                findings.append(Finding(
                    "config-matrix", path, 0,
                    f"unsupported combination was accepted — expected "
                    f"ValueError matching /{entry.expect_error}/ (a "
                    f"fail-loud guard was removed or weakened)"))
            continue

        try:
            train_text, eval_text, layout, shapes = \
                _abstract_programs(entry)
        except Exception as e:
            # One broken entry must not cost the report for the rest.
            findings.append(Finding(
                "config-matrix", path, 0,
                f"supported combination FAILED to trace: "
                f"{type(e).__name__}: {e}"))
            continue
        stats["traced"] += 1
        findings.extend(_structural_findings(entry, train_text,
                                             eval_text, shapes))
        th, eh = _sha(train_text), _sha(eval_text)
        hashes[entry.name] = (th, eh)
        layout_hash = _sha(json.dumps(layout))
        record = {"train": th, "eval": eh,
                  "state_leaves": len(layout),
                  "state_layout": layout_hash}
        if update_golden:
            golden["entries"][entry.name] = record
            stats["updated"].append(entry.name)
            continue
        want = golden["entries"].get(entry.name)
        if not on_cpu:
            findings.append(Finding(
                "config-matrix", path, 0,
                f"golden hash compare skipped on backend "
                f"'{jax.default_backend()}' (goldens are defined over "
                f"the CPU abstract trace)", "warning"))
        elif want is None:
            findings.append(Finding(
                "golden-jaxpr-drift", path, 0,
                "no golden recorded for this entry — run `python -m "
                "tpu_resnet check --update-golden` and commit the "
                "regenerated analysis/golden_jaxprs.json"))
        else:
            stats["hash_checked"] += 1
            for which, got, exp in (("train", th, want.get("train")),
                                    ("eval", eh, want.get("eval"))):
                if got != exp:
                    findings.append(Finding(
                        "golden-jaxpr-drift", path, 0,
                        f"the compiled {which} program for this config "
                        f"CHANGED (jaxpr {got[:12]}… != golden "
                        f"{exp[:12]}…, golden jax {golden.get('jax')} vs "
                        f"current {jax.__version__}). If intended, "
                        f"regenerate via `python -m tpu_resnet check "
                        f"--update-golden` and say why in the PR; if "
                        f"not, this is the silent-program-change "
                        f"incident class (PR 1) caught at review time"))
            if want.get("state_layout") != layout_hash:
                findings.append(Finding(
                    "golden-jaxpr-drift", path, 0,
                    f"donated-buffer/state layout changed "
                    f"({want.get('state_leaves')} -> {len(layout)} "
                    f"leaves) — checkpoints and donation layout are "
                    f"affected; regenerate goldens if intended"))

    # Registry coverage (tpu_resnet/programs): every traced entry must
    # resolve through the ONE key spelling (programs.spell_entry — the
    # same function the FLOPs registry, memory ledger and executable
    # cache key by), and one key must name exactly one program: two
    # entries that spell the same key with different traced programs
    # mean the spelling under-specifies a config dimension — the
    # executable cache would hand one config the other's program (the
    # PR 1 wrong-executable class, caught here at review time). Two
    # keys naming one program is fine (identity twins).
    from tpu_resnet.programs import spell_entry

    key_owners: Dict[str, Tuple[str, Tuple[str, str]]] = {}
    for entry in entries:
        if entry.name not in hashes:
            continue  # must-raise/failed entries never built a program
        path = f"<config-matrix>/{entry.name}"
        try:
            key = spell_entry(entry)
        except Exception as e:  # noqa: BLE001 - a spell crash is a finding
            findings.append(Finding(
                "registry-coverage", path, 0,
                f"entry does not resolve through the program registry's "
                f"key spelling (programs.spell_entry raised "
                f"{type(e).__name__}: {e}) — the check engines and the "
                f"runtime can no longer agree on what this program is "
                f"called"))
            continue
        stats["registry_keys"] = stats.get("registry_keys", 0) + 1
        prior = key_owners.get(key)
        if prior is None:
            key_owners[key] = (entry.name, hashes[entry.name])
        elif prior[1] != hashes[entry.name]:
            findings.append(Finding(
                "registry-coverage", path, 0,
                f"program key collision: '{entry.name}' and "
                f"'{prior[0]}' both spell {key} but trace DIFFERENT "
                f"programs — the registry key under-specifies a config "
                f"dimension; extend programs.spell so the executable "
                f"cache and the flops/memory ledgers can never hand one "
                f"config the other's program"))

    # engine (and any other declared-invariant) twins
    for entry in entries:
        if entry.same_program_as and entry.name in hashes:
            twin = hashes.get(entry.same_program_as)
            if twin is None:
                findings.append(Finding(
                    "config-matrix", f"<config-matrix>/{entry.name}", 0,
                    f"declared-identical twin '{entry.same_program_as}' "
                    f"was not traced in this run (renamed/removed?) — "
                    f"the engine-invariance contract is silently "
                    f"unverified; fix the same_program_as reference"))
            elif twin != hashes[entry.name]:
                findings.append(Finding(
                    "config-matrix", f"<config-matrix>/{entry.name}", 0,
                    f"program differs from declared-identical twin "
                    f"'{entry.same_program_as}' — this dimension (e.g. "
                    f"data.engine) must not change the compiled step"))

    # concrete-mesh donation/sharding contract where devices allow
    for entry in entries:
        if entry.expect_error is None and entry.check_lowering:
            need = entry.data_axis * entry.model_axis
            if len(jax.devices()) >= need:
                findings.extend(verify_lowering(entry))
                stats["lowered"] += 1
            else:
                stats["skipped_lowering"] += 1

    if update_golden:
        # Prune renamed/removed entries: the golden mirrors MATRIX exactly.
        live = {e.name for e in entries if e.expect_error is None}
        golden["entries"] = {k: v for k, v in golden["entries"].items()
                             if k in live}
        golden["format"] = GOLDEN_FORMAT
        golden["jax"] = jax.__version__
        try:
            import flax
            golden["flax"] = flax.__version__
        except Exception:
            pass
        save_golden(golden, golden_path)
    return findings, stats
