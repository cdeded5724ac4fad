"""Model export — the freeze_graph pipeline rebuilt for XLA.

The reference freezes a checkpoint into a GraphDef ``.pb`` with named
placeholder inputs and fetches (reference resnet_cifar_frozen_model.py:2-23:
rebuild eval graph on placeholders → export_meta_graph → freeze_graph →
load_graph + feed_dict), and serves it via feed-dict sessions
(resnet_cifar_predict_from_pd.py:66-105).

TPU-native equivalent: serialize the *compiled inference function* as
StableHLO via ``jax.export`` (weights baked in as constants — the exact
analog of freezing) next to a JSON manifest. The artifact is loadable
without any model code, like a ``.pb``:

    bundle = load_inference(path)
    logits = bundle(images_uint8)   # preprocessing is baked into the graph

Layout of an export directory:
    manifest.json      model/config metadata
    inference.stablehlo  serialized jax.export artifact
"""

from __future__ import annotations

import json
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export

from tpu_resnet.config import RunConfig
from tpu_resnet.data import augment as aug_lib
from tpu_resnet.models import build_model, require_image_model
from tpu_resnet.ops import quant as quant_lib

MANIFEST = "manifest.json"
ARTIFACT = "inference.stablehlo"
WEIGHTS = "weights.npz"  # quantized bundles only: the int8 argument tree


def _flatten_tree(tree) -> dict:
    """Pytree of arrays → flat ``{"a/b/c": np.ndarray}`` (dict keys
    joined by "/"; param names never contain one). The npz-serializable
    form of the quantized argument tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = np.asarray(leaf)
    return out


def _unflatten_tree(flat: dict) -> dict:
    out = {}
    for key, leaf in flat.items():
        parts = key.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = leaf
    return out


def make_inference_fn(cfg: RunConfig, params, batch_stats) -> Callable:
    """Pure fn: uint8 [B,H,W,3] → logits [B,classes]; eval preprocessing
    (standardization / mean subtraction) baked in, like the frozen graph's
    in-graph preprocessing (resnet_cifar_frozen_model.py:81-88)."""
    require_image_model(cfg, "export")
    model = build_model(cfg)
    _, eval_pre = aug_lib.get_augment_fns(cfg.data.dataset)

    def infer(images):
        x = eval_pre(images)
        return model.apply({"params": params, "batch_stats": batch_stats},
                           x, train=False)

    return infer


def save_inference(cfg: RunConfig, params, batch_stats, out_dir: str,
                   batch_size: int = 0, step: int | None = None,
                   calibration: dict | None = None) -> str:
    """Freeze params into a serialized StableHLO artifact.

    ``batch_size=0`` exports with a symbolic (polymorphic) batch dimension;
    a fixed size pins it like the reference's placeholder shape. ``step``
    (when known — ``export_from_checkpoint`` passes the restored step)
    is recorded in the manifest so serving a frozen bundle can still
    report which training step it is (the ``serve_model_step`` gauge).

    ``cfg.serve.quantize="int8"`` exports the QUANTIZED bundle instead:
    the serialized program is the live serve arm's weights-as-ARGUMENTS
    program (serve/infer.py — identical math, same `_q8` family), and
    the int8 argument tree lands beside it as ``weights.npz``. Baking
    the quantized tree in as constants would be a lie: trace-time
    constant folding materializes the dequantized fp32 weights into the
    artifact. As arguments the on-disk payload and the runtime argument
    footprint are genuinely ~0.25x, and ``calibration`` provenance
    (a serve/calibrate.py record; collected on the spot when None) is
    stamped into the manifest — quant mode, calibration digest, and the
    weight-tree bytes the serve backend reports.
    """
    os.makedirs(out_dir, exist_ok=True)
    quantize = getattr(cfg.serve, "quantize", "off")
    quant_lib.check_quantize_config(cfg)
    size = cfg.data.resolved_image_size
    if batch_size:
        arg = jax.ShapeDtypeStruct((batch_size, size, size, 3), jnp.uint8)
    else:
        (b,) = jax_export.symbolic_shape("b")
        arg = jax.ShapeDtypeStruct((b, size, size, 3), jnp.uint8)
    calibration_digest = ""
    if quantize == "int8":
        from tpu_resnet.serve.infer import make_serve_infer

        if calibration is None:
            from tpu_resnet.serve import calibrate

            calibration = calibrate.collect_ranges(cfg)
        calibration_digest = calibration["digest"]
        qvars = quant_lib.quantize_variables(
            {"params": params, "batch_stats": batch_stats},
            act_max=calibration["act_max"]["input"])
        # Round-trip through the flat npz form NOW, so the traced pytree
        # structure is exactly the one load_inference reconstructs.
        qflat = _flatten_tree(qvars)
        variables = _unflatten_tree(qflat)
        for top in ("params", "batch_stats", quant_lib.QSCALES_KEY,
                    quant_lib.QACT_KEY):
            variables.setdefault(top, {})
        var_avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), variables)
        exported = jax_export.export(make_serve_infer(cfg))(var_avals,
                                                            arg)
        np.savez(os.path.join(out_dir, WEIGHTS), **qflat)
    else:
        variables = {"params": params, "batch_stats": batch_stats}
        infer = make_inference_fn(cfg, params, batch_stats)
        exported = jax_export.export(jax.jit(infer))(arg)
    with open(os.path.join(out_dir, ARTIFACT), "wb") as f:
        f.write(exported.serialize())
    with open(os.path.join(out_dir, MANIFEST), "w") as f:
        json.dump({
            "format": "jax.export/stablehlo",
            "model": cfg.model.name,
            "resnet_size": cfg.model.resnet_size,
            "dataset": cfg.data.dataset,
            "num_classes": cfg.data.num_classes,
            "image_size": size,
            "batch_size": batch_size or "dynamic",
            "input": "uint8 NHWC, raw pixels (preprocessing baked in)",
            "output": "float32 logits",
            "step": step if step is not None else -1,
            "quantize": quantize,
            "calibration_digest": calibration_digest,
            "weights": WEIGHTS if quantize == "int8" else "",
            "weight_bytes": quant_lib.tree_argument_bytes(variables),
        }, f, indent=2)
    return out_dir


class InferenceBundle:
    """Loaded frozen model (the load_graph+feed analog,
    resnet_cifar_predict_from_pd.py:66-105). Quantized bundles carry
    their int8 weight tree separately (``weights.npz``) and feed it as
    the program's first argument on every call."""

    def __init__(self, exported, manifest: dict, qvars=None):
        self._exported = exported
        self.manifest = manifest
        self._qvars = qvars

    def __call__(self, images: np.ndarray) -> np.ndarray:
        images = jnp.asarray(images, jnp.uint8)
        if self._qvars is not None:
            return np.asarray(self._exported.call(self._qvars, images))
        return np.asarray(self._exported.call(images))

    def predict(self, images: np.ndarray) -> np.ndarray:
        return np.argmax(self(images), axis=-1)


def load_inference(out_dir: str) -> InferenceBundle:
    with open(os.path.join(out_dir, ARTIFACT), "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(os.path.join(out_dir, MANIFEST)) as f:
        manifest = json.load(f)
    qvars = None
    if manifest.get("quantize", "off") == "int8":
        with np.load(os.path.join(out_dir,
                                  manifest.get("weights") or WEIGHTS)) as z:
            qvars = _unflatten_tree({k: z[k] for k in z.files})
        for top in ("params", "batch_stats", quant_lib.QSCALES_KEY,
                    quant_lib.QACT_KEY):
            qvars.setdefault(top, {})
    return InferenceBundle(exported, manifest, qvars=qvars)


def export_from_checkpoint(cfg: RunConfig, out_dir: str,
                           step: int | None = None,
                           batch_size: int = 0) -> str:
    """checkpoint dir (cfg.train.train_dir) → frozen artifact — the 4-step
    freeze recipe (resnet_cifar_frozen_model.py:2-23) as one call."""
    from tpu_resnet import parallel
    from tpu_resnet.train.checkpoint import (CheckpointManager,
                                             partitioned_template)

    mesh = parallel.create_mesh(cfg.mesh)
    require_image_model(cfg, "export")
    model = build_model(cfg)
    # Abstract template in the run's partition layout (no device
    # allocation; a zero1 run's checkpoint restores into its shards and
    # the replicated params/stats below are untouched by the mode).
    template = partitioned_template(cfg, mesh, model=model)
    ckpt = CheckpointManager(cfg.train.train_dir)
    state = ckpt.restore(template, step=step)
    calibration = None
    if getattr(cfg.serve, "quantize", "off") == "int8":
        # Calibration lives next to the checkpoints (load-or-collect),
        # so a quantized export and a quantized live replica of the same
        # train_dir stamp the SAME digest — the A/B provenance link.
        from tpu_resnet.serve import calibrate

        calibration = calibrate.ensure_calibration(cfg,
                                                   cfg.train.train_dir)
    return save_inference(cfg, jax.device_get(state.params),
                          jax.device_get(state.batch_stats), out_dir,
                          batch_size=batch_size,
                          step=int(jax.device_get(state.step)),
                          calibration=calibration)
