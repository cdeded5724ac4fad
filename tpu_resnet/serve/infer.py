"""The serve hot path's compiled inference function.

This module is **jit-scope** for the static-analysis suite (it is listed
in ``analysis/jaxlint.py`` ``JIT_SCOPE_FILES``): every function here is
jit-reachable, so host I/O, clocks, host RNG and per-call device syncs
are lint errors. Host-side serving code (queueing, timing, HTTP) lives
in ``batcher.py``/``server.py`` — keep it out of this file.
"""

from __future__ import annotations

from typing import Callable

import jax

from tpu_resnet.config import RunConfig
from tpu_resnet.data import augment as aug_lib
from tpu_resnet.models import build_model, require_image_model
from tpu_resnet.ops import quant


def make_serve_infer(cfg: RunConfig) -> Callable:
    """``infer(variables, images_uint8[B,H,W,3]) -> logits [B,classes]``.

    Same computation as the frozen export (``export.make_inference_fn``):
    eval preprocessing baked into the compiled program. The one deliberate
    difference: ``variables`` are *arguments*, not baked-in constants, so
    a checkpoint hot-reload swaps weights by passing a new pytree of the
    same structure/shapes — the cached executable is reused, zero
    recompiles mid-traffic.

    ``serve.quantize="int8"`` compiles the QUANTIZED program instead:
    ``variables`` is the int8 argument tree of ``quant.quantize_variables``
    (int8 kernels + per-channel scales + calibrated activation scale —
    the ~0.25x weight-argument footprint the golden memory twin gates),
    the input is fake-quantized with the calibrated per-tensor scale,
    and the kernels dequantize inside the program (the multiply that
    folds into the scale_bias_relu epilogue; ops/quant.py). A different
    argument tree means a different program signature — the registry
    spells it under the ``_q8`` key family (programs/registry.py)."""
    require_image_model(cfg, "serving")
    model = build_model(cfg)
    _, eval_pre = aug_lib.get_augment_fns(cfg.data.dataset)
    quantized = getattr(cfg.serve, "quantize", "off") == "int8"

    def infer(variables, images):
        x = eval_pre(images)
        if quantized:
            x = quant.fake_quant(x, variables[quant.QACT_KEY]["input"])
            variables = quant.dequantize_variables(variables)
        return model.apply(variables, x, train=False)

    return jax.jit(infer)
