"""Pallas TPU kernels for hot ops. Each op has an interpret-mode path so the
same kernel code runs (slowly) on CPU in tests. The image model's ops
(epilogue, fused blocks, softmax-xent) are guarded in the hot path by the
compile-time A/B probe (ops/autotune.py): such a lowering rides only where
it measured a win over XLA, because there the two stand within a few
percent of each other. Attention (ops/attention.py), the experts'
grouped products (ops/grouped.py) and the sum of the experts' rows into
their tokens (ops/rows_to_tokens.py), all imported by what the token
families share (models/transformer.py) and not re-exported here, are chosen by backend and shape alone: the
paths they replace send every score through HBM several times, multiply
four padded rows for each one filled, and scatter every row of a buffer
one at a time, so no timing could choose otherwise, and a probe would cost
a compile of each side at start-up."""

from tpu_resnet.ops import autotune
from tpu_resnet.ops.epilogue import (
    probe_epilogue,
    probe_model_epilogues,
    scale_bias_relu,
    scale_bias_relu_add,
    scale_bias_relu_add_auto,
    scale_bias_relu_add_reference,
    scale_bias_relu_auto,
    scale_bias_relu_reference,
)
from tpu_resnet.ops.fused_block import (
    block_apply,
    block_train_apply,
    block_fwd,
    block_fwd_reference,
    block_train_fwd,
    block_train_fwd_reference,
)
from tpu_resnet.ops.softmax_xent import (
    ensure_xent_probe,
    is_tpu_backend,
    make_pallas_xent,
    softmax_xent_mean,
    softmax_xent_per_example,
    softmax_xent_reference,
)

__all__ = ["autotune",
           "block_apply", "block_fwd", "block_fwd_reference",
           "block_train_apply",
           "block_train_fwd", "block_train_fwd_reference",
           "ensure_xent_probe", "is_tpu_backend", "make_pallas_xent",
           "probe_epilogue", "probe_model_epilogues",
           "scale_bias_relu", "scale_bias_relu_add",
           "scale_bias_relu_add_auto", "scale_bias_relu_add_reference",
           "scale_bias_relu_auto", "scale_bias_relu_reference",
           "softmax_xent_mean", "softmax_xent_per_example",
           "softmax_xent_reference"]
