"""The model family ``smallthinker``: decoders that route each token before
attention, attend over a window with rotary or globally with no position,
and sum ReGLU experts, trained on packed token sequences by AdamW, as one
chip of an expert-parallel group holds them. What the harness asks of a
family (benchmarks/lib/manifest.py), said for this one; the plain
reference is ``benchmarks/reference/smallthinker.py``.

The state is large beside the run's seconds (371 M parameters and two
moments: 4.4 GB), so what is copied to the host and how is the first token
family's (``benchmarks/families/afmoe.py::snapshot``), as are ``groups``
and ``state_unchanged``; a step is one sequence, so ``half_batch`` and
``readings`` are the block-diffusion family's
(``benchmarks/families/sdar_moe.py``): half of the sequence left out, and
no ``bias_gap``.

The counts, each from the configuration's ``model`` group:
``train_flops_per_example`` is 3 x 2 x the multiply-adds a token meets
here (every matrix it is multiplied by, with ``top_k * held / total`` of a
routed expert, the routing being even; attention's scores and values over
the entries the causal mask and, on a window layer, the window leave) x
the tokens of the sequence. Document masks leave fewer live entries, so
the count bounds the mathematics from above; norms, softmax, the router's
top-k, the loss and the optimizer are left out, and nothing recomputed
counts.

``attention_flops`` and ``attention_bytes`` are the attention kernels' own
(``tpu_resnet/ops/attention.py``: JAX's ``splash_attention``, one call a
layer each way), a sequence over every layer, global and window together:
forward the scores and the values over the live entries of each layer's
mask (two products of ``head_dim`` an entry and head), backward 2.5 x that
(the fused backward kernel computes the scores again: five products for
two); the bytes each tensor has to cross HBM once at bf16 (``q``, ``k``,
``v`` and the output forward, and backward also the output's cotangent
read and the three cotangents written) and the float32 log-sum of each
query row. ``roofline`` reads their share from a traced run. The expert
half of a layer is what ``model.remat`` computes again, never attention:
each kernel runs once a layer and step.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.families.afmoe import (  # noqa: F401  (the harness asks)
    groups, sampled, snapshot, state_unchanged)
from benchmarks.families.sdar_moe import half_batch, readings  # noqa: F401
from benchmarks.lib.harness import log

KINDS = ("global", "window")


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "packed sequence", "tokens": arch["seq_len"]}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights: a
    short sequence of ids (no leaf's shape depends on its length)."""
    import jax.numpy as jnp

    return jnp.zeros((1, min(8, cfg.data.seq_len)), jnp.int32)


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding (benchmarks/reference/smallthinker.py).
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from. ``seed`` is unread: the job draws nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import smallthinker as ref

    del seed
    inputs, labels = rows
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        params, mu, _, losses, gnorms = ref.follow(
            {k: jnp.asarray(v, jnp.float32)
             for k, v in before["params"].items()},
            inputs, labels, config["model"], config["job"],
            quantize=quantize, start_step=before["step"])
        params, mu = jax.device_get(jax.jit(
            lambda p, m: jax.tree_util.tree_map(sampled, (p, m)))(params, mu))
    log(f"reference ({quantize}) over {len(inputs)} steps: "
        f"{time.perf_counter() - t0:.1f} s")
    return {"params": params, "stats": {}, "mu": mu, "loss": losses[-1],
            "gnorm": gnorms[-1], "losses": losses}


# ------------------------------------------------------------------ counts
def live_entries(seq_len: int, window: int) -> int:
    """The (query, key) pairs of a head that the causal mask leaves, and
    of them the last ``window`` keys of each row (0: all)."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _windows(arch: Dict):
    """Each layer's window, 0 for a global layer."""
    return [arch["window"] if kind == "window" else 0
            for kind in arch["layers"]]


def forward_macs_per_token(arch: Dict) -> float:
    d, s = arch["hidden"], arch["seq_len"]
    q = arch["heads"] * arch["head_dim"]
    kv = arch["kv_heads"] * arch["head_dim"]
    routed = arch["top_k"] * arch["experts_held"] / arch["experts_total"]
    total = d * arch["vocab_rows"]                     # the head
    for window in _windows(arch):
        total += d * arch["experts_total"]             # the router
        total += d * (q + kv + kv) + q * d             # q, k, v; out
        total += 2 * q * live_entries(s, window) / s   # scores, values
        total += 3 * d * arch["expert_width"] * routed
    return total


def train_flops_per_example(arch: Dict) -> float:
    """Forward + backward model FLOPs of one example, a packed sequence:
    3 x 2 x MACs a token x its tokens."""
    return 6.0 * forward_macs_per_token(arch) * arch["seq_len"]


def attention_flops(arch: Dict, backward: bool = False) -> float:
    """The attention kernels' FLOPs a sequence (module docstring): two
    products of ``head_dim`` over the live entries of every head of every
    layer forward, five backward."""
    s = arch["seq_len"]
    live = sum(live_entries(s, window) for window in _windows(arch))
    return ((2.5 if backward else 1.0) * arch["heads"] * live
            * 2 * 2 * arch["head_dim"])


def attention_bytes(arch: Dict, backward: bool = False) -> float:
    """The bytes the attention kernels move a sequence (module
    docstring)."""
    s, d = arch["seq_len"], arch["head_dim"]
    q, kv = arch["heads"] * s * d * 2, arch["kv_heads"] * s * d * 2
    lse = arch["heads"] * s * 4
    moved = 2 * (q + 2 * kv) + q + lse if backward else \
        q + 2 * kv + q + lse
    return float(len(arch["layers"]) * moved)


def roofline(run, prefix: str, backward: bool):
    """The attention kernels' share of their roofline: the least time the
    chip could take for their operations or for their bytes, whichever is
    longer, at the peaks of ``run.peaks``, for the sequences of the traced
    window's steps, over the seconds of the ``device_ops`` rows whose name
    begins with ``prefix``, in percent. None where there is nothing to
    read: no trace, another family, or no such row among the largest."""
    if run.trace is None or run.peaks is None or not run.images \
            or not set(run.arch.get("layers", ())) <= set(KINDS) \
            or "window" not in run.arch:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"]
                  if name.startswith(prefix))
    if not seconds:
        return None
    least = max(
        attention_flops(run.arch, backward) / run.peaks["bf16_flops_per_s"],
        attention_bytes(run.arch, backward) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * run.images / (seconds * run.chips)
