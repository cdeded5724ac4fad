"""The model family ``qwen3_next``: decoders whose layers mix tokens by
Gated DeltaNet or by gated full attention, each followed by routed experts
beside a gated shared one, trained on packed token sequences by AdamW, as
one chip of an expert-parallel group holds them. What the harness asks of
a family (benchmarks/lib/manifest.py), said for this one; the plain
reference is ``benchmarks/reference/qwen3_next.py``.

The state is large beside the run's seconds (626 M parameters and two
moments: 7.5 GB), so what is copied to the host and how is the first token
family's (``benchmarks/families/afmoe.py::snapshot``), as are ``groups``,
``state_unchanged`` and ``half_batch`` (two sequences a step: half of them
is one). This family has no state beside its parameters, so ``readings``
are the block-diffusion family's (``benchmarks/families/sdar_moe.py``):
no ``bias_gap``.

The counts, each from the configuration's ``model`` group:
``train_flops_per_example`` is 3 x 2 x the multiply-adds a token meets
here (every matrix it is multiplied by, with ``top_k * held / total`` of a
routed expert, the routing being even, and the shared expert whole; a
filter's taps; the recurrence at its own ``3 dk dv`` a value head, not the
chunked form's; attention's scores and values over the entries the causal
mask leaves) x the tokens of the sequence. Document masks leave fewer live
entries, so the count bounds the mathematics from above; norms, gates,
softmax, the router's top-k, the loss and the optimizer are left out, and
nothing recomputed counts.

``gdn_ops`` and ``gdn_bytes`` are the recurrence kernels' own
(``tpu_resnet/ops/gated_delta.py``), a sequence over every DeltaNet layer,
at the kernel's chunk ``C``: the operations of the chunked form as the
kernels compute it (each product's multiply-adds x 2, the inverse's
``log2 C - 1`` squarings and products among them), forward, and backward
with the forward's parts computed again; the bytes each tensor has to
cross HBM once at its dtype (``q`` and ``k`` once, though two value heads
read each), and the states the forward saves, written forward and read
backward. ``roofline`` reads their share from a traced run; where the
configuration's ``remat`` is on, the forward kernel runs twice a step (the
DeltaNet mixer's forward is computed again for its backward), and its rows
are set against twice its count.
"""

from __future__ import annotations

import math
import time
from typing import Dict

from benchmarks.families.afmoe import (  # noqa: F401  (the harness asks)
    groups, half_batch, sampled, snapshot, state_unchanged)
from benchmarks.families.sdar_moe import readings  # noqa: F401
from benchmarks.lib.harness import log


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "packed sequence", "tokens": arch["seq_len"]}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights: a
    short sequence of ids (no leaf's shape depends on its length; the
    recurrence takes it as one chunk)."""
    import jax.numpy as jnp

    return jnp.zeros((1, min(8, cfg.data.seq_len)), jnp.int32)


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding (benchmarks/reference/qwen3_next.py).
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from. ``seed`` is unread: the job draws nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import qwen3_next as ref

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
def forward_macs_per_token(arch: Dict) -> float:
    d, s = arch["hidden"], arch["seq_len"]
    hk = arch["key_heads"] * arch["key_dim"]
    hv = arch["value_heads"] * arch["value_dim"]
    q = arch["heads"] * arch["head_dim"]
    kv = arch["kv_heads"] * arch["head_dim"]
    routed = arch["top_k"] * arch["experts_held"] / arch["experts_total"]
    total = d * arch["vocab_rows"]                     # the head
    for kind in arch["layers"]:
        if kind == "linear":
            total += d * (2 * hk + 2 * hv)             # q, k, v, z
            total += d * 2 * arch["value_heads"]       # b, a
            total += (2 * hk + hv) * arch["conv_taps"]  # the filter
            total += 3 * arch["key_dim"] * hv          # the recurrence
            total += hv * d                            # out
        else:
            total += d * (2 * q + kv + kv) + q * d     # q and gate, k, v; out
            total += 2 * q * (s + 1) / 2               # scores, values
        total += d * arch["experts_total"] + d         # router, shared gate
        total += 3 * d * (arch["expert_width"] * routed
                          + arch["shared_width"])
    return total


def train_flops_per_example(arch: Dict) -> float:
    """Forward + backward model FLOPs of one example, a packed sequence:
    3 x 2 x MACs a token x its tokens."""
    return 6.0 * forward_macs_per_token(arch) * arch["seq_len"]


def _chunks(arch: Dict):
    """``(units, C, dk, dv, steps)``: the (chunk, value head) pairs of a
    sequence over every DeltaNet layer, the chunk, the head sizes and the
    inverse's squarings."""
    c = arch["chunk"]
    layers = sum(kind == "linear" for kind in arch["layers"])
    units = layers * arch["value_heads"] * arch["seq_len"] // c
    return (units, c, arch["key_dim"], arch["value_dim"],
            max(0, math.ceil(math.log2(c)) - 1))


def gdn_ops(arch: Dict, backward: bool = False) -> float:
    """The recurrence kernels' operations a sequence (module docstring):
    forward ``2 C^2 dk + 2 C^2 dv + 3 C dk dv`` multiply-adds a chunk and
    value head, backward ``6 C^2 dk + 5 C^2 dv + 8 C dk dv``, each with the
    inverse's ``2 steps C^3``."""
    units, c, dk, dv, steps = _chunks(arch)
    if backward:
        macs = 6 * c * c * dk + 5 * c * c * dv + 8 * c * dk * dv
    else:
        macs = 2 * c * c * dk + 2 * c * c * dv + 3 * c * dk * dv
    return 2.0 * units * (macs + 2 * steps * c ** 3)


def gdn_bytes(arch: Dict, backward: bool = False) -> float:
    """The bytes the recurrence kernels move a sequence (module
    docstring): forward reads ``q``, ``k`` (a key head's), ``v`` in bf16,
    ``beta``, ``G``, ``R`` and the two rows in float32, writes the output in
    bf16 and each chunk's starting state in float32; backward reads the
    same inputs, the states and the output's cotangent, and writes the
    cotangents of ``q``, ``k`` (a value head's each), ``v``, ``beta`` and
    ``G`` (a column and a row) in float32."""
    layers = sum(kind == "linear" for kind in arch["layers"])
    s, hk, hv = arch["seq_len"], arch["key_heads"], arch["value_heads"]
    dk, dv = arch["key_dim"], arch["value_dim"]
    states = hv * (s // arch["chunk"]) * dk * dv * 4
    inputs = s * (2 * hk * dk + hv * dv) * 2 + s * hv * 5 * 4
    if backward:
        out = s * hv * (2 * dk + dv) * 4 + s * hv * 3 * 4
        moved = inputs + states + s * hv * dv * 2 + out
    else:
        moved = inputs + s * hv * dv * 2 + states
    return float(layers * moved)


def roofline(run, prefix: str, backward: bool):
    """A recurrence kernel's share of its roofline: the least time the
    chip could take for its operations or for its bytes, whichever is
    longer, at the peaks of ``run.peaks``, for the sequences of the traced
    window's steps, over the seconds of the ``device_ops`` rows whose name
    begins with ``prefix``, in percent. None where there is nothing to
    read: no trace, another family, or no such row among the largest."""
    if run.trace is None or run.peaks is None or not run.images \
            or "value_heads" not in run.arch:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"]
                  if name.startswith(prefix))
    if not seconds:
        return None
    least = max(
        gdn_ops(run.arch, backward) / run.peaks["bf16_flops_per_s"],
        gdn_bytes(run.arch, backward) / run.peaks["hbm_bytes_per_s"])
    runs = 1 if backward or not run.arch.get("remat") else 2
    return 100.0 * runs * least * run.images / (seconds * run.chips)
