"""The model family ``lfm2_moe``: decoders whose layers mix tokens by a
gated short convolution or by full attention, with a dense MLP or routed
experts behind either and a tied head, trained on packed token sequences
by AdamW, as one chip of an expert-parallel group holds them. What the
harness asks of a family (benchmarks/lib/manifest.py), said for this one;
the plain reference is ``benchmarks/reference/lfm2_moe.py``.

The state is large beside the run's seconds (469 M parameters and two
moments: 5.6 GB), so what is copied to the host and how is the first token
family's (``benchmarks/families/afmoe.py::snapshot``: every parameter and
``expert_bias`` of the start and the largest magnitude among its moments;
of the state after the chunk the biases and every ``stride``-th element,
about 2^18 of a leaf, of each parameter and first moment), as are
``groups``, ``state_unchanged``, ``half_batch`` and the ``readings``, which
this family takes with one difference: the leaf next to the loss is the
tied ``embed`` (there is no ``head``), so ``head_gap`` and ``head_cos``
are of the embedding's first moment, the sum of the head's gradient and
the lookup's. ``bias_gap`` is read as for ``afmoe``: the family has state
beside its parameters, each expert layer's ``expert_bias``.

The counts, each from the configuration's ``model`` group:
``train_flops_per_example`` is 3 x 2 x the multiply-adds a token meets
here (every matrix it is multiplied by, with ``top_k * held / total`` of a
routed expert, the routing being even, and the tied table once, as the
head; attention's scores and values over the entries the causal mask
leaves) x the tokens of the sequence. Document masks leave fewer live
entries, so the count bounds the mathematics from above; a filter's
taps count (``taps`` multiply-adds a channel), the convolution's two
gates, norms, softmax, the router's top-k, the loss and the optimizer are
left out, and nothing recomputed counts.
``attention_fwd_flops`` and ``attention_bwd_flops`` are the attention
kernels' own: scores and values over the live causal entries at the
PUBLISHED head of 64, forward, and 2.5 x that backward (the fused
backward kernel computes the scores again: five products for two).
``conv_bytes`` is what the elementwise part of one conv operator has to
move through HBM, its floor: it has no FLOPs to speak of. No reader calls
the last three yet: one attention layer's forward kernel stands under the
tenth row of ``device_ops`` (0.25 s of 100 steps; PERF.md section 7), so
``kernel_share`` and the counts wait here, with their tests, for the
``benchmark`` PR that reads device operations by scope.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.families import afmoe
from benchmarks.families.afmoe import (  # noqa: F401  (the harness asks)
    groups, half_batch, sampled, snapshot, state_unchanged)
from benchmarks.lib.harness import flat, log


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "packed sequence", "tokens": arch["seq_len"]}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights: a
    short sequence of ids (no leaf's shape depends on its length: a
    filter is ``(hidden, taps)``)."""
    import jax.numpy as jnp

    return jnp.zeros((1, min(8, cfg.data.seq_len)), jnp.int32)


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding (benchmarks/reference/lfm2_moe.py).
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from. ``seed`` is unread: the job draws nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import lfm2_moe as ref

    del seed
    inputs, labels = rows
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        params, biases, mu, _, losses, gnorms = ref.follow(
            {k: jnp.asarray(v, jnp.float32)
             for k, v in before["params"].items()},
            {k: jnp.asarray(v, jnp.float32)
             for k, v in before["stats"].items()},
            inputs, labels, config["model"], config["job"],
            quantize=quantize, start_step=before["step"])
        params, mu = jax.device_get(jax.jit(
            lambda p, m: jax.tree_util.tree_map(sampled, (p, m)))(params, mu))
    log(f"reference ({quantize}) over {len(inputs)} steps: "
        f"{time.perf_counter() - t0:.1f} s")
    return {"params": params, "stats": flat(biases), "mu": mu,
            "loss": losses[-1], "gnorm": gnorms[-1], "losses": losses}


def readings(program: Dict, reference: Dict) -> Dict[str, float]:
    """``afmoe``'s numbers, the leaf next to the loss being the tied
    embedding."""
    return afmoe.readings(program, reference, head="embed")


# ------------------------------------------------------------------ counts
def forward_macs_per_token(arch: Dict) -> float:
    d = arch["hidden"]
    q = arch["heads"] * arch["head_dim"]
    kv = arch["kv_heads"] * arch["head_dim"]
    routed = arch["top_k"] * arch["experts_held"] / arch["experts_total"]
    total = d * arch["vocab_rows"]                     # the tied head
    for kind in arch["layers"]:
        if kind.endswith("_conv"):
            total += d * 3 * d + d * d                 # in_proj; out_proj
            total += d * arch["conv_taps"]             # the filter
        else:
            total += d * (q + kv + kv) + q * d         # q, k, v; out
            total += 2 * q * (arch["seq_len"] + 1) / 2  # scores, values
        if kind.startswith("dense"):
            total += 3 * d * arch["dense_width"]
        else:
            total += d * arch["experts_total"]         # the router
            total += 3 * d * arch["expert_width"] * routed
    return total


def train_flops_per_example(arch: Dict) -> float:
    """Forward + backward model FLOPs of one example, a packed sequence:
    3 x 2 x MACs a token x its tokens."""
    return 6.0 * forward_macs_per_token(arch) * arch["seq_len"]


def attention_layers(arch: Dict) -> int:
    return sum(kind.endswith("_full") for kind in arch["layers"])


def attention_fwd_flops(arch: Dict) -> float:
    """The forward attention kernels' FLOPs a sequence: two products of
    ``head_dim`` over the ``S (S + 1) / 2`` live entries of every head of
    every attention layer."""
    s = arch["seq_len"]
    return (attention_layers(arch) * arch["heads"] * s * (s + 1) / 2
            * 2 * 2 * arch["head_dim"])


def attention_bwd_flops(arch: Dict) -> float:
    """The backward kernels': five products for the forward's two."""
    return 2.5 * attention_fwd_flops(arch)


def conv_bytes(arch: Dict, tokens: int) -> float:
    """The bytes the elementwise part of ONE conv operator has to move for
    ``tokens`` positions, forward and backward, in the products' 2-byte
    type: forward reads ``B``, ``X``, ``C`` and writes ``y`` (4 arrays of
    ``tokens x hidden``); backward reads the three and ``dy`` and writes
    their three gradients (7)."""
    return (4 + 7) * tokens * arch["hidden"] * 2.0


def kernel_share(run, prefix: str, flops_per_example):
    """What the two attention readers share: ``flops_per_example`` of the
    run's configuration x the examples of the traced window's steps, over
    the seconds of the ``device_ops`` rows whose name begins with
    ``prefix`` and the chips' bf16 peak, in percent. None where there is
    nothing to read."""
    if run.trace is None or run.peaks is None or not run.images \
            or "conv_taps" not in run.arch:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"]
                  if name.startswith(prefix))
    if not seconds:
        return None
    return (100.0 * flops_per_example(run.arch) * run.images / seconds
            / (run.peaks["bf16_flops_per_s"] * run.chips))
