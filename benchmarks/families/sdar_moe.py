"""The model family ``sdar_moe``: sparse-expert decoders trained by
diffusion over blocks (a noised and a clean copy of every packed sequence
under a three-part attention mask, the loss on the masked positions) by
AdamW, as one chip of an expert-parallel group holds them. What the
harness asks of a family (benchmarks/lib/manifest.py), said for this one;
the plain reference is ``benchmarks/reference/sdar_moe.py``.

The state is large beside the run's seconds (456 M parameters and two
moments: 5.5 GB), so what is copied to the host and how is the other token
family's (``benchmarks/families/afmoe.py::snapshot``: every parameter of
the start and the largest magnitude among its moments; of the state after
the chunk every ``stride``-th element, about 2^18 of a leaf, of each
parameter and first moment), as are ``groups`` and ``state_unchanged``.
This family has no state beside its parameters (no ``expert_bias``).

The noise is not part of what is copied: the program draws it on the
device from the step's key and the reference draws it again from the
recipe (the configuration's ``job.noise``; ``train.seed`` and the step's
number), so a program that noised otherwise reads a different loss.

The numbers ``readings`` gives (benchmarks/lib/check.py has the measures):

``loss_rel``, ``gnorm_rel``  the chunk's reported loss and gradient norm
                 (its last step's) against the reference's, relative.
``step_count``   the program's step counter after the chunk against the
                 rows it was fed; exact.
``moments0``     the largest magnitude among the moments at the start.
``head_gap``, ``head_cos``  the first moment of the output head, the leaf
                 next to the loss: the gap of its norm, and one minus its
                 cosine with the reference's.
``mu_*``         the first moments of all leaves: worst leaf's gap, median
                 leaf's, all leaves' norm and one minus their cosine.
``dparam_*``     the parameters' change over the chunk, likewise.

The counts (``seq_len`` = ``L`` clean ids a sequence, fed as ``2L``
positions): ``train_flops_per_example`` is 3 x 2 x the multiply-adds of a
forward pass: every matrix the ``2L`` positions are multiplied by (``top_k
* held / total`` of a routed expert, the routing being even), attention's
scores and values over the ``L^2 + L B`` entries a head that the
three-part mask leaves live, and the head over the ``L`` noisy positions.
Document masks leave fewer live entries than that, so the count is an
upper bound of the work the mathematics needs; norms, softmax, the
router's top-k, the loss and the optimizer are left out, and nothing
recomputed counts. ``attention_fwd_flops`` and ``attention_bwd_flops`` are
the attention kernels' own: scores and values over the live entries
forward, and 2.5 x that backward (the fused backward kernel computes the
scores again: five products for two).
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.families.afmoe import (  # noqa: F401  (the harness asks)
    groups, sampled, snapshot, state_unchanged)
from benchmarks.lib import check
from benchmarks.lib.harness import log


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "packed sequence", "tokens": arch["seq_len"],
            "positions": 2 * arch["seq_len"]}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights: a
    noised and a clean copy of one block (no leaf's shape depends on the
    length)."""
    import jax.numpy as jnp

    return jnp.zeros((1, 2 * cfg.sdar_moe.block_length), jnp.int32)


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding.
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from. ``seed`` is the run's ``train.seed``: the noise
    of a step is drawn from it and the step's number. The rows' labels
    (the next ids) are not read."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sdar_moe as ref

    inputs, _ = rows
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        params, mu, _, losses, gnorms, shares = ref.follow(
            {k: jnp.asarray(v, jnp.float32)
             for k, v in before["params"].items()},
            inputs, config["model"], config["job"], seed,
            quantize=quantize, start_step=before["step"])
        params, mu = jax.device_get(jax.jit(
            lambda p, m: jax.tree_util.tree_map(sampled, (p, m)))(params, mu))
    log(f"reference ({quantize}) over {len(inputs)} steps: "
        f"{time.perf_counter() - t0:.1f} s; masked shares "
        f"{[round(s, 4) for s in shares]}")
    return {"params": params, "stats": {}, "mu": mu, "loss": losses[-1],
            "gnorm": gnorms[-1], "losses": losses}


# ------------------------------------------------------------------ faults
def half_batch(inner):
    """``benchmarks/lib/faults.py::half_batch`` for a batch of one
    sequence (its own halves the batch axis, and cuts this one to
    nothing): half of the sequence left out, its first half standing in
    for the second."""
    import jax.numpy as jnp

    def run(state, gi, gl, off, c):
        n = gi.shape[2] // 2
        halved = [jnp.asarray(jnp.tile(g[:, :, :n], (1, 1, 2)), g.dtype,
                              device=g.sharding) for g in (gi, gl)]
        return inner(state, *halved, off, c)

    return run


# ---------------------------------------------------------------- readings
def readings(program: Dict, reference: Dict, head: str = "head"
             ) -> Dict[str, float]:
    """The numbers compared. ``program`` and ``reference`` hold ``params``
    and ``mu`` (cut, see ``sampled``) after the chunk, ``loss`` and
    ``gnorm`` of its last step; the program's also ``params0`` (the shared
    start, whole), ``moments0``, ``step0`` and ``step`` (its counter
    before and after) and ``rows`` (steps fed)."""
    out = {
        "loss_rel": check.rel(program["loss"], reference["loss"]),
        "gnorm_rel": check.rel(program["gnorm"], reference["gnorm"]),
        "step_count": float(abs(program["step"] - program.get("step0", 0)
                                - program["rows"])),
        "moments0": float(program.get("moments0", 0.0)),
    }
    compared = groups(program, reference)
    prog, ref = compared["mu"]
    out["head_gap"], out["head_cos"] = check.whole({head: prog[head]},
                                                   {head: ref[head]})
    still = check.still_leaves(ref)
    for name, (prog, ref) in compared.items():
        out.update(check.group_readings(
            name, prog, ref, skip=still if name == "dparam" else ()))
    return out


# ------------------------------------------------------------------- FLOPs
def live_entries(arch: Dict) -> int:
    """The entries of the ``2L x 2L`` scores a head that the three-part
    mask leaves: block causal ``L (L + B) / 2``, offset block causal ``L
    (L - B) / 2``, the noisy diagonal ``L B``."""
    length, block = arch["seq_len"], arch["block_length"]
    return length * length + length * block


def forward_macs_per_example(arch: Dict) -> float:
    d = arch["hidden"]
    q = arch["heads"] * arch["head_dim"]
    kv = arch["kv_heads"] * arch["head_dim"]
    routed = arch["top_k"] * arch["experts_held"] / arch["experts_total"]
    position = (d * (q + kv + kv) + q * d             # q, k, v; out
                + d * arch["experts_total"]           # the router
                + 3 * d * arch["expert_width"] * routed)
    return (arch["layers"] * (2 * arch["seq_len"] * position
                              + 2 * q * live_entries(arch))
            + arch["seq_len"] * d * arch["vocab_rows"])    # the head


def train_flops_per_example(arch: Dict) -> float:
    """Forward + backward model FLOPs of one example, a packed sequence
    of ``seq_len`` clean ids: 3 x 2 x its multiply-adds."""
    return 6.0 * forward_macs_per_example(arch)


def attention_fwd_flops(arch: Dict) -> float:
    """The forward attention kernels' FLOPs a sequence: two products of
    ``head_dim`` over the live entries of every head of every layer."""
    return (arch["layers"] * arch["heads"] * live_entries(arch)
            * 2 * 2 * arch["head_dim"])


def attention_bwd_flops(arch: Dict) -> float:
    """The backward kernels': five products for the forward's two."""
    return 2.5 * attention_fwd_flops(arch)


def kernel_share(run, prefix: str, flops_per_example):
    """What the two attention readers share: ``flops_per_example`` of the
    run's configuration x the examples of the traced window's steps, over
    the seconds of the ``device_ops`` rows whose name begins with
    ``prefix`` and the chips' bf16 peak, in percent. None where there is
    nothing to read."""
    if run.trace is None or run.peaks is None or not run.images \
            or "block_length" not in run.arch:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"]
                  if name.startswith(prefix))
    if not seconds:
        return None
    return (100.0 * flops_per_example(run.arch) * run.images / seconds
            / (run.peaks["bf16_flops_per_s"] * run.chips))
