"""The model family ``afmoe``: sparse-expert transformers with windowed and
full attention mixed, trained on packed token sequences by AdamW, as one
chip of an expert-parallel group holds them. What the harness asks of a
family (benchmarks/lib/manifest.py), said for this one; the plain
reference is ``benchmarks/reference/afmoe.py``.

A state of this family is large beside the run's seconds (504 M
parameters and two moments: 6 GB), so ``snapshot`` copies to the host only
what is read. Of the state the program starts from: every parameter and
``expert_bias`` (the reference starts from them), and of the moments one
reduction, their largest magnitude (``moments0``, which has to read 0: the
reference starts from zero moments). Of the state after the chunk: the
biases, and of each parameter and its first moment every ``stride``-th
element, about 2^18 of a leaf (``sampled``; the reference's leaves are cut
the same way, on the device). Which of the two a state is, its moments
say: all zero, it is a start.

The numbers ``readings`` gives (benchmarks/lib/check.py has the measures):

``loss_rel``, ``gnorm_rel``  the chunk's reported loss and gradient norm
                 (its last step's) against the reference's, relative.
``step_count``   the program's step counter after the chunk against the
                 rows it was fed; exact.
``moments0``     the largest magnitude among the moments at the start.
``head_gap``, ``head_cos``  the first moment of the output head, the leaf
                 next to the loss: the gap of its norm, and one minus its
                 cosine with the reference's. A first moment is a decayed
                 sum of the gradients as the optimizer got them, and linear
                 in them.
``mu_*``         the first moments of all leaves: worst leaf's gap, median
                 leaf's, all leaves' norm and one minus their cosine.
``dparam_*``     the parameters' change over the chunk, likewise. AdamW
                 divides each element's moment by the root of its second
                 moment, so an element whose gradient is small moves as far
                 as any other and rounding turns its direction: the cosine
                 reads further from 0 than a first moment's.
``bias_gap``     the routers' ``expert_bias`` after the chunk: the largest
                 difference from the reference's, in units of the update's
                 step (``balance_coeff``). Each step moves an entry by one
                 unit up or down by the sign of its expert's load against
                 the mean, so experts within rounding of the mean read a
                 few units apart and a router that chose otherwise reads
                 many.

``train_flops_per_example`` gives the model FLOPs of one packed sequence in
a training step from the configuration's shapes: 3 x 2 x the multiply-adds
a token meets here (every matrix it is multiplied by, with ``top_k *
held / total`` of a routed expert, the routing being even; attention's
scores and values over the entries that the causal mask and the window
leave) x the tokens of the sequence. Document masks leave fewer live
entries than that, so the count is an upper bound of the work the
mathematics needs; norms, softmax, the router's top-k, the loss and the
optimizer are left out, and nothing recomputed counts.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

import numpy as np

from benchmarks.lib import check
from benchmarks.lib.harness import BenchmarkError, flat, log

SAMPLE = 2 ** 18     # elements of a leaf that are compared, about


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "packed sequence", "tokens": arch["seq_len"]}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights: a
    short sequence of ids (no leaf's shape depends on its length)."""
    import jax.numpy as jnp

    return jnp.zeros((1, min(8, cfg.data.seq_len)), jnp.int32)


# ---------------------------------------------------------------- snapshot
def stride(size: int) -> int:
    return max(1, size // SAMPLE)


def sampled(x):
    """Every ``stride``-th element of a leaf, flat. A leaf already cut is
    left as it is (its stride is 1)."""
    x = x.reshape(-1)
    return x[::stride(x.size)]


def named(tree) -> Dict:
    """A pytree's leaves, still on the device, as ``{"a/b/c": leaf}``."""
    import jax

    return {"/".join(str(getattr(p, "key", getattr(p, "name", getattr(
        p, "idx", p)))) for p in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def moments_of(opt_state) -> Tuple[Dict, Dict]:
    """AdamW's first and second moments keyed like the parameters."""
    leaves = named(opt_state)
    out = []
    for which in ("mu", "nu"):
        mark = f"/{which}/"
        out.append({("/" + k).split(mark, 1)[1]: v
                    for k, v in leaves.items() if mark in "/" + k})
    if not out[0] or set(out[0]) != set(out[1]):
        raise BenchmarkError("the optimizer state holds no pair of Adam "
                             "moments a parameter")
    return out[0], out[1]


def snapshot(state) -> Dict:
    """The host copy of what is compared (module docstring)."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    mu, nu = moments_of(state.opt_state)
    largest = float(jax.jit(lambda m, n: jnp.max(jnp.stack(
        [jnp.max(jnp.abs(x)) for x in list(m.values()) + list(n.values())])
    ))(mu, nu))
    out = {"stats": flat(state.batch_stats), "moments": largest,
           "step": int(np.asarray(state.step))}
    if largest == 0.0:      # a start: the reference needs every parameter
        # one call for the whole tree: every leaf's copy is begun before
        # the first is waited for (leaf by leaf the 2 GB took about 20 s)
        out["params"] = jax.device_get(named(state.params))
        out["mu"] = {k: np.zeros(sampled(v).shape, np.float32)
                     for k, v in out["params"].items()}
    else:
        out["params"], out["mu"] = jax.device_get(jax.jit(
            lambda p, m: jax.tree_util.tree_map(sampled, (p, m)))(
                named(state.params), mu))
    log(f"snapshot of a state {'at its start' if largest == 0.0 else 'after'}"
        f" {out['step']} steps: {time.perf_counter() - t0:.1f} s, "
        f"{sum(v.nbytes for v in out['params'].values()) / 1e6:.0f} MB of "
        f"parameters")
    return out


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding (benchmarks/reference/afmoe.py).
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from. ``seed`` is unread: the job draws nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import afmoe as ref

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


# ------------------------------------------------------------------ faults
def state_unchanged(inner):
    """``benchmarks/lib/faults.py::state_unchanged`` for a state that does
    not fit the device twice (its own keeps a device copy beside the
    program's 9 GB of temporaries): the state waits on the host while the
    step runs, and comes back in its place. The metrics are real."""
    import jax

    def run(state, gi, gl, off, c):
        layout = jax.tree_util.tree_map(lambda x: x.sharding, state)
        kept = jax.device_get(state)
        stepped, metrics = inner(state, gi, gl, off, c)
        metrics = jax.device_get(metrics)
        for leaf in jax.tree_util.tree_leaves(stepped):
            leaf.delete()
        return jax.device_put(kept, layout), metrics

    return run


def half_batch(inner):
    """``benchmarks/lib/faults.py::half_batch`` for labels of any rank (its
    own tiles labels of one entry an example): half of the batch left out,
    the first half standing in for the second."""
    import jax.numpy as jnp

    def run(state, gi, gl, off, c):
        n = gi.shape[1] // 2
        halved = [jnp.asarray(jnp.tile(g[:, :n], (1, 2) + (1,) * (g.ndim - 2)),
                              g.dtype, device=g.sharding) for g in (gi, gl)]
        return inner(state, *halved, off, c)

    return run


# ---------------------------------------------------------------- readings
def cut(tree: Dict) -> Dict:
    return {k: sampled(np.asarray(v)) for k, v in tree.items()}


def groups(program: Dict, reference: Dict) -> Dict[str, Tuple[Dict, Dict]]:
    """What is compared leaf by leaf, the program's beside the
    reference's: first moments, and the parameters' change."""
    start = cut(program["params0"])
    return {
        "mu": (cut(program["mu"]), cut(reference["mu"])),
        "dparam": (check.delta(cut(program["params"]), start),
                   check.delta(cut(reference["params"]), start)),
    }


def readings(program: Dict, reference: Dict, head: str = "head",
             coeff: float = 0.001) -> Dict[str, float]:
    """The numbers compared. ``program`` and ``reference`` hold ``params``,
    ``mu`` (cut, see ``sampled``) and ``stats`` after the chunk, ``loss``
    and ``gnorm`` of its last step; the program's also ``params0`` (the
    shared start, whole), ``moments0``, ``step0`` and ``step`` (its counter
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
    out["bias_gap"] = max(
        float(np.max(np.abs(np.asarray(program["stats"][k], np.float64)
                            - np.asarray(reference["stats"][k], np.float64))))
        for k in reference["stats"]) / coeff
    return out


# ------------------------------------------------------------------- FLOPs
def live_entries(seq_len: int, window: int) -> float:
    """Keys a query attends to, meaned over the positions of a sequence:
    those before it and itself, and of them the last ``window`` (0: all)."""
    return sum(min(i + 1, window) if window else i + 1
               for i in range(seq_len)) / seq_len


def forward_macs_per_token(arch: Dict) -> float:
    d = arch["hidden"]
    q = arch["heads"] * arch["head_dim"]
    kv = arch["kv_heads"] * arch["head_dim"]
    total = d * arch["vocab_rows"]                     # the output head
    for kind in arch["layers"]:
        window = arch["window"] if kind.endswith("_sliding") else 0
        total += d * (q + kv + kv + q) + q * d         # q, k, v, gate; out
        total += 2 * q * live_entries(arch["seq_len"], window)
        if kind.startswith("dense"):
            total += 3 * d * arch["dense_width"]
        else:
            routed = arch["top_k"] * arch["experts_held"] \
                / arch["experts_total"]
            total += d * arch["experts_total"]         # the router
            total += 3 * d * arch["expert_width"] * (arch["shared"] + routed)
    return total


def train_flops_per_example(arch: Dict) -> float:
    """Forward + backward model FLOPs of one example, a packed sequence:
    3 x 2 x MACs a token x its tokens."""
    return 6.0 * forward_macs_per_token(arch) * arch["seq_len"]
