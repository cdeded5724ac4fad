"""The fixture's second model family, ``mlp``: the program's
one-hidden-layer classifier (``tpu_resnet/models/mlp.py``; leaves
``hidden/*`` and ``softmax_linear/*``, no batch statistics). It is here to
show that a family is added by files alone: this module, its reference
beside it (``../reference/mlp.py``), a configuration, a limits file and
the manifest's entries; no file under ``benchmarks/`` knows of it.

``readings`` gives ``loss_rel``, ``gnorm_rel`` and ``step_count`` as the
ResNet family does, and ``mom`` (the momentum buffers after the chunk) and
``dparam`` (the parameters' change over it) as ``_gap``, ``_med``,
``_all`` and ``_cos`` (benchmarks/lib/check.py)."""

import os

import numpy as np

from benchmarks.lib import check
from benchmarks.lib.harness import flat
from benchmarks.lib.manifest import load_module

STAND_INS = ("bf16",)  # the control: the precision below float32


def example(arch):
    return {"what": "image", "pixels": arch["image_size"] ** 2}


def example_input(cfg):
    import jax.numpy as jnp

    size = cfg.data.resolved_image_size
    return jnp.zeros((1, size, size, 3), jnp.float32)


def train_flops_per_example(arch):
    """3 x 2 x the multiply-adds of the two dense layers."""
    inputs = 3 * arch["image_size"] ** 2
    return 6 * arch["hidden_units"] * (inputs + arch["num_classes"])


def snapshot(state):
    """Parameters, one momentum buffer a parameter (optax.sgd's trace,
    keyed alike) and the step counter."""
    params = flat(state.params)
    trace = flat(state.opt_state)
    mom = {k: next(v for t, v in trace.items() if t.endswith("/" + k))
           for k in params}
    return {"params": params, "mom": mom, "step": int(np.asarray(state.step))}


def follow(before, rows, config, seed, quantize="none"):
    ref = load_module(os.path.join(os.path.dirname(__file__), os.pardir,
                                   "reference", "mlp.py"))
    f32 = lambda tree: {k: np.asarray(v, np.float32) for k, v in tree.items()}
    params, mom, losses, gnorms = ref.follow(
        f32(before["params"]), f32(before["mom"]), *rows, config["job"],
        seed, quantize=quantize, start_step=before["step"])
    return {"params": flat(params), "mom": flat(mom), "loss": losses[-1],
            "gnorm": gnorms[-1], "losses": losses}


def groups(program, reference):
    return {
        "mom": (program["mom"], reference["mom"]),
        "dparam": (check.delta(program["params"], program["params0"]),
                   check.delta(reference["params"], program["params0"])),
    }


def readings(program, reference):
    out = {
        "loss_rel": check.rel(program["loss"], reference["loss"]),
        "gnorm_rel": check.rel(program["gnorm"], reference["gnorm"]),
        "step_count": float(abs(program["step"] - program["step0"]
                                - program["rows"])),
    }
    for name, (prog, ref) in groups(program, reference).items():
        out.update(check.group_readings(name, prog, ref))
    return out
