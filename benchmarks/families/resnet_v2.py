"""The model family ``resnet_v2``: pre-activation ResNets and their wide
variant, image classifiers with batch normalization, trained by momentum
SGD. What the harness asks of a family (benchmarks/lib/manifest.py), said
for this one; the plain reference is ``benchmarks/reference/resnet_v2.py``.

The numbers ``readings`` gives (benchmarks/lib/check.py has the measures):

``loss_rel``     the chunk's reported loss (its last step's) against the
                 reference's loss at that step, relative.
``gnorm_rel``    the reported global gradient norm of that step, likewise.
``mom_gap``      the momentum buffers after the chunk, worst leaf: the gap
                 between the program's norm and the reference's, over the
                 reference's norm of that leaf or of the median leaf,
                 whichever is larger. The buffer is the gradients as the
                 optimizer got them, summed with decay.
``dparam_gap``   the parameters' change over the chunk, worst leaf, same
                 measure. Leaves whose reference gradient (its momentum
                 buffer) is under a thousandth of the median leaf's are
                 left out: round-off alone moves them.
``bn_gap``       the BN running statistics' change over the chunk, worst
                 leaf, same measure.
``step_count``   the program's step counter after the chunk against the
                 rows it was fed; exact.

``head_gap``, ``head_cos``  the momentum buffer of the leaf next to the
                 loss (the dense layer's kernel): the gap of its norm, and
                 one minus its cosine with the reference's.
``head_bias_cos``  one minus the cosine of the dense layer's bias buffer:
                 the gradient at the logits, softmax minus labels, meaned
                 over each batch and summed over the chunk's steps. The
                 reference's reading of it moves with its forward pass
                 alone (no normalization layer's backward pass stands
                 behind it), which is why the fp8 control moves it far;
                 the program's own reading is set by how it sums that
                 gradient from bf16 cotangents (PERF.md section 6).
``bn_mean_cos``  one minus the cosine of the change of the BN running
                 *means* alone (``bn_cos`` without the variances): rounded
                 weights shift a channel's mean at first order, which no
                 batch averages away, and its variance at second.

Beside each worst-leaf ``_gap`` stand the same group's ``_med``, ``_all``
and ``_cos``.

``train_flops_per_example`` gives the model FLOPs of one image in a
training step, from the configuration's shapes. It counts what the forward and backward passes require and nothing else: each
convolution and the dense layer as 2 x multiply-adds forward, twice that
again backward (input gradient and weight gradient), so 3 x 2 x
multiply-adds. A multiply-add is counted only where the kernel tap lies on
the image, not on its zero padding (9% of WRN-28-10's nominal count at
32x32, 1% of ResNet-50's at 224x224); that is how XLA counts too.
Normalization, activations, pooling, the loss and the optimizer are left
out (under 1% of a ResNet), and nothing recomputed counts. XLA's own count
of the compiled step is a little lower still, because the first layer needs
no input gradient.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.lib import check
from benchmarks.lib.harness import BenchmarkError, flat


def example(arch: Dict) -> Dict:
    """What one example of ``train_images_per_s`` is, and what it holds."""
    return {"what": "image", "pixels": arch["image_size"] ** 2}


def example_input(cfg):
    """What ``init_partitioned_state`` is shown to draw the weights."""
    import jax.numpy as jnp

    size = cfg.data.resolved_image_size
    return jnp.zeros((1, size, size, 3), jnp.float32)


# ---------------------------------------------------------------- snapshot
def momentum_of(opt_state, params: Dict[str, np.ndarray]
                ) -> Dict[str, np.ndarray]:
    """The optimizer's momentum buffers keyed like the parameters: the
    leaves of its state whose path ends in a parameter's path."""
    leaves = flat(opt_state)
    out = {}
    for key in params:
        hits = [v for k, v in leaves.items()
                if k.endswith("/" + key) and v.shape == params[key].shape]
        if len(hits) != 1:
            raise BenchmarkError(
                f"optimizer state holds {len(hits)} buffers for {key!r}; "
                f"the comparison expects one momentum buffer per leaf")
        out[key] = hits[0]
    return out


def snapshot(state) -> Dict:
    """The host copy of what is compared."""
    params = flat(state.params)
    return {"params": params, "stats": flat(state.batch_stats),
            "mom": momentum_of(state.opt_state, params),
            "step": int(np.asarray(state.step))}


# --------------------------------------------------------------- reference
# What can stand in the program's place for a reading: the reference in a
# lower precision. ``fp8`` is the control; ``bf16`` is the reference's own
# picture of the program's rounding (benchmarks/reference/resnet_v2.py).
STAND_INS = ("fp8", "bf16")


def follow(before: Dict, rows, config: Dict, seed: int,
           quantize: str = "none") -> Dict:
    """The plain reference (or, with ``quantize``, a stand-in for the
    program) over the rows of the first dispatch, from the state the
    program started from."""
    import jax

    from benchmarks.reference import resnet_v2 as ref

    images, labels = rows

    def put(tree):
        return jax.device_put({k: np.asarray(v, np.float32)
                               for k, v in tree.items()})

    with jax.default_matmul_precision("highest"):
        params, stats, mom, losses, gnorms = ref.follow(
            put(before["params"]), put(before["stats"]),
            put(before["mom"]), images, labels, config["model"],
            config["job"], seed, quantize=quantize,
            start_step=before["step"])
    out = {"params": flat(params), "stats": flat(stats), "mom": flat(mom),
           "loss": losses[-1], "gnorm": gnorms[-1], "losses": losses}
    del params, stats, mom
    return out


# ---------------------------------------------------------------- readings
def groups(program: Dict, reference: Dict) -> Dict[str, Tuple[Dict, Dict]]:
    """What is compared leaf by leaf, the program's beside the
    reference's: momentum buffers, the parameters' change, the BN running
    statistics' change."""
    return {
        "mom": (program["mom"], reference["mom"]),
        "dparam": (check.delta(program["params"], program["params0"]),
                   check.delta(reference["params"], program["params0"])),
        "bn": (check.delta(program["stats"], program["stats0"]),
               check.delta(reference["stats"], program["stats0"])),
    }


def readings(program: Dict, reference: Dict,
             head: str = "final_dense/kernel",
             head_bias: str = "final_dense/bias") -> Dict[str, float]:
    """The numbers compared. ``program`` and ``reference`` hold ``params,
    stats, mom`` after the chunk, ``loss`` and ``gnorm`` of its last step;
    the program's also ``params0, stats0`` (the shared start), ``step0``
    and ``step`` (its counter before and after) and ``rows`` (steps fed).
    For each of ``mom``, ``dparam`` and ``bn``: ``_gap`` the worst leaf,
    ``_med`` the median leaf, ``_all`` the norms over all leaves, ``_cos``
    one minus the cosine over all leaves."""
    still = check.still_leaves(reference["mom"])
    out = {
        "loss_rel": check.rel(program["loss"], reference["loss"]),
        "gnorm_rel": check.rel(program["gnorm"], reference["gnorm"]),
        "step_count": float(abs(program["step"] - program.get("step0", 0)
                                - program["rows"])),
    }
    # The leaf next to the loss: its gradient passes through no
    # normalization layer on the way back, so it is the one gradient that
    # rounding in the activations does not scramble (PERF.md section 6).
    out["head_gap"], out["head_cos"] = check.whole(
        {head: program["mom"][head]}, {head: reference["mom"][head]})
    out["head_bias_cos"] = check.whole(
        {head_bias: program["mom"][head_bias]},
        {head_bias: reference["mom"][head_bias]})[1]
    compared = groups(program, reference)
    for name, (prog, ref) in compared.items():
        out.update(check.group_readings(
            name, prog, ref, skip=still if name == "dparam" else ()))
    prog, ref = compared["bn"]
    means = [k for k in ref if k.endswith("/mean")]
    out["bn_mean_cos"] = check.whole({k: prog[k] for k in means},
                                     {k: ref[k] for k in means})[1]
    return out


# ------------------------------------------------------------------- FLOPs
def valid_taps(in_size: int, k: int, stride: int) -> int:
    """Kernel taps that land on the image, summed over one axis's output
    positions: padding is (k-1)//2 before the image, as the model pads."""
    out = -(-in_size // stride)
    beg = (k - 1) // 2
    return sum(
        sum(1 for t in range(k) if 0 <= i * stride - beg + t < in_size)
        for i in range(out))


def conv_layers(arch: Dict) -> List[Tuple[int, int, int, int, int]]:
    """Every convolution and the dense layer as ``(in_size, stride, k,
    c_in, c_out)`` on square maps; the dense layer is a 1x1 on a 1x1 map."""
    size = arch["image_size"]
    layers = []
    bottleneck = arch["block"] == "bottleneck"
    if arch["stem"] == "imagenet":
        layers.append((size, 2, 7, 3, arch["stem_filters"]))
        size = -(-size // 2)
        size = -(-size // 2)  # 3x3/2 max-pool
    else:
        layers.append((size, 1, 3, 3, arch["stem_filters"]))
    c_in = arch["stem_filters"]
    for f, n, s in zip(arch["stage_filters"], arch["stage_blocks"],
                       arch["stage_strides"]):
        c_out = 4 * f if bottleneck else f
        for j in range(n):
            stride = s if j == 0 else 1
            out = -(-size // stride)
            if j == 0:  # projection shortcut, 1x1 at the block's stride
                layers.append((size, stride, 1, c_in, c_out))
            if bottleneck:
                layers.append((size, 1, 1, c_in, f))
                layers.append((size, stride, 3, f, f))
                layers.append((out, 1, 1, f, c_out))
            else:
                layers.append((size, stride, 3, c_in, f))
                layers.append((out, 1, 3, f, f))
            size, c_in = out, c_out
    layers.append((1, 1, 1, c_in, arch["num_classes"]))
    return layers


def forward_macs_per_image(arch: Dict) -> int:
    return sum(valid_taps(size, k, s) ** 2 * ci * co
               for size, s, k, ci, co in conv_layers(arch))


def train_flops_per_example(arch: Dict) -> int:
    """Forward + backward model FLOPs of one example, an image: 3 x 2 x
    MACs."""
    return 6 * forward_macs_per_image(arch)


def param_count(arch: Dict) -> int:
    """Trainable parameters: kernels, the dense bias and two BN leaves per
    BN site (one site before every conv of a block, one at the end)."""
    layers = conv_layers(arch)
    n = sum(k * k * ci * co for _, _, k, ci, co in layers)
    n += arch["num_classes"]  # dense bias
    bottleneck = arch["block"] == "bottleneck"
    c_in = arch["stem_filters"]
    for f, blocks in zip(arch["stage_filters"], arch["stage_blocks"]):
        c_out = 4 * f if bottleneck else f
        for _ in range(blocks):
            n += 2 * c_in + 2 * f + (2 * f if bottleneck else 0)
            c_in = c_out
    return n + 2 * c_in  # final BN
