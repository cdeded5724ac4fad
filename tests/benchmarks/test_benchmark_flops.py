"""The ResNet family's FLOPs function against XLA's count of the compiled
step: on the CPU at a small size, and against the two counts ISSUE 25
tabulated for the described v5e (24.2 and 28.7 GFLOP an image)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.families import resnet_v2 as flops
from benchmarks.lib.manifest import BENCH_DIR
from benchmarks.reference import resnet_v2 as ref


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,xla_gflop_per_image", [
    ("imagenet_rn50", 6.187e12 / 256 / 1e9),
    ("wrn28_10_cifar100", 2.939e13 / 1024 / 1e9),
])
def test_against_the_issues_table(name, xla_gflop_per_image):
    arch = config(name)["model"]
    mine = flops.train_flops_per_example(arch) / 1e9
    assert abs(mine - xla_gflop_per_image) / xla_gflop_per_image < 0.03


@pytest.mark.parametrize("name", ["imagenet_rn50", "wrn28_10_cifar100"])
def test_parameter_count_is_the_published_one(name):
    arch = config(name)["model"]
    assert flops.param_count(arch) == arch["parameters"]


def test_nominal_macs_of_resnet50():
    """4.09 G multiply-adds is the figure the literature gives for
    ResNet-50 at 224x224, padding taps included."""
    arch = config("imagenet_rn50")["model"]
    nominal = sum((-(-size // s)) ** 2 * k * k * ci * co
                  for size, s, k, ci, co in flops.conv_layers(arch))
    assert abs(nominal / 1e9 - 4.09) < 0.01
    assert flops.forward_macs_per_image(arch) < nominal


@pytest.mark.parametrize("arch", [
    {"stem": "cifar", "stem_filters": 16, "block": "basic",
     "stage_filters": [16, 32, 64], "stage_blocks": [1, 1, 1],
     "stage_strides": [1, 2, 2], "num_classes": 10, "image_size": 32},
    {"stem": "imagenet", "stem_filters": 64, "block": "bottleneck",
     "stage_filters": [16, 32], "stage_blocks": [1, 2],
     "stage_strides": [1, 2], "num_classes": 10, "image_size": 64},
], ids=["basic", "bottleneck"])
def test_against_xla_cost_analysis_on_the_cpu(arch):
    """XLA counts a convolution's multiply-adds on the image only, and
    adds the elementwise work: the model count lies a little under it.
    (The forward pass: the reference's backward recomputes each block.)"""
    arch = dict(arch, bn_momentum=0.997, bn_epsilon=1e-5)
    batch = 4
    size = arch["image_size"]
    layers = flops.conv_layers(arch)
    params, stats = {}, {}
    names = _leaf_names(arch)
    for (name, kind), shape in zip(names, _leaf_shapes(arch, layers)):
        (params if kind == "p" else stats)[name] = jnp.ones(shape) * 0.01
    images = jnp.zeros((batch, size, size, 3))
    fwd = jax.jit(lambda p: ref.forward(p, stats, images, arch)[0])
    xla = fwd.lower(params).compile().cost_analysis()["flops"] / batch
    mine = 2 * flops.forward_macs_per_image(arch)
    # XLA adds BN, ReLU, pooling and the means; the model count is the
    # convolutions and the dense layer alone.
    assert 0.85 < mine / xla <= 1.0, (mine, xla)
    assert flops.train_flops_per_example(arch) == 3 * mine


def _leaf_names(arch):
    """(name, 'p'|'s') of every leaf of the reference's flat layout."""
    out = [("initial_conv/conv/kernel", "p")]
    bottleneck = arch["block"] == "bottleneck"
    for i, n in enumerate(arch["stage_blocks"]):
        for j in range(n):
            b = f"block_layer{i + 1}/block{j}"
            sites = ["preact", "bnrelu1"] + (["bnrelu2"] if bottleneck
                                              else [])
            convs = (["proj"] if j == 0 else []) + ["conv1", "conv2"] + (
                ["conv3"] if bottleneck else [])
            for c in convs:
                out.append((f"{b}/{c}/conv/kernel", "p"))
            for s in sites:
                out += [(f"{b}/{s}/bn/scale", "p"), (f"{b}/{s}/bn/bias", "p"),
                        (f"{b}/{s}/bn/mean", "s"), (f"{b}/{s}/bn/var", "s")]
    out += [("final_bnrelu/bn/scale", "p"), ("final_bnrelu/bn/bias", "p"),
            ("final_bnrelu/bn/mean", "s"), ("final_bnrelu/bn/var", "s"),
            ("final_dense/kernel", "p"), ("final_dense/bias", "p")]
    return out


def _leaf_shapes(arch, layers):
    """Shapes in the order of ``_leaf_names``."""
    it = iter(layers)
    _, _, k, ci, co = next(it)
    shapes = [(k, k, ci, co)]
    bottleneck = arch["block"] == "bottleneck"
    c_in = arch["stem_filters"]
    for f, n in zip(arch["stage_filters"], arch["stage_blocks"]):
        c_out = 4 * f if bottleneck else f
        for j in range(n):
            n_convs = (1 if j == 0 else 0) + (3 if bottleneck else 2)
            for _ in range(n_convs):
                _, _, k, ci, co = next(it)
                shapes.append((k, k, ci, co))
            widths = [c_in, f] + ([f] if bottleneck else [])
            for w in widths:
                shapes += [(w,)] * 4
            c_in = c_out
    shapes += [(c_in,)] * 4
    _, _, _, ci, co = next(it)
    shapes += [(ci, co), (co,)]
    return shapes
