"""Model FLOPs of one training step, from the configuration's shapes.

Counts what the forward and backward passes require and nothing else: each
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


def train_flops_per_image(arch: Dict) -> int:
    """Forward + backward model FLOPs of one image: 3 x 2 x MACs."""
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
