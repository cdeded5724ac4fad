"""The kind of data set ``cifar_bin``: a CIFAR-100-format ``train.bin``
(coarse label, fine label, 3072 bytes depth-major) of uniform random pixels
and labels. ``generate`` returns the directory the program's
``data.data_dir`` points at.
"""

from __future__ import annotations

import os
from typing import Dict

import numpy as np


def generate(out_dir: str, params: Dict, seed: int) -> str:
    n, classes = params["examples"], params["classes"]
    rng = np.random.default_rng((seed, 0))
    raw = np.empty((n, 2 + 3072), np.uint8)
    raw[:, 2:] = rng.integers(0, 256, (n, 3072), dtype=np.uint8)
    fine = rng.integers(0, classes, n)
    raw[:, 1] = fine
    raw[:, 0] = fine // max(1, classes // 20)  # coarse label, unread
    d = os.path.join(out_dir, "cifar-100-binary")
    os.makedirs(d, exist_ok=True)
    raw.tofile(os.path.join(d, "train.bin"))
    return out_dir
