"""The kind of data set ``packed_tokens``: one file of token ids as a
pre-training job packs them, ``train.tokens`` (little-endian int32).
Documents of log-normal length (``median``, ``sigma``, clipped to
``min_len .. max_len``; each begins with id 0, its other ids uniform in
``1 .. vocab - 1``) are concatenated until ``sequences * seq_len + 1`` ids
are there, and cut there: the program cuts the file into consecutive
sequences of ``seq_len``, so a document runs across a sequence's end as
packed data does, and the one id more is the last sequence's last label.
``generate`` returns the directory the program's ``data.data_dir`` points
at.
"""

from __future__ import annotations

import os
from typing import Dict

import numpy as np


def generate(out_dir: str, params: Dict, seed: int) -> str:
    total = params["sequences"] * params["seq_len"] + 1
    rng = np.random.default_rng((seed, 1))
    ids = rng.integers(1, params["vocab"], total, dtype=np.int32)
    # more documents than can be needed, then as many as fill the file
    lengths = np.clip(
        np.rint(rng.lognormal(np.log(params["median"]), params["sigma"],
                              total // params["min_len"] + 1)),
        params["min_len"], params["max_len"]).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    ids[starts[starts < total]] = 0
    os.makedirs(out_dir, exist_ok=True)
    ids.astype("<i4").tofile(os.path.join(out_dir, "train.tokens"))
    return out_dir
