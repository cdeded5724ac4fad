"""Token data: one file of ids, cut into consecutive sequences.

``<data_dir>/train.tokens`` holds little-endian int32 ids and nothing
else: documents concatenated, each begun by id 0 (so the document of a
position is ``cumsum(ids == 0)``, which the model derives itself), every id
in ``[0, data.vocab_size)``. The first ``N * S + 1`` ids make ``N``
sequences of ``S = data.seq_len``: sequence ``i`` is ``ids[i*S : (i+1)*S]``
and its labels are the next ids, ``ids[i*S + 1 : (i+1)*S + 1]`` (the last
label of a sequence is the first id of the one that follows it in the
file). A document cut by a sequence's end goes on in the next sequence as
a document of its own.

The split is small beside HBM and lives there whole
(``device_data.DeviceDataset``); the host engine does not carry token data
(ROADMAP B-I).
"""

from __future__ import annotations

import os
from typing import Tuple

import numpy as np

FILE = "train.tokens"


def write_tokens(data_dir: str, ids: np.ndarray) -> str:
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(data_dir, FILE)
    np.asarray(ids, "<i4").tofile(path)
    return path


def load_tokens(data_cfg) -> Tuple[np.ndarray, np.ndarray]:
    """``(inputs, labels)``, both ``(N, seq_len)`` int32."""
    s = data_cfg.seq_len
    if s < 1 or data_cfg.vocab_size < 2:
        raise ValueError(f"dataset 'tokens' needs data.seq_len >= 1 and "
                         f"data.vocab_size >= 2, got {s} and "
                         f"{data_cfg.vocab_size}")
    path = os.path.join(data_cfg.data_dir, FILE)
    ids = np.fromfile(path, "<i4")
    n = (len(ids) - 1) // s
    if n < 1:
        raise ValueError(f"{path} holds {len(ids)} ids, under one sequence "
                         f"of {s} and its last label")
    if ids.min() < 0 or ids.max() >= data_cfg.vocab_size:
        raise ValueError(f"{path} holds ids in [{ids.min()}, {ids.max()}], "
                         f"outside [0, {data_cfg.vocab_size})")
    return (ids[:n * s].reshape(n, s).astype(np.int32),
            ids[1:n * s + 1].reshape(n, s).astype(np.int32))
