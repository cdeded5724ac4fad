"""The kind of data set ``jpeg_tfrecord``: photo-like JPEGs (smooth
structure plus mild noise, about 10:1 like real photographs) as
Inception-style ``tf.train.Example`` records in TFRecord shards named
``train-XXXXX-of-NNNNN``: the format the ImageNet trainer reads. It follows
``tools/input_edge.py::make_shards`` and
``tpu_resnet.data.engine.synthetic_photo_jpeg``, with each image drawn from
``(seed, index)``, so that a pool of processes makes them in any order, and with
1/f random fields where the original has one sine wave (see ``photo_jpeg``).

``generate`` returns the directory the program's ``data.data_dir`` points
at.
"""

from __future__ import annotations

import io
import os
import struct
import multiprocessing
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List

import numpy as np


# ------------------------------------------------------------- TFRecord
def _crc32c(data: bytes) -> int:
    try:
        import google_crc32c
        return google_crc32c.value(data)
    except ImportError:  # table-driven fallback, slow but exact
        crc = 0xFFFFFFFF
        for b in data:
            crc = (crc >> 8) ^ _CRC_TABLE[(crc ^ b) & 0xFF]
        return crc ^ 0xFFFFFFFF


def _make_crc_table() -> List[int]:
    table = []
    for i in range(256):
        crc = i
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
        table.append(crc)
    return table


_CRC_TABLE = _make_crc_table()


def _masked_crc(data: bytes) -> int:
    crc = _crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    """A length-delimited protobuf field."""
    return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def encode_example(jpeg: bytes, label: int) -> bytes:
    """``tf.train.Example{image/encoded: bytes, image/class/label: int64}``."""
    def entry(key: str, feature: bytes) -> bytes:
        return _field(1, _field(1, key.encode()) + _field(2, feature))

    bytes_feature = _field(1, _field(1, jpeg))
    int_feature = _field(3, _varint((1 << 3) | 0) + _varint(label))
    return _field(1, entry("image/encoded", bytes_feature)
                  + entry("image/class/label", int_feature))


def write_tfrecord(path: str, records: List[bytes]) -> None:
    with open(path, "wb") as f:
        for rec in records:
            length = struct.pack("<Q", len(rec))
            f.write(length)
            f.write(struct.pack("<I", _masked_crc(length)))
            f.write(rec)
            f.write(struct.pack("<I", _masked_crc(rec)))


# ---------------------------------------------------------------- images
def photo_jpeg(size, quality: int, rng: np.random.Generator) -> bytes:
    """A photo-like JPEG of ``size`` = (width, height): random fields with
    the f**-1.2 amplitude spectrum of natural images (one for luminance, a
    weaker one a colour channel) plus mild sensor noise. It compresses
    about 6:1, as ImageNet's photographs do (110 KB at 400x350 on
    average; uniform noise would be the 1.5:1 worst case and hide every
    decode-path win). The program's own generator
    draws one sine wave an image; on batches of such rank-one patterns
    the first training steps are so badly conditioned that two float32
    implementations part by tenths (PERF.md)."""
    from PIL import Image

    w, h = size
    fy = np.fft.fftfreq(h)[:, None]
    fx = np.fft.rfftfreq(w)[None, :]
    amp = (np.maximum(np.hypot(fx, fy), 1.0 / max(w, h)) ** -1.2).astype(
        np.float32)

    def field():
        phase = rng.random(amp.shape, dtype=np.float32) * np.float32(
            2 * np.pi)
        spec = (amp * np.cos(phase)) + 1j * (amp * np.sin(phase))
        f = np.fft.irfft2(spec.astype(np.complex64), s=(h, w))
        return (f - f.mean()) / f.std()

    lum = field()
    img = np.stack([lum + 0.3 * field() for _ in range(3)], -1)
    img = img * 48.0 + 120.0 + rng.integers(0, 8, (h, w, 3))
    buf = io.BytesIO()
    Image.fromarray(img.clip(0, 255).astype(np.uint8)).save(
        buf, "JPEG", quality=quality)
    return buf.getvalue()


def _record(args) -> bytes:
    """Record ``index`` of the data set of ``seed`` (a pool's task)."""
    seed, index, size, quality, lo, hi = args
    rng = np.random.default_rng((seed, index))
    label = int(rng.integers(lo, hi + 1))
    return encode_example(photo_jpeg(size, quality, rng), label)


def generate(out_dir: str, params: Dict, seed: int) -> str:
    n_shards, per_shard = params["shards"], params["per_shard"]
    lo, hi = params["label_range"]
    # Every seed decodes the same multiset of sizes (decode cost follows
    # the pixel count), in an order of its own.
    n, sizes = n_shards * per_shard, params["sizes"]
    order = np.random.default_rng((seed, n)).permutation(n)
    tasks = [(seed, i, sizes[int(order[i]) % len(sizes)],
              params["quality"], lo, hi) for i in range(n)]
    workers = min(8, os.cpu_count() or 1)
    if len(tasks) < 128 or workers < 2:
        records = [_record(t) for t in tasks]
    else:
        # Processes, not threads: the fields are numpy work under the
        # interpreter lock. Spawned workers import numpy and PIL only and
        # never touch the chip; the pool is joined before this returns.
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as ex:
            records = list(ex.map(_record, tasks, chunksize=16))
    for s in range(n_shards):
        write_tfrecord(
            os.path.join(out_dir, f"train-{s:05d}-of-{n_shards:05d}"),
            records[s * per_shard:(s + 1) * per_shard])
    return out_dir
