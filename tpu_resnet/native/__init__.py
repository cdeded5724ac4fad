"""Native (C++) data-plane bindings via ctypes.

Build once with ``python -m tpu_resnet.native.build`` (or let the launchers
do it); every consumer falls back to the pure-numpy path when the shared
library is absent, so the framework never *requires* a compiler at runtime.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional

import numpy as np

_SO_PATH = os.path.join(os.path.dirname(__file__), "libtpuresnet_loader.so")
_lib: Optional[ctypes.CDLL] = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        if not os.path.exists(_SO_PATH):
            raise ImportError(f"native loader not built ({_SO_PATH} missing); "
                              "run: python -m tpu_resnet.native.build")
        lib = ctypes.CDLL(_SO_PATH)
        lib.tr_crc32c.restype = ctypes.c_uint32
        lib.tr_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.tr_file_size.restype = ctypes.c_int64
        lib.tr_file_size.argtypes = [ctypes.c_char_p]
        lib.tr_read_file.restype = ctypes.c_int64
        lib.tr_read_file.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                     ctypes.c_int64]
        lib.tr_read_files_concat.restype = ctypes.c_int64
        lib.tr_read_files_concat.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
        lib.tr_tfrecord_split.restype = ctypes.c_int64
        lib.tr_tfrecord_split.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int32]
        if hasattr(lib, "tr_has_jpeg"):  # absent in pre-JPEG .so builds —
            lib.tr_has_jpeg.restype = ctypes.c_int32  # optional by design
            lib.tr_has_jpeg.argtypes = []
            lib.tr_decode_jpeg_vgg.restype = ctypes.c_int32
            lib.tr_decode_jpeg_vgg.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_float, ctypes.c_float,
                ctypes.c_void_p]
        _lib = lib
    return _lib


def available() -> bool:
    if os.path.exists(_SO_PATH):
        return True
    return _autobuild()


_AUTOBUILD_TRIED = False


def _autobuild() -> bool:
    """One-shot lazy build of the shared library (the binary is a build
    artifact, never vendored in git). Opt out with
    ``TPU_RESNET_NATIVE_AUTOBUILD=0``; failures fall back to numpy."""
    global _AUTOBUILD_TRIED
    if _AUTOBUILD_TRIED or os.environ.get(
            "TPU_RESNET_NATIVE_AUTOBUILD", "1") == "0":
        return os.path.exists(_SO_PATH)
    _AUTOBUILD_TRIED = True
    try:
        from tpu_resnet.native.build import build
        build()
    except Exception:
        return False
    return os.path.exists(_SO_PATH)


def jpeg_available() -> bool:
    """True when the shared library was built with libjpeg."""
    try:
        lib = _load() if available() else None
        return lib is not None and hasattr(lib, "tr_has_jpeg") and \
            bool(lib.tr_has_jpeg())
    except ImportError:
        return False


def decode_path() -> str:
    """Which JPEG decoder ``data.use_native_loader`` gets on this machine:
    ``turbo`` (libjpeg-turbo partial decode), ``libjpeg`` (plain libjpeg)
    or ``pil`` (no compiler or no libjpeg: the pure-Python fallback). The
    build ladder falls down these rungs without a word, so anything that
    reports a decode rate should say which rung it stood on."""
    try:
        lib = _load() if available() else None
    except ImportError:
        lib = None
    level = lib.tr_has_jpeg() if lib is not None \
        and hasattr(lib, "tr_has_jpeg") else 0
    return {2: "turbo", 1: "libjpeg"}.get(level, "pil")


class loader:
    """Namespace matching the import sites (`from tpu_resnet.native import
    loader`)."""

    @staticmethod
    def crc32c(data: bytes) -> int:
        lib = _load()
        buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
        return lib.tr_crc32c(buf, len(data))

    @staticmethod
    def read_fixed_length_records(files: List[str],
                                  record_bytes: int) -> np.ndarray:
        """Concurrent whole-file reads → uint8 [N, record_bytes]
        (FixedLengthRecordReader role, reference cifar_input.py:58)."""
        lib = _load()
        sizes = [os.path.getsize(f) for f in files]
        for f, s in zip(files, sizes):
            if s % record_bytes:
                raise ValueError(f"{f}: size {s} not a multiple of "
                                 f"{record_bytes}")
        total = sum(sizes)
        out = np.empty(total, np.uint8)
        c_paths = (ctypes.c_char_p * len(files))(
            *[f.encode() for f in files])
        c_sizes = (ctypes.c_int64 * len(files))(*sizes)
        rc = lib.tr_read_files_concat(
            c_paths, c_sizes, len(files),
            out.ctypes.data_as(ctypes.c_void_p),
            min(8, len(files)))
        if rc != 0:
            raise IOError(f"native read failed for {files[-int(rc) - 1]}")
        return out.reshape(-1, record_bytes)

    @staticmethod
    def tfrecord_payloads(path: str, verify_crc: bool = False):
        """All record payloads of a TFRecord file as bytes
        (TFRecordDataset role): one bulk GIL-released file read, one C
        framing/CRC pass, then exactly one copy per payload."""
        lib = _load()
        size = os.path.getsize(path)
        buf = np.empty(size, np.uint8)
        got = lib.tr_read_file(path.encode(),
                               buf.ctypes.data_as(ctypes.c_void_p), size)
        if got != size:
            raise IOError(f"short read on {path}")
        max_records = max(16, size // 16)  # min framed record = 16 bytes
        spans = np.empty(2 * max_records, np.int64)
        n = lib.tr_tfrecord_split(
            buf.ctypes.data_as(ctypes.c_void_p), size,
            spans.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_records, 1 if verify_crc else 0)
        if n == -1:
            raise ValueError(f"{path}: corrupt TFRecord framing")
        if n == -2:
            raise ValueError(f"{path}: CRC mismatch")
        if n < 0:
            raise ValueError(f"{path}: split failed ({n})")
        sp = spans[:2 * int(n)].tolist()
        mv = memoryview(buf)
        return [bytes(mv[sp[2 * i]:sp[2 * i] + sp[2 * i + 1]])
                for i in range(int(n))]

    @staticmethod
    def decode_jpeg_vgg(jpeg: bytes, resize_side: int, crop: int,
                        fx: float = -1.0, fy: float = -1.0
                        ) -> Optional[np.ndarray]:
        """JPEG → uint8 [crop, crop, 3]: aspect-preserving resize (shorter
        side = resize_side) + crop. fx/fy in [0,1) pick uniformly among
        the valid offsets; negative (default) = floor-central crop. GIL
        released during decode — worker threads scale across cores.
        Returns None for images this decoder does not handle (caller
        falls back to PIL)."""
        lib = _load()
        out = np.empty((crop, crop, 3), np.uint8)
        rc = lib.tr_decode_jpeg_vgg(
            jpeg, len(jpeg), resize_side, crop,
            ctypes.c_float(fx), ctypes.c_float(fy),
            out.ctypes.data_as(ctypes.c_void_p))
        return out if rc == 0 else None
