"""Attention of a token model as one fused TPU kernel: scores, masks,
softmax and values of a tile at a time in VMEM (online softmax), the key
blocks that the mask leaves empty never visited, and the row-wise log-sum
kept for a backward pass that computes the probabilities again on the
chip. Nothing of a score tensor goes through HBM, forward or backward.

One wrapper, two kinds of mask, both within documents (``doc`` ids a
position). A ``mask`` is an ``int``, the causal mask of a next-token model
(``0 <= i - j < window``, or every ``j <= i`` where the window is 0), or a
``BlockDiffusion(clean_len, block)``, the three-part mask of training by
diffusion over blocks: the sequence is a noised copy of ``clean_len``
positions and then the clean copy, a noisy block sees itself both ways and
the clean text strictly before it, the clean copy sees clean text up to
and including its own block (``BlockDiffusion.allows``).

The kernels are JAX's own ``splash_attention`` (``jax.experimental.pallas
.ops.tpu``), in its form for one key/value head shared by ``G`` query
heads, mapped over the key/value heads and the batch. What this module adds
is the model's contract (``models/transformer.py::blocked_attention``,
which stays the path of every other backend and shape and the reference of
the tests), the masks, the block sizes, and the choice between the two
paths as a pure function of what the code can see (``attention_path``:
backend, devices, head size, sequence length). There is
no start-up probe here (``ops/autotune.py``): the scan sends every score
through HBM several times and takes five times the kernel's time at the
shapes the kernel takes (257 against 46 ms a step of the benchmark's
Trinity cell, PERF.md section 5), so there is nothing for a timing to
decide, and a probe costs a compile of each side.

In a device trace the two ``pallas_call``s of a layer read
``splash_mqa_fwd_segmented_residuals`` (``..._no_residuals`` where no
gradient is taken) and ``splash_mqa_dkv_segmented_no_residuals`` (the
backward pass as one kernel, ``dq`` with it).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash


class BlockDiffusion(NamedTuple):
    """The mask of training by diffusion over blocks (Arriola et al. 2025,
    ``block_diff_mask``) over ``2 * clean_len`` positions: a noised copy,
    then the clean one; blocks of ``block`` positions counted from each
    copy's start."""
    clean_len: int
    block: int

    def allows(self, q, k):
        """Whether query position ``q`` sees key position ``k`` (arrays of
        NumPy or of JAX that broadcast; in the kernel, of a tile)."""
        length, block = self.clean_len, self.block
        noisy_q, noisy_k = q < length, k < length
        block_q = (q - (q >= length) * length) // block
        block_k = (k - (k >= length) * length) // block
        return ((noisy_q == noisy_k) & (block_q == block_k)   # own block
                | ~noisy_k & (block_k < block_q))         # clean text before


Mask = Union[int, BlockDiffusion]


class _BlockDiffusionMask(splash.splash_attention_mask._ComputableMask):
    """``BlockDiffusion`` as a mask the kernel computes from a tile's
    indices (no table of it goes through HBM)."""

    def __init__(self, mask: BlockDiffusion):
        self.mask = mask
        size = 2 * mask.clean_len
        super().__init__(shape=(size, size), mask_function=mask.allows)

    def __eq__(self, other):
        return isinstance(other, type(self)) and self.mask == other.mask

    def __hash__(self):
        return hash((type(self), self.mask))


def block_sizes(block_q: int, block_kv: int,
                block_kv_compute: int) -> splash.BlockSizes:
    """One tile of queries by keys for the forward kernel and the backward
    one alike (``block_kv_compute`` keys a product within a tile of
    ``block_kv`` fetched); the backward pass as one kernel (``dq`` with
    ``dk`` and ``dv``), which was 15-20% faster than two at every tile
    tried (PERF.md section 5)."""
    return splash.BlockSizes(
        block_q=block_q, block_kv=block_kv,
        block_kv_compute=block_kv_compute,
        block_q_dkv=block_q, block_kv_dkv=block_kv,
        block_kv_dkv_compute=block_kv_compute, use_fused_bwd_kernel=True)


# Fixed after a sweep on the chip at the cell's shapes (PERF.md section 5).
# A sequence that these do not divide takes the scan.
BLOCKS = block_sizes(1024, 1024, 512)


def attention_path(backend: str, devices: int, head_dim: int,
                   seq_len: int) -> str:
    """``kernel`` or ``scan``, from the backend's name, the number of its
    devices and the shapes alone: the kernel where Mosaic compiles it (a
    TPU, heads a multiple of the 128 lanes, a sequence its blocks divide)
    and the step is one device's program; the scan everywhere else. Over
    several devices the step is one auto-partitioned ``jit``, which refuses
    a Mosaic kernel ("cannot be automatically partitioned"): there the
    kernel waits for a ``shard_map`` over the batch (ROADMAP B-I 4)."""
    if (backend == "tpu" and devices == 1 and head_dim % 128 == 0
            and seq_len % max(BLOCKS.block_q, BLOCKS.block_kv) == 0):
        return "kernel"
    return "scan"


@functools.lru_cache(maxsize=None)
def _kernel(seq_len: int, mask: Mask, group: int,
            blocks: splash.BlockSizes, interpret: bool):
    """The kernel of one key/value head and its ``group`` query heads over
    ``seq_len`` positions under ``mask``, built once for each such shape:
    the mask tables are made on the host, and every layer of a kind and
    both copies of the step in a chunk program share them."""
    shape = (seq_len, seq_len)
    if isinstance(mask, BlockDiffusion):
        if seq_len != 2 * mask.clean_len:
            raise ValueError(f"{mask} is over {2 * mask.clean_len} "
                             f"positions, not {seq_len}")
        one = _BlockDiffusionMask(mask)
    else:
        # the model's mask is 0 <= i - j < window
        one = (splash.LocalMask(shape, (mask - 1, 0), 0) if mask
               else splash.CausalMask(shape))
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * group),
            block_sizes=blocks, interpret=interpret)


def fused_attention(q, k, v, doc, mask: Mask, dtype, *,
                    blocks: Optional[splash.BlockSizes] = None,
                    interpret: Optional[bool] = None):
    """``blocked_attention``'s contract without its block: attention
    within documents under ``mask`` (the module docstring). ``q`` is ``(B,
    S, KV, G, D)``, ``k`` and ``v`` ``(B, S, KV, D)``, ``doc`` ``(B, S)``;
    returns ``(B, S, KV, G, D)`` in ``dtype``.

    ``1 / sqrt(D)`` goes into ``q`` before it is cast, so ``q`` is rounded
    once; both products take ``dtype`` operands and accumulate in float32;
    maximum, exponentials and sum are float32. ``blocks`` and ``interpret``
    are for the tests: the first defaults to ``BLOCKS``, the second to any
    backend but a TPU."""
    b, s, kv, g, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = _kernel(s, mask, g, blocks or BLOCKS, interpret)
    q = (q * (1.0 / math.sqrt(d))).astype(dtype)
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))   # key/value heads
    out = jax.vmap(per_head)(                               # the batch
        jnp.transpose(q, (0, 2, 3, 1, 4)),                  # (B, KV, G, S, D)
        jnp.transpose(k.astype(dtype), (0, 2, 1, 3)),       # (B, KV, S, D)
        jnp.transpose(v.astype(dtype), (0, 2, 1, 3)),
        splash.SegmentIds(doc, doc))
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def key_blocks(seq_len: int, mask: Mask, group: int) -> Tuple[int, int]:
    """``(visited, total)``: the tiles of queries by keys the kernel steps
    through at ``seq_len`` under ``mask`` of all there are, read from its
    own forward mask table (one query head's; all heads share the mask)."""
    table = np.asarray(_kernel(seq_len, mask, group, BLOCKS, False
                               ).fwd_mask_info.block_mask)
    return (int(np.count_nonzero(table[0])),
            (seq_len // BLOCKS.block_q) * (seq_len // BLOCKS.block_kv))
