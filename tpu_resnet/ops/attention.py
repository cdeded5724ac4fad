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
backend, devices, head size, sequence length). Heads of 128 columns and
heads of 64 both take the kernel as it is: JAX's kernel lays a head under
the 128 lanes out itself, and on the chip that ran 1.3% faster than the
same head padded with zero columns to 128 (one layer at 2 x 4,096 x 8 x 4
x 64: 9.55 against 9.68 ms forward and backward, the scan 53.2; PERF.md
section 5 at PR 36), so nothing is padded. There is
no start-up probe here (``ops/autotune.py``): the scan sends every score
through HBM several times and takes five times the kernel's time at the
shapes the kernel takes (257 against 46 ms a step of the benchmark's
Trinity cell, PERF.md section 5), so there is nothing for a timing to
decide, and a probe costs a compile of each side.

The two kinds of mask take separate paths through ``fused_attention``,
keyed on the mask's type. An ``int`` mask is one call of the kernel over
the ``S x S`` square through JAX's own ``custom_vjp``. ``BlockDiffusion``
is computed as the two parts the mask has (``_two_parts``, PERF.md
section 5 at PR 35), under one ``custom_vjp`` of this module:

1. every query against the **clean keys only**, through the kernel. There
   the three-part mask is one comparison of the key's position with a
   bound that depends on the query's row alone (``clean_bounds``: the
   clean text strictly before a noisy row's block, up to and including a
   clean row's own), which the kernel is handed where a causal mask hands
   it the row's index. It visits 20 of 32 tiles at 4,096 clean ids; the
   whole square under ``allows`` was 24 of 64, each paying two floor
   divisions and a dozen more integer operations a score;
2. the **noisy diagonal**, each noisy block against its own ``block`` noisy
   keys: ``L x block`` entries a head, 0.1% of the mask's live entries,
   which cost a sixth of the kernel's time while they were 4 of its 24
   tiles. A small Pallas kernel of this module beside JAX's
   (``blockdiff_diagonal_fwd``): tiles of 128 rows of a group's heads
   against the 128 keys of the same positions;
3. the **join**, in that same kernel: a noisy row's output is the two
   parts' outputs weighted by ``exp(lse_part - lse)`` with ``lse =
   logaddexp(lse_clean, lse_diag)``, in float32, written over the clean-key
   kernel's output in place and so rounded to ``dtype`` once more (that
   kernel's part comes in ``dtype``); a clean row's is the kernel's. A
   noisy row of a document's first block sees no clean key: the kernel's
   log-sum there is near its mask value, the weight is 0 and nothing is
   NaN.

Backward, the residuals are the inputs, the joined output and the joined
log-sum. JAX's fused backward kernel (``_splash_attention_bwd_dkv``)
computes ``exp(s - lse)`` again, which with the joined log-sum is each
entry's probability among all the keys its row sees, so it gives ``dq``
and the clean keys' ``dk``, ``dv`` unchanged; the diagonal's backward
kernel (``blockdiff_diagonal_bwd``) adds its ``dq`` to that in place and
gives the noisy keys' ``dk``, ``dv`` from the same ``lse`` and ``di``.

In a device trace the two ``pallas_call``s of a layer read
``splash_mqa_fwd_segmented_residuals`` (``..._no_residuals`` under an
``int`` mask where no gradient is taken) and
``splash_mqa_dkv_segmented_no_residuals`` (the backward pass as one
kernel, ``dq`` with it); under ``BlockDiffusion`` the two small kernels
read ``vmap_vmap_blockdiff_diagonal_fwd`` and ``..._bwd`` under the scope
``diagonal`` (``join`` holds what is left of it outside them: the noisy
and the clean keys' ``dk`` and ``dv`` laid end to end).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


def clean_bounds(mask: BlockDiffusion) -> np.ndarray:
    """For each of the ``2 * clean_len`` query rows, the clean keys it sees
    are those before this bound (counted from the clean copy's start):
    the clean text strictly before a noisy row's block, up to and including
    a clean row's own."""
    first = (np.arange(mask.clean_len, dtype=np.int32)
             // mask.block * mask.block)
    return np.concatenate([first, first + mask.block])


def _before(bound, k):
    return k < bound


class _CleanKeysMask(splash.splash_attention_mask._ComputableMask):
    """What ``BlockDiffusion`` leaves of the clean keys, ``(2 * clean_len,
    clean_len)``, as a mask the kernel computes from a tile's indices: one
    comparison a score. The kernel hands ``mask_function`` the mask's
    ``q_sequence`` at the tile's rows where a causal mask holds the rows'
    indices; here it holds ``clean_bounds``, and the mask tables are made
    from the same pair (``splash_attention_mask_info``), so tables and
    kernel agree by construction."""

    def __init__(self, mask: BlockDiffusion):
        self.mask = mask
        super().__init__(shape=(2 * mask.clean_len, mask.clean_len),
                         mask_function=_before)
        self.q_sequence = clean_bounds(mask)

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
# Heads wider than the 128 lanes: the fused backward's tile of 1,024
# queries needs 17.5 MB of VMEM at heads of 256, past the 16 MB Mosaic
# allows a kernel; half the queries a backward tile, the forward's as above.
WIDE_BLOCKS = dataclasses.replace(BLOCKS, block_q_dkv=512)


def blocks_for(head_dim: int) -> splash.BlockSizes:
    """The kernel's tiles at heads of ``head_dim``."""
    return BLOCKS if head_dim <= 128 else WIDE_BLOCKS


def attention_path(backend: str, devices: int, head_dim: int,
                   seq_len: int) -> str:
    """``kernel`` or ``scan``, from the backend's name, the number of its
    devices and the shapes alone: the kernel where Mosaic compiles it (a
    TPU, heads a multiple of the 128 lanes or of 64, half of them, the
    two sizes the kernel was run at on the chip; a sequence its blocks
    divide) and the step is one device's program; the scan everywhere
    else. Over
    several devices the step is one auto-partitioned ``jit``, which refuses
    a Mosaic kernel ("cannot be automatically partitioned"): there the
    kernel waits for a ``shard_map`` over the batch (ROADMAP B-I 4)."""
    if (backend == "tpu" and devices == 1
            and (head_dim % 128 == 0 or head_dim == 64)
            and seq_len % max(BLOCKS.block_q, BLOCKS.block_kv) == 0):
        return "kernel"
    return "scan"


@functools.lru_cache(maxsize=None)
def _kernel(seq_len: int, mask: Mask, group: int,
            blocks: splash.BlockSizes, interpret: bool):
    """The kernel of one key/value head and its ``group`` query heads over
    ``seq_len`` positions under ``mask``, built once for each such shape:
    the mask tables are made on the host, and every layer of a kind and
    both copies of the step in a chunk program share them. Under
    ``BlockDiffusion`` it is the kernel of the clean keys alone (every
    query against ``clean_len`` keys), and its forward pass returns each
    row's log-sum beside the output (``_two_parts``)."""
    diffusion = isinstance(mask, BlockDiffusion)
    if diffusion:
        if seq_len != 2 * mask.clean_len:
            raise ValueError(f"{mask} is over {2 * mask.clean_len} "
                             f"positions, not {seq_len}")
        one = _CleanKeysMask(mask)
    else:
        shape = (seq_len, seq_len)
        # the model's mask is 0 <= i - j < window
        one = (splash.LocalMask(shape, (mask - 1, 0), 0) if mask
               else splash.CausalMask(shape))
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * group), block_sizes=blocks,
            save_residuals=diffusion, interpret=interpret)


def _heads_first(q, k, v, dtype):
    """``q`` scaled by ``1 / sqrt(D)`` and cast, as ``(B, KV, G, S, D)``;
    ``k`` and ``v`` cast, as ``(B, KV, S, D)``."""
    q = (q * (1.0 / math.sqrt(q.shape[-1]))).astype(dtype)
    return (jnp.transpose(q, (0, 2, 3, 1, 4)),
            jnp.transpose(k.astype(dtype), (0, 2, 1, 3)),
            jnp.transpose(v.astype(dtype), (0, 2, 1, 3)))


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
    are for the tests: the first defaults to ``blocks_for`` the heads, the
    second to any backend but a TPU."""
    q, k, v = _heads_first(q, k, v, dtype)
    out = heads_first_attention(q, k, v, doc, mask, blocks=blocks,
                                interpret=interpret)
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def heads_first_attention(q, k, v, doc, mask: Mask, *,
                          blocks: Optional[splash.BlockSizes] = None,
                          interpret: Optional[bool] = None):
    """``fused_attention`` on inputs already in the kernel's layout and
    dtype: ``q`` ``(B, KV, G, S, D)`` scaled by ``1 / sqrt(D)``, ``k`` and
    ``v`` ``(B, KV, S, D)``; returns ``(B, KV, G, S, D)``
    (``ops/attention_inputs.py`` writes that layout in the pass that
    prepares them)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blocks = blocks or blocks_for(q.shape[-1])
    if isinstance(mask, BlockDiffusion):
        return _two_parts(q, k, v, doc, mask, blocks, interpret)
    kernel = _kernel(q.shape[3], mask, q.shape[2], blocks, interpret)
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))   # over KV
    return jax.vmap(per_head)(q, k, v, splash.SegmentIds(doc, doc))


# ------------------------------------------ block diffusion, in two parts
# All below is heads first: ``q`` and the output ``(B, KV, G, S, D)``, ``k``
# and ``v`` ``(B, KV, S, D)``, a row's log-sum ``(B, KV, G, S)``, ``doc``
# ``(B, S)``; the noisy copy is the first half of ``S``, the clean one the
# second.
_MASKED = splash.splash_attention_kernel.DEFAULT_MASK_VALUE


def _clean_part(kernel, q, k, v, doc):
    """Every query against the clean keys, through the fused kernel: the
    output and each row's log-sum."""
    length = k.shape[2] // 2
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))   # key/value heads
    out, (lse,) = jax.vmap(per_head)(                       # the batch
        q, k[:, :, length:], v[:, :, length:],
        splash.SegmentIds(doc, doc[:, length:]))
    return out, lse


def _clean_part_bwd(kernel, q, k, v, doc, lse, d_out, di):
    """``dq`` of every query and ``dk``, ``dv`` of the clean keys by JAX's
    own fused backward kernel, from the joined ``lse`` and ``di``: the
    kernel computes ``exp(s - lse)`` again, which with the log-sum over
    both parts is each entry's probability among all the keys its row
    sees."""
    length = k.shape[2] // 2
    blocks = kernel.kwargs["block_sizes"]

    def one(q, k, v, q_doc, kv_doc, lse, d_out, di):
        return splash.splash_attention_kernel._splash_attention_bwd_dkv(
            q, k, v, splash.SegmentIds(q_doc, kv_doc), None, lse, d_out, di,
            bq=blocks.block_q_dkv, bkv=blocks.block_kv_dkv,
            bkv_compute=blocks.block_kv_dkv_compute, is_mqa=True,
            mask_info=kernel.dkv_mask_info,
            mask_value=kernel.kwargs["mask_value"],
            attn_logits_soft_cap=None, use_fused_bwd_kernel=True,
            q_layout=blocks.q_layout, k_layout=blocks.k_layout,
            v_layout=blocks.v_layout,
            mask_function=kernel.kwargs["mask_function"],
            interpret=kernel.kwargs["interpret"])

    per_head = jax.vmap(one, in_axes=(0, 0, 0, None, None, 0, 0, 0))
    return jax.vmap(per_head)(q, k[:, :, length:], v[:, :, length:], doc,
                              doc[:, length:], lse, d_out, di)


# The diagonal and the join are one small kernel a pass: a tile of ``T``
# noisy rows of all ``G`` heads against the ``T`` noisy keys of the same
# positions (``T`` the least multiple of the 128 lanes that whole blocks
# divide), ``G`` products of ``T x T`` of which the blocks on the diagonal
# are live. A position's ``ident`` is its document and its block in one
# number, so "same document, same block" is one comparison; the kernel
# takes it along the rows and along the columns as JAX's kernel takes its
# segment ids. Nothing of it goes through HBM but what the kernel over the
# clean keys wrote: the forward pass joins into that kernel's output and
# log-sum in place, the backward pass adds into its ``dq`` in place.
_LANES, _SUBLANES = 128, 8
_NT = (((1,), (1,)), ((), ()))       # a @ b.T


def _diagonal_tile(block: int) -> int:
    return math.lcm(_LANES, block)


def _idents(doc, block: int):
    """``doc`` of the noisy copy ``(L,)`` as the diagonal's ``ident`` (what
    has to be equal within a tile: the document and the block's place in
    the tile), along the rows ``(L, 128)`` and along the columns ``(8,
    L)``."""
    length, tile = doc.shape[0], _diagonal_tile(block)
    place = jnp.arange(length, dtype=doc.dtype) % tile // block
    ident = doc * (tile // block) + place
    return (jax.lax.broadcast_in_dim(ident, (length, _LANES), (0,)),
            jax.lax.broadcast_in_dim(ident, (_SUBLANES, length), (1,)))


def _down(row, tile: int):
    """A ``(1, T)`` row of a number a position as ``(T, 1)``."""
    return jnp.broadcast_to(row, (tile, tile)).T[:, :1]


def _diagonal_join_kernel(q_ref, k_ref, v_ref, rows_ref, cols_ref, out_ref,
                          lse_ref, joined_ref, joined_lse_ref):
    """One tile: ``out`` and ``lse`` are the clean-key kernel's; what is
    written is the softmax over both parts, the clean part entering as one
    more key of weight ``exp(lse)`` and value ``out``. A row that sees no
    clean key comes with ``lse`` near the mask value: its weight is 0. The
    scores are transposed (keys down, queries across) as in the backward
    kernel, so that maximum, sum and log-sum are rows as ``lse`` is."""
    group, tile, _ = q_ref.shape
    same = (jnp.tile(rows_ref[...], (1, tile // _LANES))
            == cols_ref[:1, :])
    k, v = k_ref[...], v_ref[...]

    def one_head(g, _):
        s = jax.lax.dot_general(k, q_ref[g], _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(same, s, _MASKED)
        lse_clean = lse_ref[g]
        m = jnp.maximum(s.max(axis=0, keepdims=True), lse_clean)
        p = jnp.exp(s - m)
        weight = jnp.exp(lse_clean - m)
        total = p.sum(axis=0, keepdims=True) + weight
        joined_lse_ref[g] = m + jnp.log(total)
        share = 1.0 / total
        out = (jax.lax.dot((p * share).T.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
               + _down(weight * share, tile) * out_ref[g].astype(jnp.float32))
        joined_ref[g] = out.astype(joined_ref.dtype)

    # unrolled: a rolled loop ran each kernel at twice the time (PERF.md
    # section 5)
    jax.lax.fori_loop(0, group, one_head, None, unroll=True)


def _diagonal_bwd_kernel(q_ref, k_ref, v_ref, rows_ref, cols_ref, lse_ref,
                         di_ref, d_out_ref, dq_ref, dq_sum_ref, dk_ref,
                         dv_ref):
    """One tile, the scores transposed as in JAX's backward kernel, so that
    ``lse`` and ``di`` are rows: ``dq`` added to the clean-key kernel's,
    ``dk`` and ``dv`` of the tile's noisy keys summed over the group."""
    group, tile, _ = q_ref.shape
    same = (jnp.tile(rows_ref[...], (1, tile // _LANES))
            == cols_ref[:1, :])
    k, v = k_ref[...], v_ref[...]

    def one_head(g, sums):
        dk, dv = sums
        q, d_out = q_ref[g], d_out_ref[g]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
        p = jnp.exp(jnp.where(same, s, _MASKED) - lse_ref[g])
        dv += jax.lax.dot(p.astype(d_out.dtype), d_out,
                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, d_out, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[g])
        dk += jax.lax.dot(ds.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        dq = jax.lax.dot(ds.T.astype(k.dtype), k,
                         preferred_element_type=jnp.float32)
        dq_sum_ref[g] = (dq_ref[g].astype(jnp.float32) + dq
                         ).astype(dq_sum_ref.dtype)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, group, one_head, (jnp.zeros(k.shape, jnp.float32),
                             jnp.zeros(v.shape, jnp.float32)), unroll=True)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _tiles(q, tile: int):
    """The block specs of one tile of the noisy copy: of ``q``-like ``(G,
    S, D)``, of ``k``-like ``(S, D)``, of the idents' rows and columns, and
    of ``lse``-like ``(G, 1, S)`` (a head's row is then an index of the
    leading dimension). The noisy copy is the first half, so a tile's index
    is its index into the whole."""
    group, _, d = q.shape
    return (pl.BlockSpec((group, tile, d), lambda t: (0, t, 0)),
            pl.BlockSpec((tile, d), lambda t: (t, 0)),
            pl.BlockSpec((tile, _LANES), lambda t: (t, 0)),
            pl.BlockSpec((_SUBLANES, tile), lambda t: (0, t)),
            pl.BlockSpec((group, 1, tile), lambda t: (0, 0, t)))


def _diagonal_join(q, k, v, doc, out, lse, block: int, interpret: bool):
    """Of one key/value head and one sequence: the clean-key kernel's
    ``out`` and ``lse`` with the noisy rows' joined with their diagonal."""
    tile = _diagonal_tile(block)
    length = doc.shape[0] // 2
    rows, cols = _idents(doc[:length], block)
    qs, ks, rs, cs, ls = _tiles(q, tile)
    out, lse = pl.pallas_call(
        _diagonal_join_kernel, grid=(length // tile,),
        in_specs=[qs, ks, ks, rs, cs, qs, ls], out_specs=[qs, ls],
        out_shape=[jax.ShapeDtypeStruct(out.shape, out.dtype),
                   jax.ShapeDtypeStruct(lse[:, None].shape, lse.dtype)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="blockdiff_diagonal_fwd", interpret=interpret,
    )(q, k, v, rows, cols, out, lse[:, None])
    return out, lse[:, 0]


def _diagonal_bwd(q, k, v, doc, lse, di, d_out, dq, block: int,
                  interpret: bool):
    """Of one key/value head and one sequence: ``dq`` with the diagonal's
    added to the noisy rows', and ``dk``, ``dv`` of the noisy keys."""
    tile = _diagonal_tile(block)
    length = doc.shape[0] // 2
    rows, cols = _idents(doc[:length], block)
    qs, ks, rs, cs, ls = _tiles(q, tile)
    return pl.pallas_call(
        _diagonal_bwd_kernel, grid=(length // tile,),
        in_specs=[qs, ks, ks, rs, cs, ls, ls, qs, qs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct(dq.shape, dq.dtype),
                   jax.ShapeDtypeStruct((length, k.shape[1]), k.dtype),
                   jax.ShapeDtypeStruct((length, v.shape[1]), v.dtype)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="blockdiff_diagonal_bwd", interpret=interpret,
    )(q, k, v, rows, cols, lse[:, None], di[:, None], d_out, dq)


# Both passes are jitted on their own, as JAX's kernel is: every layer and
# both copies of the step in a chunk program then share one trace and one
# lowered function, where the kernels' bodies would be traced and lowered
# anew for each (5 to 6 s of a warm start of the benchmark's cell at four
# layers; PERF.md section 5).
@functools.partial(jax.jit, static_argnames=("mask", "blocks", "interpret"))
def _forward(q, k, v, doc, *, mask: BlockDiffusion, blocks, interpret):
    """The joined output and the joined log-sum."""
    kernel = _kernel(q.shape[3], mask, q.shape[2], blocks, interpret)
    out, lse = _clean_part(kernel, q, k, v, doc)
    with jax.named_scope("diagonal"):     # the join is in its kernel
        join = functools.partial(_diagonal_join, block=mask.block,
                                 interpret=interpret)
        per_head = jax.vmap(join, in_axes=(0, 0, 0, None, 0, 0))
        return jax.vmap(per_head)(q, k, v, doc, out, lse)


@functools.partial(jax.jit, static_argnames=("mask", "blocks", "interpret"))
def _backward(q, k, v, doc, out, lse, d_out, *, mask: BlockDiffusion,
              blocks, interpret):
    kernel = _kernel(q.shape[3], mask, q.shape[2], blocks, interpret)
    di = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32), -1)
    dq, dk, dv = _clean_part_bwd(kernel, q, k, v, doc, lse, d_out, di)
    with jax.named_scope("diagonal"):
        bwd = functools.partial(_diagonal_bwd, block=mask.block,
                                interpret=interpret)
        per_head = jax.vmap(bwd, in_axes=(0, 0, 0, None, 0, 0, 0, 0))
        dq, dk_diag, dv_diag = jax.vmap(per_head)(q, k, v, doc, lse, di,
                                                  d_out, dq)
    with jax.named_scope("join"):
        return (dq, jnp.concatenate([dk_diag, dk], axis=2),
                jnp.concatenate([dv_diag, dv], axis=2))


def _two_parts_fwd(q, k, v, doc, mask: BlockDiffusion, blocks, interpret):
    out, lse = _forward(q, k, v, doc, mask=mask, blocks=blocks,
                        interpret=interpret)
    return out, (q, k, v, doc, out, lse)


def _two_parts_bwd(mask: BlockDiffusion, blocks, interpret, residuals,
                   d_out):
    doc = residuals[3]
    return (*_backward(*residuals, d_out, mask=mask, blocks=blocks,
                       interpret=interpret),
            np.zeros(doc.shape, jax.dtypes.float0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _two_parts(q, k, v, doc, mask: BlockDiffusion, blocks, interpret):
    """Attention under ``BlockDiffusion`` as the two parts its mask has
    (the module docstring), under one backward pass: the residuals are the
    inputs, the joined output and the joined log-sum."""
    return _two_parts_fwd(q, k, v, doc, mask, blocks, interpret)[0]


_two_parts.defvjp(_two_parts_fwd, _two_parts_bwd)


def key_blocks(seq_len: int, mask: Mask, group: int) -> Tuple[int, int]:
    """``(visited, total)``: the tiles of queries by keys the kernel steps
    through at ``seq_len`` under ``mask`` of all there are (under
    ``BlockDiffusion`` the queries by the clean keys), read from its own
    forward mask table (one query head's; all heads share the mask)."""
    kernel = _kernel(seq_len, mask, group, BLOCKS, False)
    keys = mask.clean_len if isinstance(mask, BlockDiffusion) else seq_len
    return (int(np.count_nonzero(np.asarray(
                kernel.fwd_mask_info.block_mask)[0])),
            (seq_len // BLOCKS.block_q) * (keys // BLOCKS.block_kv))


def diagonal_rows(mask: Mask) -> int:
    """The rows a sequence that the kernel path serves outside the kernel:
    under ``BlockDiffusion`` the noisy copy's, against their own blocks."""
    return mask.clean_len if isinstance(mask, BlockDiffusion) else 0
