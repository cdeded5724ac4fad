"""The sum of an expert buffer's rows into their tokens, and its transpose.

A token model's expert layer (models/transformer.py::dispatch_experts)
gathers its tokens into one buffer of rows sorted by expert (a token's
row once for each held expert it chose), runs the experts' grouped
products over it (ops/grouped.py) and sums every row back into its token.
The sum and the gather are each other's transpose, and this module gives
both, as one pair:

- ``combine(y, plan)``: ``(n, d)`` float32, token ``t``'s row the sum of
  the rows of ``y`` that hold one of its assignments; backward, the
  gather of the cotangent by the rows' tokens;
- ``dispatch(x, plan)``: the gather ``take(x, token)``; backward, the sum
  of the cotangent's rows into their tokens, in float32, rounded once to
  ``x``'s type.

The rows that hold no assignment (past the held experts' sum, or past
this buffer's share of the sorted order) are never read: they hold
whatever the grouped kernel left there (ops/grouped.py), and not even a
0 times NaN of theirs reaches a token.

Two paths, one contract (``rows_path``: a pure function of the backend's
name and the number of its devices, as ``grouped_path`` is; no probe):

- ``kernel``, one TPU chip: one Pallas kernel, ``rows_to_tokens``, for
  both sums. Its grid is tiles of tokens, and each output row is written
  once. Within an expert the buffer's rows lie in token order, so a
  tile's rows of one expert are one run of the buffer; the kernel copies
  those runs from HBM in blocks of ``ROW_BLOCK`` rows, ``DEPTH`` copies in
  flight across tiles, and adds each row it holds into its token's row of
  a float32 tile in VMEM. It reads the rows the assignments filled and
  the few around each run's ends that its blocks hold, where XLA's
  scatter-add read every row of the buffer one at a time (PERF.md section
  5 at PR 37 has the sweep). The runs are counted once a buffer by
  ``plan``, from the routing, as integers.
- ``scatter``, everywhere else (the CPU, and several chips under one
  auto-partitioned ``jit``, which refuses a Mosaic kernel): the
  ``.at[token].add`` and the ``take`` that the layer used before, and
  their own derivatives.

In a device trace the kernel reads as a ``pallas_call`` named
``rows_to_tokens``, under ``moe/combine`` forward and under
``moe/dispatch`` backward.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a tile, rows a copy and copies in flight. Fixed after a sweep on
# the chip at the three token cells' shapes (PERF.md section 5 at PR 37).
# A block of 16 rows is one tile of bf16 rows and two of float32.
TOKEN_TILE = 512
ROW_BLOCK = 16
DEPTH = 8


def rows_path(backend: str, devices: int) -> str:
    """``kernel`` or ``scatter``, from the backend's name and the number
    of its devices alone: the kernel where Mosaic compiles it and the step
    is one device's program."""
    return "kernel" if backend == "tpu" and devices == 1 else "scatter"


def token_tile(n: int, tile: int = TOKEN_TILE) -> int:
    """The tokens of a tile: ``tile``, or the largest multiple of 8 under
    it that divides ``n``; ``n`` whole where none does."""
    return max((t for t in range(8, min(tile, n) + 1, 8) if n % t == 0),
               default=n)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("token", "bounds", "starts", "firsts",
                                "lasts"),
                   meta_fields=("n", "block", "depth", "interpret"))
@dataclasses.dataclass(frozen=True)
class Plan:
    """Where a buffer's rows go. ``token`` ``(rows,)``: each row's token
    (whatever it is for a row without an assignment). The kernel's tables,
    None on the ``scatter`` path: ``bounds`` ``(tiles + 1,)``, the first
    block of each tile of tokens and the count of all; for each block,
    ``starts``, its first row (a multiple of ``block``), and ``firsts``,
    ``lasts``, the part of it that the tile's run holds."""
    token: jax.Array
    bounds: Optional[jax.Array]
    starts: Optional[jax.Array]
    firsts: Optional[jax.Array]
    lasts: Optional[jax.Array]
    n: int
    block: int
    depth: int
    interpret: Any


def plan(token, n: int, path: str, *, expert, ends, load, lo, here,
         tile: Optional[int] = None, block: int = ROW_BLOCK,
         depth: int = DEPTH, interpret: Any = None) -> Plan:
    """The ``Plan`` of a buffer that holds the sorted positions ``lo ..
    lo + rows`` of ``n`` tokens' assignments, of which the first ``here``
    fall on an expert held here. ``token`` ``(rows,)`` is each row's token;
    ``expert`` ``(n * top_k,)`` each assignment's held expert (``count``
    where it is not held), ``load`` ``(count,)`` each held expert's
    assignments and ``ends`` their running sum: the sort is by expert,
    stable, so an expert's positions lie in token order. On the
    ``scatter`` path the plan is ``token`` alone. ``tile``, ``block``,
    ``depth`` and ``interpret`` are for the tests and the sweep."""
    if path != "kernel":
        return Plan(token, None, None, None, None, n, block, depth, False)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, count = token.shape[0], load.shape[0]
    # a TPU's buffer is whole tiles of 256 rows; a smaller one (the tests')
    # takes the blocks that divide it
    block = math.gcd(block, rows)
    expert = expert.reshape(n, -1)
    first_row, top = ends - load, jnp.minimum(lo + rows, here)
    tile = token_tile(n, tile or TOKEN_TILE)
    tiles = n // tile
    # of each held expert, the tokens before each tile that chose it, and
    # so where in the buffer each tile's run of that expert starts
    chose = jnp.any(expert[:, :, None] == jnp.arange(count), axis=1)
    per_tile = jnp.sum(chose.reshape(tiles, tile, count), axis=1,
                       dtype=jnp.int32)
    before = jnp.concatenate([jnp.zeros((1, count), jnp.int32),
                              jnp.cumsum(per_tile, axis=0)])
    at = jnp.clip(first_row + before, lo, top) - lo
    low, high = at[:-1].reshape(-1), at[1:].reshape(-1)   # tile-major runs
    # each run's blocks, and the block list they make, tile after tile
    low_block = low // block
    blocks = jnp.where(high > low, -(-high // block) - low_block, 0)
    through = jnp.cumsum(blocks)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              through.reshape(tiles, count)[:, -1]])
    # a run of m rows spans at most m / block + 2 blocks
    q = jnp.arange(rows // block + 2 * tiles * count, dtype=jnp.int32)
    run = jnp.minimum(jnp.sum(through[None, :] <= q[:, None], axis=1,
                              dtype=jnp.int32), tiles * count - 1)
    starts = jnp.clip((low_block[run] + q - through[run] + blocks[run])
                      * block, 0, rows - block)
    return Plan(token, bounds, starts,
                jnp.clip(low[run] - starts, 0, block),
                jnp.clip(high[run] - starts, 0, block),
                n, block, depth, interpret)


# --------------------------------------------------------------- the kernel
def _kernel(bounds, starts, firsts, lasts, token, y_hbm, out_ref, stage,
            sems, *acc, block: int, depth: int):
    """One tile of tokens: the blocks ``bounds[i] .. bounds[i + 1]`` of
    the list, each row of a block's run added into its token's row. The
    copies run ``depth - 1`` blocks ahead across tiles (slot ``q %
    depth``), so a tile's first blocks were on their way during the tile
    before it."""
    i = pl.program_id(0)
    acc = acc[0] if acc else out_ref     # a float32 output is its own sum
    tile = acc.shape[0]
    total = bounds[pl.num_programs(0)]

    def copy(q):
        slot = q % depth
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(pl.multiple_of(starts[q], block), block)],
            stage.at[slot], sems.at[slot])

    @pl.when(i == 0)
    def _():
        def start(q, _):
            copy(q).start()
        jax.lax.fori_loop(0, jnp.minimum(depth - 1, total), start, None)

    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def one_block(q, _):
        @pl.when(q + depth - 1 < total)
        def _():
            copy(q + depth - 1).start()

        copy(q).wait()
        rows = stage[q % depth].astype(jnp.float32)
        first, last, row0 = firsts[q], lasts[q], starts[q]
        for r in range(block):      # unrolled: the rows of one block
            @pl.when((first <= r) & (r < last))
            def _():
                t = token[row0 + r] - i * tile
                acc[pl.ds(t, 1), :] += rows[r:r + 1]

    jax.lax.fori_loop(bounds[i], bounds[i + 1], one_block, None)
    if acc is not out_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _sum_rows(y, plan: Plan, out_dtype):
    """``(n, d)`` in ``out_dtype``: each token's rows of ``y`` summed in
    float32, by the kernel. Jitted on its own, so that every layer and both
    copies of the step in a chunk program share one trace of it."""
    d = y.shape[1]
    tile = plan.n // (plan.bounds.shape[0] - 1)
    out_dtype = jnp.dtype(out_dtype)
    scratch = [pltpu.VMEM((plan.depth, plan.block, d), y.dtype),
               pltpu.SemaphoreType.DMA((plan.depth,))]
    if out_dtype != jnp.float32:
        scratch.append(pltpu.VMEM((tile, d), jnp.float32))
    vmem = (2 * tile * d * out_dtype.itemsize + 4 * tile * d
            + plan.depth * plan.block * d * y.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, block=plan.block, depth=plan.depth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(plan.n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((plan.n, d), out_dtype),
        # the copies run ahead across tiles: the tiles go in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        name="rows_to_tokens", interpret=plan.interpret,
    )(plan.bounds, plan.starts, plan.firsts, plan.lasts, plan.token, y)


# -------------------------------------------------------------- the pair
@jax.custom_vjp
def _combine(y, plan: Plan):
    return _sum_rows(y, plan, jnp.float32)


def _combine_fwd(y, plan):
    return _combine(y, plan), plan


def _combine_bwd(plan, grad):
    return jnp.take(grad, plan.token, axis=0), None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _dispatch(x, plan: Plan):
    return jnp.take(x, plan.token, axis=0)


def _dispatch_fwd(x, plan):
    return _dispatch(x, plan), plan


def _dispatch_bwd(plan, grad):
    return _sum_rows(grad, plan, grad.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def combine(y, plan: Plan):
    """``(n, d)`` float32: each token's rows of ``y`` ``(rows, d)``
    summed (the module docstring)."""
    if plan.bounds is None:
        return jnp.zeros((plan.n, y.shape[1]), jnp.float32).at[
            plan.token].add(y)
    return _combine(y.astype(jnp.float32), plan)


def dispatch(x, plan: Plan):
    """``(rows, d)``: each row's token's row of ``x`` ``(n, d)``."""
    if plan.bounds is None:
        return jnp.take(x, plan.token, axis=0)
    return _dispatch(x, plan)
