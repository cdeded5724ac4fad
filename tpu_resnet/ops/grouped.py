"""A grouped matrix product whose work follows the groups: the rows of
``lhs`` lie sorted by group (first ``group_sizes[0]`` rows of group 0, then
group 1's, ...), every group has a matrix of its own in ``rhs``, and row
``r`` of the result is ``lhs[r] @ rhs[group of r]``. The groups' sum may
fall short of the rows: the rows past it belong to no group, and they are
0 in the result and in the gradient with respect to ``lhs``, and add
nothing to the gradient with respect to ``rhs``. That is the product of
a token model's routed experts (models/transformer.py::dispatch_experts,
which every token family's expert layers call): one buffer
of assignments sorted by expert, sized to what the held experts take
together and not to the busiest one times their number. The buffer's rows
are gathered from their tokens before the products and summed back into
them after (``ops/rows_to_tokens.py``: on one TPU chip a kernel that reads
only the rows an assignment filled, everywhere else the scatter-add; its
path is chosen as this module's is, by backend and device count).

Two paths, one contract (``grouped_path``: a pure function of the backend's
name and the number of its devices, like ``ops/attention.py``'s; no probe:
the padded products this replaces did four times the work, and on the chip
``ragged_dot`` took their time, 1.57 x the kernel's, PERF.md section 5):

- ``kernel``, one TPU chip: JAX's own ``megablox`` kernels
  (``jax.experimental.pallas.ops.tpu.megablox``): ``gmm`` forward, and
  backward ``gmm`` against the transposed matrices for ``lhs`` and ``tgmm``
  for ``rhs``. Their grid is the row tiles that some group fills (a tile that
  two groups share is visited for each), so a buffer half empty costs half.
  **What the kernel never writes:** ``gmm`` does not touch the rows past the
  groups' sum, forward or backward: they hold whatever the memory held
  before, NaN included. This module selects them away (``jnp.where``; a
  product with 0 would keep a NaN), so no caller sees them. ``tgmm`` selects
  its operands' rows by group itself. The backward pass rounds the
  cotangent to the operands' type first, which is what XLA's product of a
  float32 cotangent with bf16 operands does at its default precision; the
  kernel would otherwise multiply in float32 at several passes.
- ``ragged``, everywhere else (the CPU, and several chips under one
  auto-partitioned ``jit``, which refuses a Mosaic kernel: the kernel waits
  for the same ``shard_map`` as attention's, ROADMAP B-I 4):
  ``jax.lax.ragged_dot`` and its own derivatives. On a TPU its gradient
  with respect to ``lhs`` leaves the rows past the sum undefined as the
  kernel does (read on the chip: 112.5 where 0 belongs, PERF.md section
  5), so here too both ends are selected: ``lhs`` on the way in, whose
  transpose selects that gradient, and the result.

Both take operands of one type (bf16 in the benchmark's cell), accumulate in
float32 and hand on ``out_dtype``. In a device trace the kernel path reads
as ``pallas_call``s named ``gmm`` and ``tgmm``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

# Rows, contraction and columns of a tile, for all three kernels. Fixed
# after a sweep on the chip at the Trinity cell's shapes (experts 1,024
# wide; PERF.md section 5). ``_fit`` cuts it to a product's own sizes, and
# the benchmark's three expert widths take it so: 1,024 as it stands; 768
# whole (a tile of 1,024 would be a quarter padding); 1,536 whole too, one
# tile of half again the tiling's, where two of 1,024 would be a quarter
# padding and a masked remainder (one layer's three products at 8 groups
# of 1,536, forward and backward: 2.51 ms so, 2.16 as two tiles of 768,
# 1.99 whole; PERF.md section 5 at PR 36).
TILING = (256, 1024, 1024)


def grouped_path(backend: str, devices: int) -> str:
    """``kernel`` or ``ragged``, from the backend's name and the number of
    its devices alone: the kernel where Mosaic compiles it and the step is
    one device's program."""
    return "kernel" if backend == "tpu" and devices == 1 else "ragged"


def row_tile(path: str) -> int:
    """What the number of rows of ``lhs`` has to be a multiple of."""
    return TILING[0] if path == "kernel" else 8


def _zero_past(out, group_sizes):
    """``out`` with the rows past the groups' sum selected to 0."""
    inside = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(inside[:, None], out, 0)


def _fit_one(tile: int, size: int) -> int:
    """A tile that divides ``size``: the size whole where the tile covers
    it, the tile where it divides, and otherwise the largest multiple of
    the 128 lanes that divides and is no more than half again the tile
    (1,536 under a tile of 1,024: 1,536; 2,560: 1,280). Where nothing
    divides, the tile, and the kernel masks the remainder."""
    if size <= tile or size % tile == 0:
        return min(tile, size)
    return max((t for t in range(128, tile + tile // 2 + 1, 128)
                if size % t == 0), default=tile)


def _fit(tiling, contraction: int, columns: int):
    """``tiling`` with its contraction and columns cut to the product's
    own (``_fit_one``)."""
    return (tiling[0], _fit_one(tiling[1], contraction),
            _fit_one(tiling[2], columns))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_dot(lhs, rhs, group_sizes, out_dtype, tiling, interpret):
    return _zero_past(gmm(lhs, rhs, group_sizes, out_dtype,
                          _fit(tiling, *rhs.shape[1:]),
                          interpret=interpret), group_sizes)


def _kernel_fwd(lhs, rhs, group_sizes, out_dtype, tiling, interpret):
    return (_kernel_dot(lhs, rhs, group_sizes, out_dtype, tiling, interpret),
            (lhs, rhs, group_sizes))


def _kernel_bwd(out_dtype, tiling, interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    grad = grad.astype(lhs.dtype)
    k, n = rhs.shape[1:]
    d_lhs = _zero_past(gmm(
        grad, rhs, group_sizes, lhs.dtype, _fit(tiling, n, k),
        transpose_rhs=True, interpret=interpret), group_sizes)
    d_rhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                 _fit(tiling, k, n), num_actual_groups=rhs.shape[0],
                 interpret=interpret)
    return d_lhs, d_rhs, None


_kernel_dot.defvjp(_kernel_fwd, _kernel_bwd)


def grouped_dot(lhs, rhs, group_sizes, out_dtype, path: str, *,
                tiling: Optional[Tuple[int, int, int]] = None,
                interpret: Optional[bool] = None):
    """``lhs`` ``(rows, k)`` by ``rhs`` ``(groups, k, n)`` under
    ``group_sizes`` ``(groups,)`` int32, the sum of which is at most
    ``rows``: ``(rows, n)`` in ``out_dtype``, accumulated in float32, the
    rows past the sum 0. ``path`` is ``grouped_path``'s answer; on the
    kernel's, ``rows`` is a multiple of the tiling's first entry.
    ``tiling`` and ``interpret`` are for the tests and the sweep: the first
    defaults to ``TILING``, the second to any backend but a TPU."""
    if path == "kernel":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _kernel_dot(lhs, rhs, group_sizes, jnp.dtype(out_dtype),
                           tuple(tiling or TILING), interpret)
    return _zero_past(jax.lax.ragged_dot(
        _zero_past(lhs, group_sizes), rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(out_dtype), group_sizes)
