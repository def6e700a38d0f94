"""Rows added into their places: ``out[i] = sum of scale[r] * rows[r] over
the rows r with index[r] == i``, ``out`` ``[size, H]`` float32; a row whose
``index`` is negative never enters a sum (what it holds may be undefined).
The rows are added in their own order, so a place takes its rows' sum in
the order the rows come.

The routed experts' pairs' side (``models/decoder.py``) adds a buffer's rows
back into a sequence's positions with it: the result of the experts, and
the cotangent of the sequence that fed the buffer.  Whatever it is given
costs one read of ``rows`` and one write of ``out``: no array of the pairs'
``[T * top_k, H]`` is ever written.

- *On a TPU*, where the shapes tile: one Pallas kernel
  (``row_scatter_add``).  A column block of ``out`` (every place, ``lanes``
  of its columns) stays in VMEM while the rows stream through in blocks;
  each taken row is added into its place's row of the block, a row at a
  time, in the rows' order.  ``index`` and ``scale`` are read from SMEM.
- *Elsewhere* (the CPU tests), or at shapes the kernel does not tile, the
  same adds as a ``jnp`` scatter-add in the rows' order.  ``use_pallas=True``
  off a TPU runs the kernel interpreted.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES, _SUBLANES = 128, 8
# The kernel keeps a column block of ``out`` in VMEM (twice: the pipeline's
# two buffers): its columns are the most that keep both under this.  Every
# column block reads all the rows again, so fewer and wider blocks are
# faster: at 4,096 places of 2,048 columns one block of 2,048 took 0.45 ms
# less a layer's step than two of 1,024 (a v5e, which has 128 MiB of VMEM).
# What the limit leaves the compiler for the operations around the kernel
# was not measured apart: in a whole round the grouped products beside it
# ran longer than without it.
_RESIDENT_BYTES = 64 * 2**20
_VMEM_LIMIT_BYTES = 100 * 2**20
_ROWS_A_BLOCK = 512


def kernel_blocks(rows: int, size: int, width: int):
    """``(rows a block, columns a block)`` of the kernel, or None where the
    shapes do not tile."""
    if rows % _SUBLANES or size % _SUBLANES or width % _LANES:
        return None
    block = next(b for b in (_ROWS_A_BLOCK, 256, 128, 64, 32, 16, _SUBLANES) if rows % b == 0)
    lanes = next((c for c in range(width, 0, -_LANES)
                  if width % c == 0 and 2 * size * c * 4 <= _RESIDENT_BYTES), None)
    return None if lanes is None else (block, lanes)


def _kernel(index_ref, scale_ref, rows_ref, out_ref):
    first = pl.program_id(1) * rows_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def add(r, carry):
        place = index_ref[first + r]

        @pl.when(place >= 0)
        def _():
            out_ref[pl.ds(place, 1), :] += scale_ref[first + r] * rows_ref[pl.ds(r, 1), :]

        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0], add, 0)


def _pallas(rows, index, scale, size, *, block, lanes, interpret):
    n, width = rows.shape
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // lanes, n // block),
            in_specs=[pl.BlockSpec((block, lanes), lambda j, i, *_: (i, j))],
            out_specs=pl.BlockSpec((size, lanes), lambda j, i, *_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((size, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="row_scatter_add",
        interpret=interpret,
    )(index, scale, rows)


def _scattered(rows, index, scale, size):
    taken = index >= 0
    added = jnp.where(taken[:, None], scale[:, None] * rows, 0.0)
    return jnp.zeros((size, rows.shape[1]), jnp.float32).at[
        jnp.where(taken, index, size)].add(added, mode="drop")


def row_scatter_add(rows, index, scale, size, use_pallas=None):
    """``out`` [size, H] float32 from ``rows`` [R, H] float32, ``index`` [R]
    int32 and ``scale`` [R] float32 (module docstring)."""
    on_tpu = jax.default_backend() == "tpu"
    blocks = kernel_blocks(rows.shape[0], size, rows.shape[1])
    if blocks is not None and (on_tpu if use_pallas is None else use_pallas):
        block, lanes = blocks
        return _pallas(rows, index, scale, size, block=block, lanes=lanes, interpret=not on_tpu)
    return _scattered(rows, index, scale, size)
