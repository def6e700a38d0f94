"""Pallas TPU kernel for Count-Sketch compression.

The sketch is a scatter-add of sign-flipped [P] rows into S buckets
(reference semantics: murmura/aggregation/sketchguard.py:91-112, host-side
np.bincount).  On TPU, XLA lowers ``segment_sum`` with random indices to a
serialized scatter — the one op in the Sketchguard round that does not
vectorize.  This kernel reformulates it as a chunked one-hot matmul over
all rows of the [N, P] state matrix at once:

    for each chunk c of the parameter axis:
        onehot_t = (bucket_ids == hash[c])          # [S, C] bf16, in VMEM
        out     += (sign[c] * rows[:, c]) . onehot_t^T   # [N, C] x [S, C]^T

The one-hot is built once a chunk, for every row, and never touches HBM;
the rows are read in their resident dtype and signed in VMEM, so no
float32 copy of the states is written.  The dot is the ``q @ k^T`` form
the MXU takes natively, with a bf16 one-hot (0 and 1 are exact) and
float32 accumulation, at the fewest bf16 passes that are exact for the
rows' dtype:

- bf16 rows: one pass.  value x +-1 x {0, 1} is exact in bf16.
- anything else is lifted to float32 and split into three bf16 parts
  (8 + 8 + 8 mantissa bits) that sum back to the value exactly: three
  passes, no value rounded.  (``Precision.HIGHEST`` would spend six on a
  float32 one-hot.)

So only the float32 accumulation order differs from ``segment_sum``.  The
tail of the parameter axis is masked in the kernel, values as well as
buckets: what lies behind it in the last block is not the caller's.

CPU/debug path: ``interpret=True`` runs the same kernel through the Pallas
interpreter (used by the test suite, which pins JAX to CPU).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Columns of the parameter axis per grid step, and the most elements the
# [S, chunk] bf16 one-hot, the dominant VMEM tenant, may have (8 MB): a
# sketch padded to 1024 or less gets the whole chunk, a wider one half of it.
# Measured on a v5e chip at [64, 6,603,710] bf16 -> 1000 (PERF.md, PR 27):
# chunk 1024 6.29 ms a call, 2048 5.94, 4096 5.50 (4.77 inside the round).
_CHUNK = 4096
_ONEHOT_ELEMS = 4 * 1024 * 1024

# Bytes of one [rows, chunk] block of the matrix: 128 bf16 rows or 64
# float32 rows of a whole chunk.  64 and 128 bf16 nodes are one block; more
# rows get an outer grid axis, and the one-hot is then built once a block.
_ROWS_BLOCK_BYTES = 1024 * 1024

# Largest supported (padded) sketch width.  count_sketch() falls back to
# segment_sum above this.
MAX_SKETCH_PAD = 2048


def _exact_bf16_parts(signed, dtype):
    """bf16 arrays whose sum is ``signed`` (float32, holding values of
    ``dtype``) exactly."""
    if dtype == jnp.bfloat16:
        return [signed.astype(jnp.bfloat16)]
    parts, rest = [], signed
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        parts.append(part)
        rest = rest - part.astype(jnp.float32)
    return parts


def _sketch_kernel(rows_ref, hash_ref, sign_ref, out_ref, *, p, chunk, sketch_pad):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Past column p the blocks hold whatever was there (NaN x 0 is NaN):
    # no bucket for those columns, and a zero for their values.
    col = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    live = col < p  # [1, C]
    h = jnp.where(live, hash_ref[:], -1)
    # iota over sublanes against the hash row broadcast along sublanes:
    # the one-hot comes out transposed, [S, C], with no relayout.
    buckets = jax.lax.broadcasted_iota(jnp.int32, (sketch_pad, chunk), 0)
    onehot_t = jnp.where(buckets == h, 1.0, 0.0).astype(jnp.bfloat16)
    signed = jnp.where(
        live, rows_ref[:].astype(jnp.float32) * sign_ref[:], 0.0
    )  # [N, C]
    out_ref[:] += sum(
        jax.lax.dot_general(
            part, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [N, C] . [S, C]^T
        for part in _exact_bf16_parts(signed, rows_ref.dtype)
    )


@functools.partial(jax.jit, static_argnames=("sketch_size", "interpret"))
def count_sketch_pallas(
    rows: jnp.ndarray,
    hash_table: jnp.ndarray,
    sign_table: jnp.ndarray,
    sketch_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Count-Sketch of the rows of an [N, P] matrix -> [N, sketch_size]
    float32, MXU formulation; a [P] vector is the N = 1 case and gives
    [sketch_size].  The tables are [P]; ``rows`` may be wider, and its
    columns from P on are not read as values.

    Matches ``ops.sketch.count_sketch`` (segment_sum of the float32-lifted
    rows) up to float accumulation order.
    """
    if rows.ndim == 1:
        return count_sketch_pallas(
            rows[None], hash_table, sign_table, sketch_size, interpret
        )[0]
    n = rows.shape[0]
    p = hash_table.shape[-1]
    sketch_pad = ((sketch_size + 127) // 128) * 128
    if sketch_pad > MAX_SKETCH_PAD:
        raise ValueError(
            f"sketch_size {sketch_size} exceeds the kernel's VMEM budget "
            f"(padded {sketch_pad} > {MAX_SKETCH_PAD}); use the segment_sum "
            "path (count_sketch with use_pallas=False)"
        )
    chunk = min(_CHUNK, _ONEHOT_ELEMS // sketch_pad)
    row_block = min(n, _ROWS_BLOCK_BYTES // (chunk * rows.dtype.itemsize))
    table_spec = pl.BlockSpec((1, chunk), lambda r, c: (0, c))
    out = pl.pallas_call(
        functools.partial(
            _sketch_kernel, p=p, chunk=chunk, sketch_pad=sketch_pad
        ),
        grid=(pl.cdiv(n, row_block), pl.cdiv(p, chunk)),
        in_specs=[
            pl.BlockSpec((row_block, chunk), lambda r, c: (r, c)),
            table_spec,
            table_spec,
        ],
        out_specs=pl.BlockSpec((row_block, sketch_pad), lambda r, c: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sketch_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        rows,
        hash_table.reshape(1, p).astype(jnp.int32),
        sign_table.reshape(1, p).astype(jnp.float32),
    )
    return out[:, :sketch_size]
