"""Pallas TPU kernel for Count-Sketch compression.

The sketch is a scatter-add of a sign-flipped [P] vector into S buckets
(reference semantics: murmura/aggregation/sketchguard.py:91-112, host-side
np.bincount).  On TPU, XLA lowers ``segment_sum`` with random indices to a
serialized scatter — the one op in the Sketchguard round that does not
vectorize.  This kernel reformulates it as a chunked one-hot matmul:

    for each chunk c of the parameter axis:
        onehot = (hash[c] == bucket_ids)        # [C, S] built in VMEM
        out   += signed_vals[c] @ onehot        # [1, C] x [C, S] on the MXU

The one-hot never touches HBM and every accumulation is an MXU matmul, so
the sketch runs at matmul throughput instead of scatter throughput.  The
matmul runs at ``Precision.HIGHEST`` so the f32 values are not rounded to
bf16 on the way into the MXU.

CPU/debug path: ``interpret=True`` runs the same kernel through the Pallas
interpreter (used by the test suite, which pins JAX to CPU).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Chunk of the parameter axis processed per grid step. 1024 x S(<=2048) f32
# one-hot stays well under the ~16 MB VMEM budget.
_CHUNK = 1024

# Largest supported (padded) sketch width: the [_CHUNK, S] one-hot is the
# dominant VMEM tenant (1024 x 2048 f32 = 8 MB). count_sketch() falls back
# to segment_sum above this.
MAX_SKETCH_PAD = 2048


def _sketch_kernel(vals_ref, hash_ref, out_ref, *, chunk, sketch_pad):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    h = hash_ref[:].reshape(chunk, 1)  # [C, 1] int32
    buckets = jax.lax.broadcasted_iota(jnp.int32, (chunk, sketch_pad), 1)
    onehot = (h == buckets).astype(jnp.float32)  # [C, S]
    # HIGHEST: at the MXU's default precision the f32 values are rounded
    # to bf16 before the multiply — measured on a v5e chip as a 1.8e-3
    # relative error against segment_sum at P=6.6M (chip_smoke.py, PR 22).
    # The one-hot side is exact in any precision; the values are not.
    out_ref[:] += jnp.dot(
        vals_ref[:], onehot, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [1, C] @ [C, S]


@functools.partial(jax.jit, static_argnames=("sketch_size", "interpret"))
def count_sketch_pallas(
    vector: jnp.ndarray,
    hash_table: jnp.ndarray,
    sign_table: jnp.ndarray,
    sketch_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Count-Sketch of a [P] vector -> [sketch_size], MXU formulation.

    Matches ``ops.sketch.count_sketch`` (segment_sum) bit-for-bit up to
    float accumulation order.
    """
    p = vector.shape[-1]
    signed = sign_table * vector

    pad_p = (-p) % _CHUNK
    # Padded tail gets bucket id sketch_pad-1 with value 0: no contribution.
    sketch_pad = ((sketch_size + 127) // 128) * 128
    if sketch_pad > MAX_SKETCH_PAD:
        raise ValueError(
            f"sketch_size {sketch_size} exceeds the kernel's VMEM budget "
            f"(padded {sketch_pad} > {MAX_SKETCH_PAD}); use the segment_sum "
            "path (count_sketch with use_pallas=False)"
        )
    if pad_p:
        signed = jnp.pad(signed, (0, pad_p))
        hash_table = jnp.pad(
            hash_table, (0, pad_p), constant_values=sketch_pad - 1
        )

    n_chunks = signed.shape[-1] // _CHUNK
    out = pl.pallas_call(
        functools.partial(
            _sketch_kernel, chunk=_CHUNK, sketch_pad=sketch_pad
        ),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((1, _CHUNK), lambda i: (0, i)),
            pl.BlockSpec((1, _CHUNK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, sketch_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, sketch_pad), jnp.float32),
        interpret=interpret,
    )(signed.reshape(1, -1), hash_table.reshape(1, -1).astype(jnp.int32))
    return out[0, :sketch_size]
