"""Count-Sketch compression (reference: murmura/aggregation/sketchguard.py:71-124).

The reference computes the sketch host-side with ``np.bincount``; here
sketching all N nodes is one traced op inside the round step — on a TPU the
one-hot MXU kernel of ``ops/pallas_sketch.py`` over the whole [N, P] matrix,
elsewhere a vmapped ``jax.ops.segment_sum`` of the sign-flipped rows — and
the sketch itself is what would travel on the wire (sketchguard.py:126-155).
"""

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def make_sketch_tables(
    model_dim: int, sketch_size: int, seed: int = 42
) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded hash/sign tables, matching the reference's RandomState draws
    (sketchguard.py:71-76): hash ~ randint(0, sketch_size, model_dim),
    sign ~ choice({-1,+1}, model_dim)."""
    rng = np.random.RandomState(seed)
    hash_table = rng.randint(0, sketch_size, size=model_dim).astype(np.int32)
    sign_table = rng.choice([-1, 1], size=model_dim).astype(np.float32)
    return hash_table, sign_table


def count_sketch(
    rows: jnp.ndarray,
    hash_table: jnp.ndarray,
    sign_table: jnp.ndarray,
    sketch_size: int,
    use_pallas: "bool | None" = None,
) -> jnp.ndarray:
    """Compress the rows of an [N, P] matrix to [N, sketch_size]
    Count-Sketches, or a [P] vector to [sketch_size]
    (reference: sketchguard.py:91-112).

    On TPU this dispatches to the Pallas MXU kernel
    (ops/pallas_sketch.py), which sketches all rows in one call — XLA
    lowers segment_sum with random indices to a serialized scatter, the one
    non-vectorizing op in the Sketchguard round.  Elsewhere (CPU tests) it
    stays a segment_sum, vmapped over the rows; an explicit
    ``use_pallas=True`` there runs the kernel interpreted.  The kernel is
    compiled exactly when the default backend is a TPU (``chip_smoke.py``
    asserts ``tpu_custom_call`` in the compiled sketchguard round).
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        from murmura_tpu.ops.pallas_sketch import MAX_SKETCH_PAD

        use_pallas = on_tpu and sketch_size <= MAX_SKETCH_PAD
    if use_pallas:
        from murmura_tpu.ops.pallas_sketch import count_sketch_pallas

        return count_sketch_pallas(
            rows, hash_table, sign_table, sketch_size, interpret=not on_tpu
        )

    def sketch_one(vector):
        return jax.ops.segment_sum(
            sign_table * vector, hash_table, num_segments=sketch_size
        )

    return jax.vmap(sketch_one)(rows) if rows.ndim == 2 else sketch_one(rows)
