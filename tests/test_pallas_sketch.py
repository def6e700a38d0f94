"""Pallas Count-Sketch kernel vs the segment_sum reference path
(interpret mode — the suite is pinned to CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from murmura_tpu.ops import pallas_sketch
from murmura_tpu.ops.pallas_sketch import count_sketch_pallas
from murmura_tpu.ops.sketch import count_sketch, make_sketch_tables

# A chunk of the kernel at the sketch widths tested here (all pad to 1024
# or less): model_dim below it, a multiple of it, and with a tail.
CHUNK = pallas_sketch._CHUNK


def _segment_sum_rows(rows, hash_t, sign_t, sketch_size):
    """segment_sum of the float32-lifted rows: what the kernel is held to."""
    return count_sketch(
        jnp.asarray(rows).astype(jnp.float32), hash_t, sign_t, sketch_size,
        use_pallas=False,
    )


def _rows(n, model_dim, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.normal(size=(n, model_dim)).astype(np.float32)
    ).astype(dtype)


@pytest.mark.parametrize("model_dim,sketch_size", [
    (500, 100),      # smaller than one chunk, unaligned sketch
    (1024, 128),     # aligned sketch
    (5000, 1000),    # a tail behind the first chunk, both unaligned
])
def test_pallas_sketch_matches_segment_sum(model_dim, sketch_size):
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=3)
    rng = np.random.default_rng(0)
    vec = rng.normal(size=model_dim).astype(np.float32)

    ref = count_sketch(vec, hash_t, sign_t, sketch_size, use_pallas=False)
    out = count_sketch_pallas(vec, hash_t, sign_t, sketch_size, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sketch_size", [128, 100], ids=["aligned", "unaligned"])
@pytest.mark.parametrize(
    "model_dim", [700, 2 * CHUNK, CHUNK + 904],
    ids=["below_chunk", "chunk_multiple", "tail"],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [1, 4, 64])
def test_pallas_sketch_rows_match_segment_sum(n, dtype, model_dim, sketch_size):
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=5)
    rows = _rows(n, model_dim, dtype)

    ref = _segment_sum_rows(rows, hash_t, sign_t, sketch_size)
    out = count_sketch_pallas(rows, hash_t, sign_t, sketch_size, interpret=True)
    assert out.shape == (n, sketch_size) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_pallas_sketch_masks_what_lies_behind_the_tail(dtype):
    """The tables end inside the last chunk; the matrix goes on behind them
    with NaN, as an out-of-bounds block may.  Neither a bucket nor a value
    of those columns may reach the sketch (NaN x 0 is NaN)."""
    model_dim, sketch_size, n = CHUNK + 300, 100, 4
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=7)
    rows = _rows(n, model_dim, dtype)
    behind = jnp.full((n, 2 * CHUNK - model_dim), jnp.nan, dtype)
    wide = jnp.concatenate([rows, behind], axis=1)

    ref = _segment_sum_rows(rows, hash_t, sign_t, sketch_size)
    out = count_sketch_pallas(wide, hash_t, sign_t, sketch_size, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_pallas_sketch_vector_is_row_zero_of_one_row(dtype):
    model_dim, sketch_size = CHUNK + 17, 96
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=2)
    rows = _rows(1, model_dim, dtype)

    of_vector = count_sketch_pallas(
        rows[0], hash_t, sign_t, sketch_size, interpret=True
    )
    of_rows = count_sketch_pallas(rows, hash_t, sign_t, sketch_size, interpret=True)
    assert of_vector.shape == (sketch_size,)
    np.testing.assert_array_equal(np.asarray(of_vector), np.asarray(of_rows[0]))


def test_pallas_sketch_float32_values_are_not_rounded():
    """Values with all 24 mantissa bits set, one to a bucket: the three bf16
    parts must give each back exactly (a single bf16 pass would round them
    at the 8th bit, an error of 4e-3 relative)."""
    model_dim = sketch_size = 128
    hash_t = np.arange(model_dim, dtype=np.int32)
    sign_t = np.where(np.arange(model_dim) % 2, -1.0, 1.0).astype(np.float32)
    exps = np.arange(model_dim) % 40 - 20
    rows = (np.float32(2.0) - np.float32(2.0 ** -23)) * np.exp2(exps).astype(np.float32)
    rows = np.stack([rows, -rows[::-1]])

    out = count_sketch_pallas(rows, hash_t, sign_t, sketch_size, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), sign_t * rows)


def test_pallas_sketch_more_rows_than_a_block():
    """An outer grid axis over row blocks, the last of them partial."""
    model_dim, sketch_size = 600, 96
    n = pallas_sketch._ROWS_BLOCK_BYTES // (CHUNK * 2) + 5
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=4)
    rows = _rows(n, model_dim, jnp.bfloat16)

    ref = _segment_sum_rows(rows, hash_t, sign_t, sketch_size)
    out = count_sketch_pallas(rows, hash_t, sign_t, sketch_size, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_sketch_wide_sketch_halves_the_chunk():
    """A sketch padded over 1024 runs at half the chunk (the one-hot's VMEM
    budget); over MAX_SKETCH_PAD the kernel refuses."""
    model_dim, sketch_size = CHUNK + 100, 1500
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=6)
    rows = _rows(2, model_dim, jnp.float32)

    ref = _segment_sum_rows(rows, hash_t, sign_t, sketch_size)
    out = count_sketch_pallas(rows, hash_t, sign_t, sketch_size, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="segment_sum"):
        count_sketch_pallas(
            rows, hash_t, sign_t, pallas_sketch.MAX_SKETCH_PAD + 1, interpret=True
        )


def test_pallas_sketch_under_vmap():
    model_dim, sketch_size, n = 700, 96, 4
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=1)
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(n, model_dim)).astype(np.float32)

    ref = jax.vmap(
        lambda v: count_sketch(v, hash_t, sign_t, sketch_size, use_pallas=False)
    )(vecs)
    out = jax.vmap(
        lambda v: count_sketch_pallas(v, hash_t, sign_t, sketch_size,
                                      interpret=True)
    )(vecs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["segment_sum", "kernel"])
def test_count_sketch_takes_rows_and_vectors(use_pallas):
    """ops.sketch.count_sketch: an [N, P] matrix gives [N, S], a [P] vector
    [S], on either path."""
    model_dim, sketch_size, n = 900, 100, 3
    hash_t, sign_t = make_sketch_tables(model_dim, sketch_size, seed=8)
    rows = _rows(n, model_dim, jnp.float32)

    of_rows = count_sketch(rows, hash_t, sign_t, sketch_size, use_pallas=use_pallas)
    assert of_rows.shape == (n, sketch_size)
    for i in range(n):
        of_vector = count_sketch(
            rows[i], hash_t, sign_t, sketch_size, use_pallas=use_pallas
        )
        np.testing.assert_allclose(np.asarray(of_vector), np.asarray(of_rows[i]),
                                   rtol=1e-6, atol=1e-6)
