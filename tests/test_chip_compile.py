"""Compile the main path's Pallas kernels for a *described* TPU v5e at the
flagship model's real width — no chip attached, nothing runs.

Interpret mode (every other kernel test, CPU-pinned) cannot see what the
chip's compiler refuses: unaligned tiles, a VMEM overrun, a program that
does not fit HBM.  These tests ask that compiler, with ``interpret=False``,
and assert the kernel is really in the compiled program
(``tpu_custom_call``).  ``chip_smoke.py`` then runs the same kernels on the
chip against their lax paths.

The topology is described inside a module-scoped fixture, never at import:
the worker that is handed this file loads the TPU library and keeps it
until it exits, so every chip compile of the suite lives in this one file
and compiles in the test's own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from murmura_tpu.ops import pallas_agg
from murmura_tpu.ops.pallas_sketch import count_sketch_pallas

# leaf.femnist.baseline, flattened (models/cnn.py): the flagship's P.
FEMNIST_CNN_PARAMS = 6_603_710
# Compiled-mode envelope of the circulant kernels: N % 128 == 0.
NODES = 128
# k-regular(4) neighbor offsets of the flagship topology.
OFFSETS = (1, 2, NODES - 2, NODES - 1)
# aggregation/sketchguard.py default sketch_size.
SKETCH_SIZE = 1000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but can never be read back without a chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _np_operand(one_chip, n=NODES, p=FEMNIST_CNN_PARAMS, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((n, p), dtype, sharding=one_chip)


def _compiled_text(lowered) -> str:
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


def test_model_width_is_the_flagships():
    # The P the kernels are compiled at must be the registered model's own
    # width, not a constant that drifted from it.
    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.flatten import model_dimension

    model = build_model("leaf.femnist.baseline", {})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model_dimension(shapes) == FEMNIST_CNN_PARAMS


# bf16 is the resident param dtype from 64 nodes up (tpu.param_dtype auto):
# the circulant kernels then stream bf16 blocks and widen them in VMEM.
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)


@DTYPES
def test_circulant_distance_kernel_compiles(one_chip, dtype):
    x = _np_operand(one_chip, dtype=dtype)
    _compiled_text(
        pallas_agg._circ_dist_call.lower(x, x, offsets=OFFSETS, interpret=False)
    )


def test_pairwise_distance_kernel_compiles(one_chip):
    x = _np_operand(one_chip)
    _compiled_text(pallas_agg._pairwise_call.lower(x, x, interpret=False))


@DTYPES
@pytest.mark.parametrize(
    "median,trim", [(True, 0), (False, 1)], ids=["median", "trimmed_mean"]
)
def test_candidate_select_kernel_compiles(one_chip, median, trim, dtype):
    x = _np_operand(one_chip, dtype=dtype)
    _compiled_text(
        pallas_agg._candidate_call.lower(
            x, x, offsets=OFFSETS, trim=trim, median=median, interpret=False
        )
    )


@pytest.mark.parametrize(
    "shape,dtype",
    [
        ((FEMNIST_CNN_PARAMS,), jnp.float32),  # a [P] vector: the N = 1 case
        # cnn_sketchguard_er_n64's own operand: 64 bf16-resident nodes
        ((64, FEMNIST_CNN_PARAMS), jnp.bfloat16),
        # the paper's 20-node jobs keep float32 states: three bf16 parts
        ((20, FEMNIST_CNN_PARAMS), jnp.float32),
        # more float32 rows than one block holds
        ((NODES, FEMNIST_CNN_PARAMS), jnp.float32),
    ],
    ids=["vector_f32", "cell_n64_bf16", "paper_n20_f32", "n128_f32"],
)
def test_count_sketch_kernel_compiles(one_chip, shape, dtype):
    p = FEMNIST_CNN_PARAMS
    rows = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    hashes = jax.ShapeDtypeStruct((p,), jnp.int32, sharding=one_chip)
    signs = jax.ShapeDtypeStruct((p,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        count_sketch_pallas.lower(
            rows, hashes, signs, sketch_size=SKETCH_SIZE, interpret=False
        )
    )
    n_rows = shape[0] if len(shape) == 2 else 1
    if n_rows % 128:
        # The kernel reads the states where they lie, in their own dtype:
        # no pad, no float32 copy, no relayout of the tables.  (At a
        # multiple of 128 rows the standalone compile picks a column-major
        # entry layout and copies once, as for the kernels below.)
        assert " copy(" not in text and " pad(" not in text, text


# The flagship width rounded up to the 128-lane tile.  At an unaligned
# width the *standalone* compile picks a column-major entry layout and
# copies each operand once to feed the kernel — XLA's layout choice for a
# program whose only op is the kernel, not a pad in our code; at an aligned
# width the operands stream straight from the arguments.
ALIGNED_PARAMS = -(-FEMNIST_CNN_PARAMS // 128) * 128


def _lower_kernel(kernel, x, n):
    offsets = (1, 2, n - 2, n - 1)
    if kernel == "circ_dist":
        return pallas_agg._circ_dist_call.lower(
            x, x, offsets=offsets, interpret=False
        )
    if kernel == "pairwise":
        return pallas_agg._pairwise_call.lower(x, x, interpret=False)
    return pallas_agg._candidate_call.lower(
        x, x, offsets=offsets, trim=0, median=True, interpret=False
    )


@pytest.mark.parametrize("kernel", ["circ_dist", "pairwise", "median"])
def test_kernel_holds_no_operand_copy(one_chip, kernel):
    # The [N, P] operands stream through unpadded (the grid is cdiv(P,
    # chunk) with the tail masked in VMEM): beyond arguments and output the
    # program holds nothing of operand size.  The _pad_cols copies this
    # replaced cost ~1.5 GB of HLO temp here and refused N=256 outright.
    x = _np_operand(one_chip, p=ALIGNED_PARAMS)
    compiled = _lower_kernel(kernel, x, NODES).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("kernel", ["circ_dist", "pairwise"])
def test_north_star_shape_fits_hbm(one_chip, kernel):
    # N=256 at the flagship width (docs/PERFORMANCE.md north-star shape):
    # two 6.8 GB f32 operands fit one v5e chip's 15.75 GB only because the
    # distance kernels allocate nothing else of that size.  (The candidate
    # kernel also writes an [N, P] output: three such tensors cannot fit.)
    x = _np_operand(one_chip, n=256, p=ALIGNED_PARAMS)
    _compiled_text(_lower_kernel(kernel, x, 256))
