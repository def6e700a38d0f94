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

import re

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
def four_chips():
    """The four described devices of one v5e host."""
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
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


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


# --- the CNNs' node-folded convolution stack (models/core.py) ---------------


def _stacked_sgd_gradients(nodes, batch, place):
    """Lowered gradients of ``leaf.femnist.baseline``'s stacked forward (bf16
    compute, bf16-resident parameters, as ``cnn_sketchguard_er_n64`` trains),
    its arguments placed by ``place(shape, dtype)``."""
    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.losses import masked_cross_entropy

    model = build_model("leaf.femnist.baseline", {"compute_dtype": "bfloat16"})
    shapes = jax.eval_shape(
        jax.vmap(model.init), jax.random.split(jax.random.PRNGKey(0), nodes))
    params = jax.tree_util.tree_map(
        lambda l: place(l.shape, jnp.bfloat16), shapes)

    def gradients(params, x, y, mask):
        def loss(p):
            p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
            out = model.apply_stacked(p, x, None, True)
            per_node = jax.vmap(lambda o, yy, mm: masked_cross_entropy(o, yy, mm)[0])
            return per_node(out, y, mask).sum()

        return jax.grad(loss)(params)

    return gradients, (params,
                       place((nodes, batch, 28, 28, 1), jnp.float32),
                       place((nodes, batch), jnp.int32),
                       place((nodes, batch), jnp.float32))


def _conv_activations(text):
    """Result shapes of the compiled program's 5-D operations over a batch
    of images: what the grouped-convolution emitter keeps between the
    convolutions, [B, H, W, G, C/G]."""
    dims = (tuple(int(d) for d in m.group(1).split(","))
            for m in re.finditer(r"= \w+\[(\d+,\d+,\d+,\d+,\d+)\]\{", text))
    return {d for d in dims if d[1] == d[2] and d[1] in (28, 14, 7)}


def test_folded_stack_fills_the_lanes(one_chip):
    """Four nodes a group at 32 channels: every activation the TPU compiler
    keeps between the convolutions has 128 lanes or more (one node a group
    keeps ``[B, H, W, 64, 32]``, a quarter of each tile: PERF.md §6 PR 33)."""
    from murmura_tpu.models import cnn

    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    gradients, args = _stacked_sgd_gradients(16, 8, place)
    packed = _conv_activations(jax.jit(gradients).lower(*args).compile().as_text())
    # 16 nodes in 4 groups: beside the input (4 nodes x 1 channel) a group is
    # 128 or 256 channels wide, and nothing is 32 wide any more.  (What stays
    # [B, H, W, 16, 64] is the unfold to a node's rows for the dense stack.)
    groups = {d for d in packed if d[3] == 4 and d[4] != 4}
    assert groups and all(d[4] in (128, 256) for d in groups), packed
    assert not any(d[4] == 32 for d in packed), packed
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cnn, "nodes_a_group", lambda *shapes: 1)
        alone = _conv_activations(  # a new function: jit keeps a trace by function
            jax.jit(lambda *a: gradients(*a)).lower(*args).compile().as_text())
    assert any(d[3] == 16 and d[4] == 32 for d in alone), alone


def test_folded_stack_stays_on_its_chip(four_chips):
    """The node axis over the host's four chips, four nodes a chip: a group
    of four is a chip's own block, and forward and backward of the stack
    compile to no collective at all."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from murmura_tpu.analysis.ir import collective_names
    from murmura_tpu.parallel import mesh as mesh_mod

    mesh = Mesh(np.array(four_chips), ("nodes",))
    by_node = NamedSharding(mesh, PartitionSpec("nodes"))
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=by_node)
    gradients, args = _stacked_sgd_gradients(16, 8, place)
    def scoped(*args):  # as the programs jitted in parallel/mesh.py are traced
        with mesh_mod.param_axis_scope(mesh):
            return gradients(*args)

    lowered = jax.jit(scoped, out_shardings=by_node).lower(*args)
    assert "feature_group_count = 4 " in lowered.as_text()  # 16 nodes, 4 a group
    assert collective_names(lowered.compile().as_text()) == frozenset()


def _collectives(text):
    """(kind, result type) of every collective of a compiled program."""
    kinds = "all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter"
    return sorted(
        (m.group(2), m.group(1)) for m in re.finditer(
            r"= (\w+\[[\d,]*\])\S* (%s)\(" % kinds, text))


def test_sharded_round_step_gains_no_collective(four_chips):
    """The whole round step (local SGD of ``leaf.femnist.baseline``, flatten,
    exchange, FedAvg, metrics) with 16 nodes over the host's four chips, as
    ``shard_step`` jits it: with four nodes a group it holds no collective
    that one node a group's does not, and none inside the local-SGD loop.
    (One node a group's holds one there: the partitioner gathers the first
    convolution's input; PERF.md §7.)"""
    import numpy as np
    from jax.sharding import Mesh

    from murmura_tpu.config import Config
    from murmura_tpu.models import cnn
    from murmura_tpu.parallel import mesh as mesh_mod
    from murmura_tpu.utils.factories import build_network_from_config

    net = build_network_from_config(Config.model_validate({
        "experiment": {"name": "mesh", "seed": 1, "rounds": 1},
        "topology": {"type": "ring", "num_nodes": 16},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "leaf.femnist",
                 "params": {"num_samples": 96, "partition_method": "iid"}},
        "model": {"factory": "leaf.femnist.baseline", "params": {}},
        "backend": "simulation",
        "tpu": {"compute_dtype": "bfloat16"},
    }))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
        net._round_inputs(0, net.compromised))
    mesh = Mesh(np.array(four_chips), ("nodes",))

    def compiled():  # a new jit a call: the rule is read while it is traced
        step = mesh_mod.shard_step(
            net.program.train_step, net.program, mesh, donate=False)
        return step.lower(*args).compile().as_text()

    packed = compiled()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cnn, "nodes_a_group", lambda *shapes: 1)
        alone = compiled()
    assert "murmura.pack" in packed and "murmura.pack" not in alone
    mine, parents = _collectives(packed), _collectives(alone)
    assert mine and not [c for c in mine if c not in parents], (mine, parents)
    assert len(mine) <= len(parents)
    in_loop = [line for line in packed.splitlines()
               if "murmura.train" in line and _collectives(line)]
    assert in_loop == []


# --- the decoder's expert layer at Moonlight's widths (models/decoder.py) ---


def test_decoder_expert_layer_step_compiles_and_fits(one_chip):
    """One expert layer of ``moonlight_16b_a3b_ep8`` (hidden 2048, latent
    attention of 16 heads, 64 experts routed and 8 held, 4,096 positions;
    a 1,024-row vocabulary so that the layer is what is compiled), forward,
    recomputed and backward with bf16-resident parameters: the TPU compiler
    takes the grouped products as its own kernel (``tpu_custom_call``: the
    CPU tests see a dense product with masks in their place), once for each
    size the pairs' buffer may take (8,192, 16,384 and 28,672 rows: the
    dispatch is two conditionals, forward and backward), holds no
    ``[heads, T, T]`` score array, and one layer's step (its recomputed
    activations and the longest buffer's branch: 3,500,164,608 B of
    temporaries, 3.26 GiB, when this was written; 3,473,661,440 with the
    one size before) fits in a quarter of the chip."""
    import json
    from pathlib import Path

    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.losses import masked_next_token_cross_entropy

    doc = json.loads((Path(__file__).resolve().parents[1]
                      / "benchmark/configs/moonlight_16b_a3b_ep8.json").read_text())
    params = dict(doc["model"]["params"], num_hidden_layers=1, first_k_dense_replace=0,
                  vocab_size=1024, compute_dtype="bfloat16")
    model = build_model(doc["model"]["factory"], params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    tree = jax.tree_util.tree_map(lambda l: place(l.shape, jnp.bfloat16), shapes)
    assert shapes["moe_layers"]["experts"]["gate"].shape == (1, 8, 2048, 1408)
    assert shapes["moe_layers"]["router"]["w"].shape == (1, 2048, 64)

    def gradients(p, x, y):
        def loss(p):
            logits, aux = model.apply_train(p, x, None)
            return masked_next_token_cross_entropy(
                logits, y, jnp.ones((1,)))[0] + aux["loss"].sum()

        return jax.grad(loss)(p)

    ids = place((1, 4096), jnp.int32)
    compiled = jax.jit(gradients).lower(tree, ids, ids).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text
    kernels = set(re.findall(r"= f32\[(\d+),\d+\]\S* custom-call\([^\n]*ragged-dot", text))
    assert kernels == {"8192", "16384", "28672"}
    assert len(re.findall(r" conditional\(", text)) == 2
    assert not re.search(r"f32\[16,4096,4096\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


# --- one ZAYA1 layer at its published widths (models/zaya.py) ---------------


def test_zaya_layer_step_compiles_and_fits(one_chip):
    """One layer of ``zaya1_8b_ep2`` (hidden 2048, compressed convolutional
    attention of 8 query and 2 key/value heads of 128, a router 256 wide
    over 16 experts, top-1, 8 held experts of 2048, 4,096 positions; a
    1,024-row vocabulary so that the layer is what is compiled), forward,
    recomputed and backward with bf16-resident parameters: the grouped
    products are the TPU compiler's kernel at both sizes the pairs' buffer
    may take at one pair a token (6,144 and 8,192 rows), the dispatch is three
    conditionals (forward, recomputed forward, backward: the residual scale
    on the experts' term needs their result in the backward pass, where the
    decoder's plain sum does not), no ``[heads, T, T]`` score array exists,
    and the layer's
    step (1,037,348,864 B of temporaries, 0.97 GiB, when this was written)
    fits in a tenth of the chip."""
    import json
    from pathlib import Path

    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.losses import masked_next_token_cross_entropy

    doc = json.loads((Path(__file__).resolve().parents[1]
                      / "benchmark/configs/zaya1_8b_ep2.json").read_text())
    params = dict(doc["model"]["params"], num_hidden_layers=1, vocab_size=1024,
                  compute_dtype="bfloat16")
    model = build_model(doc["model"]["factory"], params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    tree = jax.tree_util.tree_map(lambda l: place(l.shape, jnp.bfloat16), shapes)
    assert shapes["layers"]["experts"]["gate"].shape == (1, 8, 2048, 2048)
    assert shapes["layers"]["cca"]["conv1"].shape == (1, 2, 10, 128, 128)
    assert shapes["layers"]["router"]["w3"].shape == (1, 256, 16)

    def gradients(p, x, y):
        def loss(p):
            logits, _ = model.apply_train(p, x, None)
            return masked_next_token_cross_entropy(logits, y, jnp.ones((1,)))[0]

        return jax.grad(loss)(p)

    ids = place((1, 4096), jnp.int32)
    compiled = jax.jit(gradients).lower(tree, ids, ids).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text
    kernels = set(re.findall(r"= f32\[(\d+),\d+\]\S* custom-call\([^\n]*ragged-dot", text))
    assert kernels == {"6144", "8192"}
    assert len(re.findall(r" conditional\(", text)) == 3
    assert not re.search(r"f32\[\d+,\d+,4096,4096\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * 2**30


# --- the decoders' causal attention (ops/attention.py) ----------------------


@pytest.mark.parametrize("config,layer,label", [
    ("moonlight_16b_a3b_ep8", {"num_hidden_layers": 1, "first_k_dense_replace": 1},
     "murmura.attention"),
    ("zaya1_8b_ep2", {"num_hidden_layers": 1}, "murmura.cca"),
], ids=["moonlight", "zaya1"])
def test_decoder_attention_is_the_flash_kernel_under_its_label(
        one_chip, config, layer, label, monkeypatch):
    """One layer of each decoder at its published widths (Moonlight's dense
    layer: 16 heads of 192 against values of 128; ZAYA1's: 8 query and 2
    key/value heads of 128), 4,096 positions, forward, recomputed and
    backward with bf16-resident parameters, as the chip compiles it (the
    backend is the TPU's there, and ``causal_attention`` takes its
    kernels): the forward kernel once (the recomputed layer keeps its
    result and log-sum-exp, ``KEEP_RESIDUALS``, and runs no attention
    forward again), the backward kernel once, both under the sublayer's
    label, the backward's in the transposed pass, so that the device time of
    ``train_attention_ops_ms`` and ``train_cca_ops_ms`` still holds the
    whole sublayer; and no float32 score block ``[heads, 512, <=4096]``
    of the ``jnp`` path is left."""
    import json
    from pathlib import Path

    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.losses import masked_next_token_cross_entropy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    doc = json.loads((Path(__file__).resolve().parents[1]
                      / f"benchmark/configs/{config}.json").read_text())
    params = dict(doc["model"]["params"], vocab_size=1024, compute_dtype="bfloat16", **layer)
    model = build_model(doc["model"]["factory"], params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    tree = jax.tree_util.tree_map(lambda l: place(l.shape, jnp.bfloat16), shapes)

    def gradients(p, x, y):
        def loss(p):
            logits, aux = model.apply_train(p, x, None)
            return masked_next_token_cross_entropy(
                logits, y, jnp.ones((1,)))[0] + aux["loss"].sum()

        return jax.grad(loss)(p)

    ids = place((1, 4096), jnp.int32)
    text = jax.jit(gradients).lower(tree, ids, ids).compile().as_text()
    kernels = sorted(
        (m.group(1), m.group(2)) for m in re.finditer(
            r"%(causal_attention_\w+?)\.\d+ = [^\n]*tpu_custom_call[^\n]*op_name=\"([^\"]*)\"",
            text))
    assert [name for name, _ in kernels] == [
        "causal_attention_bwd", "causal_attention_fwd"], kernels
    assert all(label in op_name for _, op_name in kernels), kernels
    assert "transpose(" in kernels[0][1]
    assert not re.search(r"f32\[[\d,]*,512,\d+\]", text)
