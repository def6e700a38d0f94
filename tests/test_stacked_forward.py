"""The CNNs' stacked forward (``Model.apply_stacked``, models/cnn.py): the
convolution stack of N nodes at once with the node folded into the channel
axis, against ``vmap(apply)``, which it replaces in local SGD and eval
(core/rounds.py) wherever a model offers it.

Same operations, another shape: forwards are held equal to the last bit in
float32 and gradients within one bf16 ulp of a leaf's largest entry (the
backward convolutions may sum in another order); a job's end state is held
equal between the two paths alone, under an outer ``vmap`` (a gang) and
under a mesh that shards the node axis.  The structural tests read the
lowered round and eval steps: the CNN's hold no 5-D pooling and no 5-D
float32 transpose, and a model without ``apply_stacked`` lowers to the
``vmap(grad)`` it lowered to before.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh

from murmura_tpu.config import Config
from murmura_tpu.models.cnn import (
    FEMNIST_VARIANTS,
    make_celeba_cnn,
    make_femnist_cnn,
)
from murmura_tpu.models import cnn
from murmura_tpu.models.core import nodes_a_group
from murmura_tpu.parallel import mesh as mesh_mod
from murmura_tpu.utils import factories
from murmura_tpu.utils.factories import (
    build_gang_from_config,
    build_network_from_config,
)

BF16_ULP = 2.0 ** -8  # of a value in [1, 2): one part in 256 of the largest
F32_SUM = 2.0 ** -18  # a float32 product of some thousand terms, resummed


def _make(kind, compute_dtype):
    # Small images keep the large variants' first dense layer a test's size;
    # channels, kernels and the pooling pattern are the variant's own.
    if kind == "celeba":
        return make_celeba_cnn(image_size=16, compute_dtype=compute_dtype)
    return make_femnist_cnn(variant=kind, image_size=12, compute_dtype=compute_dtype)


def _inputs(model, n, batch=4):
    params = jax.vmap(model.init)(jax.random.split(jax.random.PRNGKey(0), n))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, batch) + tuple(model.input_shape))
    y = jax.random.randint(jax.random.PRNGKey(2), (n, batch), 0, model.num_classes)
    return params, x, y


def _loss(logits, y):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, y[:, None], -1).mean()


KINDS = list(FEMNIST_VARIANTS) + ["celeba"]


def _narrowest(kind):
    return 32 if kind == "celeba" else min(FEMNIST_VARIANTS[kind][0])


# The rule's table (narrowest output of the stack, nodes one device holds ->
# nodes a group): four, or two where four do not divide, from a quarter of
# half a tile (16 channels) to under half a tile (64); one elsewhere.
@pytest.mark.parametrize("narrowest, held, want", [
    (8, 1, 1), (8, 4, 1), (8, 16, 1), (8, 64, 1), (15, 4, 1),
    (16, 1, 1), (16, 2, 2), (16, 4, 4), (16, 8, 4), (16, 64, 4),
    (32, 1, 1), (32, 2, 2), (32, 3, 1), (32, 4, 4), (32, 6, 2), (32, 8, 4), (32, 64, 4),
    (48, 4, 4), (63, 12, 4), (64, 2, 1), (64, 3, 1), (64, 4, 1), (64, 64, 1),
    (128, 4, 1), (256, 64, 1),
])
def test_nodes_a_group_follows_the_shapes(narrowest, held, want):
    assert nodes_a_group(held, narrowest) == want


@pytest.mark.parametrize("kind, want", [
    ("tiny", 1), ("small", 4), ("baseline", 4), ("large", 1), ("xlarge", 1), ("celeba", 4)])
def test_nodes_a_group_of_each_model(kind, want):
    """What the chip measured a local-SGD step at (PERF.md §6 PR 33): the
    stacks under half a tile gain at four, the 64-channel ones do not."""
    assert nodes_a_group(64, _narrowest(kind)) == want


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_a_group_stays_inside_one_device():
    """While a program jitted over a mesh is traced the rule is given the
    nodes one device holds, not the network's; outside, the network's."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    held = lambda: [mesh_mod.nodes_a_device(n) for n in (16, 8, 6)]
    with mesh_mod.param_axis_scope(mesh):
        assert held() == [4, 2, 1]  # 6 nodes do not split four ways
        assert mesh_mod.active_param_scope() is None  # no param axis: identity
    assert held() == [16, 8, 6]
    assert [nodes_a_group(h, 32) for h in (4, 2, 1)] == [4, 2, 1]


@pytest.fixture(scope="module")
def drawn():
    """One draw of parameters and inputs a kind, cut to its first n nodes:
    ``init`` does not read the compute dtype."""
    cache = {}

    def draw(kind, n):
        if kind not in cache:
            cache[kind] = _inputs(_make(kind, "float32"), 8)
        return jax.tree_util.tree_map(lambda l: l[:n], cache[kind])

    return draw


def _run(f):
    """``f`` as one program that rounds where its source rounds: left to
    itself the CPU compiler takes a cast to bf16 and back for no cast
    (operation by operation gives the same numbers in ten times the time)."""

    def call(*args):
        return jax.jit(f).lower(*args).compile(
            compiler_options={"xla_allow_excess_precision": False})(*args)

    return call


# Nodes a group by N: 1 (``apply``'s case), 3 (neither four nor two divide
# it), 4 (one group of four), 8 (two groups of four); the 8-channel stack
# and the 64-channel ones keep one node a group throughout.
@pytest.mark.parametrize("n", [1, 3, 4, 8])
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", KINDS)
def test_stacked_forward_and_gradients_match_vmap(kind, compute_dtype, n, drawn):
    model = _make(kind, compute_dtype)
    params, x, y = drawn(kind, n)
    per = nodes_a_group(n, _narrowest(kind))
    # One node a group repeats vmap's products; several sum a product's
    # terms in another order, and a rounding to bf16 may then fall the
    # other way: one more part in 256 of the largest value.
    ulp = BF16_ULP * (1 if per == 1 else 2)

    # Outputs and gradients of each path from one program.
    (_, stacked), g_stacked = _run(jax.value_and_grad(
        lambda p: (lambda out: (jax.vmap(_loss)(out, y).sum(), out))(
            model.apply_stacked(p, x, None, True)), has_aux=True))(params)
    (_, vmapped), g_vmapped = _run(jax.vmap(jax.value_and_grad(
        lambda p, xi, yi: (lambda out: (_loss(out, yi), out))(
            model.apply(p, xi, None, True)), has_aux=True)))(params, x, y)
    assert stacked.shape == (n, x.shape[1], model.num_classes)
    if compute_dtype == "float32" and per == 1:
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(vmapped))
    else:
        np.testing.assert_allclose(
            np.asarray(stacked), np.asarray(vmapped), rtol=0,
            atol=(F32_SUM if compute_dtype == "float32" else ulp)
            * float(jnp.abs(vmapped).max()),
        )
    # One node of the stacked forward is that node's apply.
    one = jax.tree_util.tree_map(lambda l: l[0], params)
    np.testing.assert_allclose(
        np.asarray(stacked[0]), np.asarray(_run(
            lambda p, xi: model.apply(p, xi, None, True))(one, x[0])),
        atol=ulp * float(jnp.abs(stacked).max()), rtol=0,
    )
    for a, b in zip(jax.tree_util.tree_leaves(g_stacked),
                    jax.tree_util.tree_leaves(g_vmapped)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            atol=ulp * float(jnp.abs(b).max()), rtol=0,
        )


def test_stacked_forward_batches_under_vmap():
    """A gang vmaps the round over members: the fold, and the packing of
    four nodes a group, must batch."""
    model = _make("baseline", "bfloat16")
    params, x, _ = _inputs(model, 4)
    gang = lambda t: jnp.stack([t, t[::-1]])
    out = jax.vmap(lambda p, xi: model.apply_stacked(p, xi, None, False))(
        jax.tree_util.tree_map(gang, params), gang(x)
    )
    alone = model.apply_stacked(params, x, None, False)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(alone[::-1]))


# --- a node at fault stays alone ----------------------------------------------


@pytest.fixture(scope="module")
def four_in_a_group():
    """``baseline`` at N = 4, one group of four: outputs and gradients of
    the stacked path and of ``vmap(apply)``, each compiled once."""
    model = _make("baseline", "bfloat16")
    params, x, y = _inputs(model, 4)
    assert nodes_a_group(4, _narrowest("baseline")) == 4
    once = lambda f, *args: jax.jit(f).lower(*args).compile(  # as ``_run``
        compiler_options={"xla_allow_excess_precision": False})
    stacked = once(jax.value_and_grad(
        lambda p, x: (lambda out: (jax.vmap(_loss)(out, y).sum(), out))(
            model.apply_stacked(p, x, None, True)), has_aux=True), params, x)
    vmapped = once(jax.vmap(jax.value_and_grad(
        lambda p, xi, yi: (lambda out: (_loss(out, yi), out))(
            model.apply(p, xi, None, True)), has_aux=True)), params, x, y)
    return params, x, stacked, (lambda p, x: vmapped(p, x, y))


def _plant(params, x, where, value, node=1):
    """One entry of ``node``'s leaf ``where`` (or of its images) set to
    ``value``."""
    if where == "images":
        return params, x.at[node, 0, 3, 3, 0].set(value)
    group, layer, leaf = where
    params = jax.tree_util.tree_map(lambda l: l, params)
    old = params[group][layer][leaf]
    params[group][layer][leaf] = old.at[(node,) + (0,) * (old.ndim - 1)].set(value)
    return params, x


# Where the fault enters: the convolutions' input (forward: the zero blocks
# of the group's kernel would multiply it), their kernels and biases, and
# the dense stack behind them (backward only: the stack's forward is finite
# and the cotangent that comes back is not).
@pytest.mark.parametrize("value", [np.inf, -np.inf, np.nan], ids=["inf", "-inf", "nan"])
@pytest.mark.parametrize("where", [
    "images", ("convs", 0, "w"), ("convs", 1, "w"), ("convs", 1, "b"),
    ("fcs", 0, "w"), ("fcs", 1, "w"),
], ids=lambda w: w if isinstance(w, str) else "{}{}.{}".format(*w))
def test_a_node_at_fault_stays_alone(four_in_a_group, where, value):
    """One node of a group of four holds a value that is not finite: the
    other three's outputs and gradients are ``vmap(apply)``'s, as under one
    node a group (0 * inf is NaN: the zero blocks must never meet it), and
    the node at fault ends non-finite where ``vmap(apply)`` ends it so, for
    ``faults.nan_quarantine`` to find it alone."""
    params, x, stacked, vmapped = four_in_a_group
    params, x = _plant(params, x, where, value)
    (_, out_s), grad_s = stacked(params, x)
    (_, out_v), grad_v = vmapped(params, x)
    others = np.array([0, 2, 3])
    f32 = lambda l: np.asarray(l, np.float32)
    np.testing.assert_allclose(
        f32(out_s)[others], f32(out_v)[others], rtol=0,
        atol=2 * BF16_ULP * float(np.abs(f32(out_v)[others]).max()))
    at_fault = {"stacked": True, "vmapped": True}
    for a, b in zip(jax.tree_util.tree_leaves(grad_s), jax.tree_util.tree_leaves(grad_v)):
        a, b = f32(a), f32(b)
        assert np.isfinite(a[others]).all()
        np.testing.assert_allclose(
            a[others], b[others], rtol=0, atol=2 * BF16_ULP * np.abs(b[others]).max())
        at_fault["stacked"] &= bool(np.isfinite(a[1]).all())
        at_fault["vmapped"] &= bool(np.isfinite(b[1]).all())
    assert at_fault["stacked"] == at_fault["vmapped"]
    assert np.isfinite(f32(out_s)[1]).all() == np.isfinite(f32(out_v)[1]).all()


# --- whole jobs: the two paths through local SGD and eval --------------------


def _raw(**overrides):
    raw = {
        "experiment": {"name": "stacked", "seed": 1, "rounds": 2},
        "topology": {"type": "ring", "num_nodes": 4},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        # Equal shards: a gang's members share one compiled program.
        "data": {"adapter": "leaf.femnist",
                 "params": {"num_samples": 96, "partition_method": "iid"}},
        "model": {"factory": "leaf.femnist.tiny", "params": {}},
        "backend": "simulation",
        "tpu": {"compute_dtype": "bfloat16"},
    }
    raw.update(overrides)
    return raw


# 32 and 64 channels: four nodes a group (``leaf.femnist.tiny``, the other
# jobs' model, has 8 and 16 and keeps one).
PACKED = {"factory": "leaf.femnist.baseline", "params": {}}
MLP = dict(
    model={"factory": "mlp",
           "params": {"input_dim": 10, "hidden_dims": [16], "num_classes": 3}},
    data={"adapter": "synthetic",
          "params": {"num_samples": 96, "input_dim": 10, "num_classes": 3}},
)
LSTM = dict(
    model={"factory": "leaf.shakespeare",
           "params": {"embed_dim": 4, "hidden": 8, "num_layers": 1, "seq_len": 6}},
    data={"adapter": "leaf.shakespeare", "params": {"num_samples": 96, "seq_len": 6}},
)


def _build(raw, folded=True, builder=build_network_from_config):
    """The job's network; with ``folded`` false every model is built
    without its stacked forward: the path a model that offers none takes,
    and the CNNs took before they offered one."""
    config = Config.model_validate(raw)
    if folded:
        return builder(config)
    build, built = factories.build_model, []

    def without(*args, **kwargs):
        built.append(dataclasses.replace(build(*args, **kwargs), apply_stacked=None))
        return built[-1]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(factories, "build_model", without)
        net = builder(config)
    assert built
    return net


def _end_state(net, rounds=2, **train):
    history = net.train(rounds=rounds, eval_every=1, **train)
    return history, [np.asarray(l, np.float32)
                     for l in jax.tree_util.tree_leaves(net.params)]


def _assert_close(got, want):
    """Within one bf16 ulp of each leaf's (or metric's) largest entry."""
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=BF16_ULP * np.abs(b).max(), rtol=0)


def _assert_histories_close(got, want):
    for key in ("mean_loss", "mean_accuracy"):
        _assert_close([np.asarray(got[key])], [np.asarray(want[key])])


@pytest.mark.parametrize("model", [_raw()["model"], PACKED], ids=["tiny", "baseline"])
def test_job_end_state_matches_vmap_path(model):
    folded = _end_state(_build(_raw(model=model)))
    history, state = _end_state(_build(_raw(model=model), folded=False))
    _assert_close(folded[1], state)
    _assert_histories_close(folded[0], history)


@pytest.mark.parametrize("quarantine", [True, False], ids=["sentinel", "no-sentinel"])
def test_one_diverged_node_is_one_quarantined(quarantine):
    """A node of a packed group diverges in local SGD (a kernel entry near
    float32's largest, so its products overflow): with ``faults.nan_quarantine`` the sentinel counts one node a
    round, not the group's four, and the other three train as they do
    beside a healthy node; without it the exchange spreads the node's row
    to the nodes it reaches under ``vmap(apply)``."""
    raw = _raw(model=PACKED, faults={"enabled": True, "nan_quarantine": quarantine},
               experiment={"name": "stacked", "seed": 1, "rounds": 1})

    def trained(planted, folded=True):
        net = _build(raw, folded)
        if planted:
            net.params = _plant(net.params, None, ("convs", 1, "w"), 3e38)[0]
        return _end_state(net, rounds=1)

    history, state = trained(True)
    if not quarantine:
        _, vmapped = trained(True, folded=False)
        whole = lambda leaves: np.all(
            [np.isfinite(l).reshape(4, -1).all(axis=1) for l in leaves], axis=0)
        np.testing.assert_array_equal(whole(state), whole(vmapped))
        return
    assert history["agg_quarantined"] == [1.0]
    _, healthy = trained(False)
    kernel = [l for l in state if l.ndim == 5 and l.shape[-1] == 64][0]
    assert kernel[1].max() > 1e38  # rolled back to what it started from
    # Nodes 0 and 2 are the ring's neighbours of node 1 and aggregate
    # without it; node 3 never hears from it and ends where it ends
    # beside a healthy node 1.
    for a, b in zip(state, healthy):
        assert np.isfinite(a[[0, 2, 3]]).all()
        np.testing.assert_allclose(a[3], b[3], atol=BF16_ULP * np.abs(b[3]).max(), rtol=0)


def test_job_under_a_gang_matches_vmap_path():
    """``vmap`` over two members multiplies the group count: each member's
    end state is what the vmapped path gives that member."""
    raw = _raw(sweep={"seeds": [1, 2]})
    gangs = [_build(raw, folded, build_gang_from_config) for folded in (True, False)]
    histories = [g.train(rounds=2, eval_every=1) for g in gangs]
    for leaf_f, leaf_v in zip(jax.tree_util.tree_leaves(gangs[0].params),
                              jax.tree_util.tree_leaves(gangs[1].params)):
        assert leaf_f.shape[:2] == (2, 4)
        _assert_close([np.asarray(leaf_f, np.float32)], [np.asarray(leaf_v, np.float32)])
    for member in range(2):
        _assert_histories_close(histories[0][member], histories[1][member])


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@pytest.mark.parametrize("model, nodes", [(_raw()["model"], 4), (PACKED, 16)],
                         ids=["tiny-4", "baseline-16"])
def test_job_on_a_mesh_matches_one_device(model, nodes):
    """Node axis sharded over a forced 4-device CPU mesh: the folded axis is
    block-sharded by node (at 16 nodes a device's four are one group of the
    convolutions), and the run equals the unsharded one."""
    runs = {}
    for devices in (1, 4):
        # float32 products: sharding is what differs, and at bf16 a node's
        # convolution alone rounds otherwise than the same node's in a group.
        raw = _raw(backend="tpu", model=model,
                   topology={"type": "ring", "num_nodes": nodes},
                   tpu={"compute_dtype": "float32", "num_devices": devices})
        net = _build(raw)
        assert dict(net.mesh.shape)["nodes"] == devices
        runs[devices] = _end_state(net)
    _assert_close(runs[4][1], runs[1][1])
    _assert_histories_close(runs[4][0], runs[1][0])


# --- structure: what the lowered round and eval steps hold -------------------


def _lowered(net):
    """The job's round step and eval step, as jit lowers them."""
    comp = net._stage(net.compromised, net._node_s)
    return (net._step.lower(*net._round_inputs(0, comp)),
            net._eval.lower(net.params, net._data))


_DEF = re.compile(r"^\s*(?:ROOT )?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((?:\w+\[[\d,]*\]\S* )?%?([\w.\-]+)")


def _ops(text, kind):
    """(result dtype, result dims, first operand's dims) of every ``kind``
    operation of an HLO text."""
    dims = lambda s: tuple(int(d) for d in s.split(",") if d)
    defs = [m for m in map(_DEF.match, text.splitlines()) if m]
    shape = {m.group(1): dims(m.group(3)) for m in defs}
    return [(m.group(2), dims(m.group(3)), shape.get(m.group(5)))
            for m in defs if m.group(4) == kind]


def _group_counts(text):
    """Groups of every convolution of an HLO text, forward and backward
    (a weight gradient's are its ``batch_group_count``; HLO prints neither
    count where it is 1)."""
    count = lambda line, key: int((re.search(key + r"=(\d+)", line) or [0, 1])[1])
    return [max(count(line, "feature_group_count"), count(line, "batch_group_count"))
            for line in text.splitlines() if " convolution(" in line]


def _activation_transposes(text, batch, nodes, channels):
    """5-D transposes of what lies between the two convolutions of a FEMNIST
    CNN (28x28 in): everything but the input batch, the kernels and the last
    pool's output."""
    first, second = channels
    between = {tuple(sorted((nodes, batch, hw, hw, c)))
               for hw, c in ((28, first), (14, first), (14, second))}
    return [op for op in _ops(text, "transpose")
            if len(op[1]) == 5 and tuple(sorted(op[1])) in between]


@pytest.fixture(scope="module")
def cnn_steps():
    cache = {}

    def steps(variant, nodes, folded):
        if (variant, nodes, folded) not in cache:
            raw = _raw(model={"factory": f"leaf.femnist.{variant}", "params": {}},
                       topology={"type": "ring", "num_nodes": nodes})
            cache[variant, nodes, folded] = [
                low.as_text(dialect="hlo") for low in _lowered(_build(raw, folded))]
        return cache[variant, nodes, folded]

    return steps


# ``tiny`` (8 and 16 channels) keeps a node a group; ``baseline`` (32 and
# 64) takes four: one group at 4 nodes, two at 8.
@pytest.mark.parametrize("variant, nodes, groups", [
    ("tiny", 4, 4), ("baseline", 4, 1), ("baseline", 8, 2)])
@pytest.mark.parametrize("program", ["round step", "eval step"])
def test_cnn_steps_keep_the_convolution_stack_folded(
        cnn_steps, program, variant, nodes, groups):
    which = ("round step", "eval step").index(program)
    channels = FEMNIST_VARIANTS[variant][0]
    text = cnn_steps(variant, nodes, True)[which]
    assert set(_group_counts(text)) == {groups}
    pools = _ops(text, "reduce-window") + _ops(text, "select-and-scatter")
    assert pools and all(len(dims) == 4 for _, dims, _ in pools)
    assert all(dims[-1] in (nodes * channels[0], nodes * channels[1])
               for _, dims, _ in pools)
    batch = pools[0][1][0]
    assert _activation_transposes(text, batch, nodes, channels) == []
    # The vmapped path is what these assertions are about: a node a group,
    # pooling in 5-D between two transposes of each activation.
    vmapped = cnn_steps(variant, nodes, False)[which]
    assert set(_group_counts(vmapped)) == {nodes}
    assert any(len(dims) == 5 for _, dims, _ in
               _ops(vmapped, "reduce-window") + _ops(vmapped, "select-and-scatter"))
    assert _activation_transposes(vmapped, batch, nodes, channels)


def test_one_node_lowers_to_the_ungrouped_convolution():
    """``apply`` (the ZMQ backend's forward, N = 1) packs nothing: its
    lowered program is one node a group's, text for text."""
    model = _make("baseline", "bfloat16")
    params, x, _ = _inputs(model, 4)
    one = jax.tree_util.tree_map(lambda l: l[0], params)

    def lowered(args, stacked=False, **text):
        # A new function a call: jit keeps a trace by function.
        if stacked:
            return jax.jit(lambda p, xi: model.apply_stacked(p, xi, None, True)
                           ).lower(*args).as_text(**text)
        return jax.jit(lambda p, xi: model.apply(p, xi, None, True)
                       ).lower(*args).as_text(**text)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cnn, "nodes_a_group", lambda *shapes: 1)
        alone = lowered((one, x[0]))
        four_alone = lowered((params, x), stacked=True)
    assert lowered((one, x[0])) == alone
    assert "murmura.pack" not in lowered((one, x[0]), debug_info=True)
    # Four nodes are packed, and the text differs from one node a group's.
    assert "murmura.pack" in lowered((params, x), stacked=True, debug_info=True)
    assert lowered((params, x), stacked=True) != four_alone


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@pytest.mark.parametrize("program", ["round step", "eval step"])
def test_a_sharded_node_axis_gains_no_collective(program):
    """16 nodes over a forced 4-device CPU mesh, four a device, so that the
    groups of four are the devices' own blocks: the compiled step holds no
    kind of collective that one node a group's does not.  (Kinds, not
    counts: the partitioner splits a grouped convolution by group only at
    one input channel a group and gathers the operands of every other,
    one node a group's second convolution included; PERF.md §7.)"""
    from murmura_tpu.analysis.ir import collective_names

    which = ("round step", "eval step").index(program)
    raw = _raw(backend="tpu", model=PACKED,
               topology={"type": "ring", "num_nodes": 16},
               tpu={"compute_dtype": "bfloat16", "num_devices": 4})

    def compiled(rule):
        with pytest.MonkeyPatch.context() as patch:
            if rule is not None:
                patch.setattr(cnn, "nodes_a_group", rule)
            return _lowered(_build(raw))[which].compile().as_text()

    packed, alone = compiled(None), compiled(lambda *shapes: 1)
    assert "feature_group_count=16" in alone
    assert "feature_group_count=16" not in packed
    assert "feature_group_count=4" in packed
    assert collective_names(packed) == collective_names(alone) == {"all_gather"}


def _products(lowered):
    """(operand and result types, label) of every ``dot_general``."""
    text = lowered.as_text(debug_info=True)
    label = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    return [(types, label.get(loc, "")) for types, loc in re.findall(
        r"stablehlo\.dot_general .* : (\(.*\) -> \S+) loc\((#loc\d+)\)", text)]


@pytest.mark.parametrize("job", [MLP, LSTM], ids=["mlp", "char-lstm"])
def test_models_without_convolutions_lower_to_vmap_grad(job):
    """No stacked forward, no grouped convolution, and the round step's
    products outside the other stages' labels (the loop's body is a
    function of its own and carries none) are ``vmap(grad)``'s, one for one."""
    from murmura_tpu.ops.losses import masked_cross_entropy

    net = _build(_raw(**job))
    model = factories.build_model(job["model"]["factory"], dict(job["model"]["params"]))
    assert model.apply_stacked is None
    step, evaluate = _lowered(net)
    for text in (step.as_text(dialect="hlo"), evaluate.as_text(dialect="hlo")):
        assert _group_counts(text) == []

    def node_loss(p, x, y, m, key):
        return masked_cross_entropy(model.apply(p, x, key, True), y, m)[0]

    d, batch = net._data, 8
    by_hand = jax.jit(jax.vmap(jax.grad(node_loss))).lower(
        net.params, d["x"][:, :batch], d["y"][:, :batch], d["mask"][:, :batch],
        jax.random.split(jax.random.PRNGKey(0), 4),
    )
    in_loop = sorted(types for types, label in _products(step) if "murmura." not in label)
    assert in_loop and in_loop == sorted(types for types, _ in _products(by_hand))
