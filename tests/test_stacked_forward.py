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

from murmura_tpu.config import Config
from murmura_tpu.models.cnn import (
    FEMNIST_VARIANTS,
    make_celeba_cnn,
    make_femnist_cnn,
)
from murmura_tpu.utils import factories
from murmura_tpu.utils.factories import (
    build_gang_from_config,
    build_network_from_config,
)

BF16_ULP = 2.0 ** -8  # of a value in [1, 2): one part in 256 of the largest


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


@pytest.mark.parametrize("n", [1, 3])
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", KINDS)
def test_stacked_forward_and_gradients_match_vmap(kind, compute_dtype, n):
    model = _make(kind, compute_dtype)
    params, x, y = _inputs(model, n)

    stacked = model.apply_stacked(params, x, None, True)
    vmapped = jax.vmap(lambda p, xi: model.apply(p, xi, None, True))(params, x)
    assert stacked.shape == (n, x.shape[1], model.num_classes)
    if compute_dtype == "float32":
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(vmapped))
    else:
        np.testing.assert_allclose(
            np.asarray(stacked), np.asarray(vmapped),
            atol=BF16_ULP * float(jnp.abs(vmapped).max()), rtol=0,
        )
    # One node of the stacked forward is that node's apply.
    one = jax.tree_util.tree_map(lambda l: l[0], params)
    np.testing.assert_allclose(
        np.asarray(stacked[0]), np.asarray(model.apply(one, x[0], None, True)),
        atol=BF16_ULP * float(jnp.abs(stacked).max()), rtol=0,
    )

    g_stacked = jax.grad(
        lambda p: jax.vmap(_loss)(model.apply_stacked(p, x, None, True), y).sum()
    )(params)
    g_vmapped = jax.vmap(
        jax.grad(lambda p, xi, yi: _loss(model.apply(p, xi, None, True), yi))
    )(params, x, y)
    for a, b in zip(jax.tree_util.tree_leaves(g_stacked),
                    jax.tree_util.tree_leaves(g_vmapped)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            atol=BF16_ULP * float(jnp.abs(b).max()), rtol=0,
        )


def test_stacked_forward_batches_under_vmap():
    """A gang vmaps the round over members: the fold must batch."""
    model = _make("tiny", "bfloat16")
    params, x, _ = _inputs(model, 3)
    gang = lambda t: jnp.stack([t, t[::-1]])
    out = jax.vmap(lambda p, xi: model.apply_stacked(p, xi, None, False))(
        jax.tree_util.tree_map(gang, params), gang(x)
    )
    alone = model.apply_stacked(params, x, None, False)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(alone[::-1]))


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


@pytest.fixture(scope="module")
def folded_job():
    return _end_state(_build(_raw()))


def test_job_end_state_matches_vmap_path(folded_job):
    history, state = _end_state(_build(_raw(), folded=False))
    _assert_close(folded_job[1], state)
    _assert_histories_close(folded_job[0], history)


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
def test_job_on_a_mesh_matches_one_device():
    """Node axis sharded over a forced 4-device CPU mesh: the folded axis is
    block-sharded by node, and the run equals the unsharded one."""
    runs = {}
    for devices in (1, 4):
        # float32 products: sharding is what differs, and at bf16 a node's
        # convolution alone rounds otherwise than the same node's in a group.
        raw = _raw(backend="tpu", tpu={"compute_dtype": "float32",
                                       "num_devices": devices})
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
    return [int(g) for g in re.findall(r"feature_group_count=(\d+)", text)]


def _activation_transposes(text, batch, nodes=4):
    """5-D transposes of what lies between the convolutions of
    ``leaf.femnist.tiny`` (8 and 16 channels, 28x28 in): everything but
    the input batch, the kernels and the last pool's output."""
    between = {tuple(sorted((nodes, batch, hw, hw, c)))
               for hw, c in ((28, 8), (14, 8), (14, 16))}
    return [op for op in _ops(text, "transpose")
            if len(op[1]) == 5 and tuple(sorted(op[1])) in between]


@pytest.fixture(scope="module")
def cnn_steps():
    return {
        folded: [low.as_text(dialect="hlo") for low in _lowered(_build(_raw(), folded))]
        for folded in (True, False)
    }


@pytest.mark.parametrize("program", ["round step", "eval step"])
def test_cnn_steps_keep_the_convolution_stack_folded(cnn_steps, program):
    which = ("round step", "eval step").index(program)
    text = cnn_steps[True][which]
    assert set(_group_counts(text)) == {4}
    pools = _ops(text, "reduce-window") + _ops(text, "select-and-scatter")
    assert pools and all(len(dims) == 4 for _, dims, _ in pools)
    assert all(dims[-1] in (4 * 8, 4 * 16) for _, dims, _ in pools)
    batch = pools[0][1][0]
    assert _activation_transposes(text, batch) == []
    # The vmapped path is what these assertions are about: it pools in 5-D
    # between two transposes of each activation.
    vmapped = cnn_steps[False][which]
    assert any(len(dims) == 5 for _, dims, _ in
               _ops(vmapped, "reduce-window") + _ops(vmapped, "select-and-scatter"))
    assert _activation_transposes(vmapped, batch)


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
