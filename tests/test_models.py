"""Model registry: forward shapes, param counts, evidential outputs
(reference models: murmura/examples/leaf/{datasets,models}.py,
murmura/examples/wearables/models.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from murmura_tpu.models.registry import build_model
from murmura_tpu.ops.flatten import model_dimension


def _param_count(model):
    return model_dimension(jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def test_model_dimension_counts_only_float_leaves():
    """ISSUE 2 satellite regression: model_dimension's documented contract
    is the *float* parameter count (only float parameters are aggregated —
    the reference skips BatchNorm's integer num_batches_tracked buffers),
    so an integer leaf in an externally supplied pytree must not inflate
    the sketch sizing / model_dim plumbing."""
    tree = {
        "w": np.zeros((4, 5), np.float32),          # 20
        "b": jnp.zeros((5,), jnp.bfloat16),         # 5
        "steps": np.zeros((3,), np.int32),          # int buffer: excluded
        "flag": jnp.zeros((2, 2), jnp.bool_),       # bool buffer: excluded
    }
    assert model_dimension(tree) == 25
    # eval_shape structs carry dtypes too — same filtering applies.
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )
    assert model_dimension(structs) == 25


def test_make_flatteners_rejects_non_float_leaves():
    """The counterpart contract: the [N, P] aggregation pipeline is
    float-only, so a mixed tree must fail loudly at build time (where the
    message can point at the design note) instead of desynchronizing
    model_dimension consumers (sketch table sizing) from the ravelled
    vector, or 'aggregating' integer buffers by means."""
    from murmura_tpu.ops.flatten import make_flatteners

    tree = {"w": np.zeros((4, 5), np.float32), "steps": np.zeros((3,), np.int32)}
    with pytest.raises(TypeError, match="non-float leaves"):
        make_flatteners(tree)
    # Raw Python float leaves stay supported (ravel_pytree accepts them).
    ravel, _, dim = make_flatteners({"w": np.zeros((2,), np.float32), "s": 1.0})
    assert dim == 3
    assert model_dimension({"w": np.zeros((2,), np.float32), "s": 1.0}) == 3


def _forward(model, batch=3):
    params = model.init(jax.random.PRNGKey(0))
    x_shape = (batch,) + tuple(model.input_shape)
    if model.input_shape and model.meta.get("discrete_input"):
        x = jnp.zeros(x_shape, jnp.int32)
    else:
        x = jnp.zeros(x_shape, jnp.float32)
    return model.apply(params, x, jax.random.PRNGKey(1), False)


@pytest.mark.parametrize("factory,params,classes", [
    ("mlp", {"input_dim": 16, "num_classes": 5}, 5),
    ("examples.leaf.LEAFFEMNISTModel", {}, 62),
    ("leaf.femnist.tiny", {}, 62),
    ("leaf.celeba", {}, 2),
    ("examples.wearables.uci_har", {}, 6),
    ("examples.wearables.pamap2", {}, 12),
    ("examples.wearables.ppg_dalia", {}, 7),
])
def test_forward_shape(factory, params, classes):
    model = build_model(factory, params)
    out = _forward(model)
    assert out.shape == (3, classes)
    assert np.isfinite(np.asarray(out)).all()


def test_femnist_variant_scaling():
    # Reference scaling family: Tiny ~200K ... Baseline ~6.5M ... XLarge ~26M
    # (murmura/examples/leaf/models.py:12-216).
    counts = {
        v: _param_count(build_model(f"leaf.femnist.{v}", {}))
        for v in ("tiny", "small", "baseline", "large", "xlarge")
    }
    assert counts["tiny"] < counts["small"] < counts["baseline"] \
        < counts["large"] < counts["xlarge"]
    assert 3e6 < counts["baseline"] < 10e6   # ~6.5M in the reference
    assert counts["xlarge"] > 20e6           # ~26M


def test_wearable_models_are_evidential():
    # Wearable classifiers carry the evidential head: outputs are Dirichlet
    # alphas, all >= 1 (reference: wearables/models.py:18-46, alpha = e + 1).
    model = build_model("examples.wearables.uci_har", {})
    assert model.evidential
    out = _forward(model)
    assert (np.asarray(out) >= 1.0).all()


def test_shakespeare_lstm_forward():
    model = build_model("leaf.shakespeare", {})
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((3, 80), jnp.int32)
    out = model.apply(params, x, None, False)
    assert out.shape == (3, 81)


def test_dropout_only_active_in_train_mode():
    model = build_model("mlp", {"input_dim": 8, "num_classes": 3,
                                "dropout": 0.5})
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((4, 8), jnp.float32)
    eval_a = model.apply(params, x, jax.random.PRNGKey(1), False)
    eval_b = model.apply(params, x, jax.random.PRNGKey(2), False)
    np.testing.assert_array_equal(np.asarray(eval_a), np.asarray(eval_b))
    train_a = model.apply(params, x, jax.random.PRNGKey(1), True)
    train_b = model.apply(params, x, jax.random.PRNGKey(2), True)
    assert not np.allclose(np.asarray(train_a), np.asarray(train_b))


@pytest.mark.parametrize("factory,params,offered", [
    ("mlp", {"input_dim": 16, "num_classes": 5}, False),
    ("leaf.femnist.tiny", {}, True),
    ("leaf.femnist.xlarge", {}, True),
    ("leaf.celeba", {}, True),
    ("leaf.shakespeare", {}, False),
    ("examples.wearables.uci_har", {}, False),
])
def test_stacked_forward_is_offered_by_the_cnns_only(
    factory, params, offered
):
    """``Model.apply_stacked`` (the node-folded convolution stack,
    tests/test_stacked_forward.py) is what the round program looks for: the
    CNNs have it, the MLPs and the LSTM do not, and where a model has it,
    ``apply`` is its one-node case."""
    model = build_model(factory, params)
    assert (model.apply_stacked is not None) == offered
    if offered:
        p = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2,) + tuple(model.input_shape))
        stacked = model.apply_stacked(
            jax.tree_util.tree_map(lambda l: l[None], p), x[None], None, False
        )
        np.testing.assert_array_equal(
            np.asarray(stacked[0]), np.asarray(model.apply(p, x, None, False))
        )
