"""The generators of a cell's samples (``benchmark/data/``) and the losses
of the plain reference (``benchmark/reference/loss_*.py``), on the CPU."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import inputs
from benchmark.data import tokens
from benchmark.reference import loss_label, loss_next_token

from bench_tiny import BENCH


def _doc(**data):
    return {"data": {"generator": "tokens", "samples_per_node": 40,
                     "held_out_per_node": 8, "seq_len": 16, "vocab_size": 50,
                     "targets": "next", "zipf_exponent": 1.0, "dependence": 0.5,
                     **data}}


@pytest.mark.parametrize("targets,y_shape", [("next", (4, 32, 16)), ("last", (4, 32))])
def test_tokens_are_integer_ids_inside_the_vocabulary(targets, y_shape):
    made = jax.device_get(inputs.make_data(_doc(targets=targets), 4, 2**31 + 3))
    assert made["x"].shape == (4, 32, 16) and made["eval_x"].shape == (4, 8, 16)
    assert made["y"].shape == y_shape and made["eval_y"].shape == (4, 8) + y_shape[2:]
    for a in made.values():
        assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 50
    if targets == "next":  # the target of a position is the next position's id
        assert np.array_equal(made["x"][..., 1:], made["y"][..., :-1])


def test_the_last_target_is_the_next_targets_last():
    nxt, last = (jax.device_get(tokens.make(_doc(targets=t), 3, 11))
                 for t in ("next", "last"))
    assert np.array_equal(nxt["x"], last["x"])
    assert np.array_equal(nxt["y"][..., -1], last["y"])
    with pytest.raises(ValueError, match="data.targets"):
        tokens.make(_doc(targets="all"), 3, 11)


@pytest.mark.parametrize("key", tokens.REQUIRED)
def test_tokens_have_no_default_of_their_own(key):
    doc = _doc()
    del doc["data"][key]
    with pytest.raises(ValueError, match=key):
        tokens.make(doc, 3, 11)


def test_tokens_follow_the_seed():
    a, again, other = (jax.device_get(tokens.make(_doc(), 4, seed))
                       for seed in (2**31 + 3, 2**31 + 3, 2**31 + 4))
    for k in a:
        assert np.array_equal(a[k], again[k]), k
    assert not np.array_equal(a["x"], other["x"])
    rows = a["x"].reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)  # every sample differs


def test_tokens_are_zipf_with_a_first_order_dependence():
    made = jax.device_get(tokens.make(
        _doc(samples_per_node=400, zipf_exponent=1.0, dependence=0.5), 4, 5))
    ids = np.concatenate([made["x"], made["y"][..., -1:]], axis=-1).reshape(-1, 17)
    # Fresh draws (a sequence's first id) follow Zipf's law over the ranks.
    first = np.bincount(ids[:, 0], minlength=50) / len(ids)
    zipf = 1.0 / np.arange(1, 51)
    assert np.abs(first - zipf / zipf.sum()).max() < 0.03
    # Half of the positions hold the successor of the id before them: the
    # commonest follower of an id takes over half of its followers.
    before, after = ids[:, :-1].ravel(), ids[:, 1:].ravel()
    share = [np.bincount(after[before == v], minlength=50).max() / (before == v).sum()
             for v in range(10)]
    assert 0.5 < np.mean(share) < 0.7
    none = jax.device_get(tokens.make(_doc(samples_per_node=400, dependence=0.0), 4, 5))
    flat = none["x"].reshape(-1, 16)
    share = [np.bincount(flat[:, 1:].ravel()[flat[:, :-1].ravel() == v],
                         minlength=50).max() / (flat[:, :-1] == v).sum()
             for v in range(10)]
    assert np.mean(share) < 0.3


def test_clusters_draw_the_parents_bits():
    """The clusters moved to ``data/clusters.py`` unchanged: the checksum is
    of the parent's draw (commit ee733d0, ``inputs.make_data``, this size
    and seed, on the CPU)."""
    doc = json.loads((BENCH / "configs" / "femnist_cnn.json").read_text())
    assert doc["data"]["generator"] == "clusters"
    doc["data"].update(samples_per_node=10, held_out_per_node=2)
    made = jax.device_get(inputs.make_data(doc, 3, 2**31 + 7))
    digest = hashlib.sha256()
    for k in ("x", "y", "eval_x", "eval_y"):
        digest.update(np.asarray(made[k]).tobytes())
    assert made["x"].shape == (3, 8, 28, 28, 1) and made["x"].dtype == np.float32
    assert digest.hexdigest() == (
        "524e924c6ad7b83845ac17fc0d4b84f23ca316c51690dc63aa1e1cdaee2a4661")


def test_like_refuses_to_cast_between_ids_and_floats():
    ids, reals = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.float32)
    with pytest.raises(ValueError, match="ids are not cast"):
        inputs._like(reals, ids)
    with pytest.raises(ValueError, match="ids are not cast"):
        inputs._like(ids, reals)
    with pytest.raises(ValueError, match="shape"):
        inputs._like(jnp.zeros((2, 4), jnp.int32), ids)
    assert inputs._like(reals, jnp.zeros((2, 3), jnp.bfloat16)).dtype == jnp.bfloat16
    assert inputs._like(jnp.zeros((2, 3), jnp.int16), ids).dtype == jnp.int32


# ---- the losses -----------------------------------------------------------
# A two-token table model: the logits of a position are the row of the
# table its id selects, so every likelihood is a softmax of two numbers.

def table_apply(table, x, dtype):
    return table[x]


def table_apply_aux(table, x, dtype):
    return table[x], jnp.sum(table ** 2)


TABLE = jnp.asarray([[1.0, 0.0], [0.5, 2.0]])
X = jnp.asarray([[0, 1, 1], [1, 0, 0], [0, 0, 1]])
Y = jnp.asarray([[1, 1, 0], [1, 0, 1], [0, 0, 0]])
MASK = jnp.asarray([1.0, 1.0, 0.0])


def _hand_nll():
    """Mean over a sample's positions, then over the masked samples."""
    logp = np.log(np.exp(np.asarray(TABLE)) /
                  np.exp(np.asarray(TABLE)).sum(axis=1, keepdims=True))
    per_sample = [-np.mean([logp[x, y] for x, y in zip(xs, ys)])
                  for xs, ys in zip(np.asarray(X), np.asarray(Y))]
    return (per_sample[0] + per_sample[1]) / 2, per_sample


def test_next_token_against_hand_values():
    want, per_sample = _hand_nll()
    # Row 0: -log softmax([1, 0]); row 1: -log softmax([.5, 2]).
    p0, p1 = np.exp(1) / (np.exp(1) + 1), np.exp(2) / (np.exp(2) + np.exp(0.5))
    assert per_sample[0] == pytest.approx(
        -(np.log(1 - p0) + np.log(p1) + np.log(1 - p1)) / 3)
    loss = loss_next_token.training(table_apply, "float32", {})
    assert float(loss(TABLE, X, Y, MASK)) == pytest.approx(want, rel=1e-6)
    evaluate = loss_next_token.evaluation(table_apply, "float32", {})
    got, accuracy = evaluate(TABLE, X, Y, MASK)
    assert float(got) == pytest.approx(want, rel=1e-6)
    # The table picks id 0 after a 0 and id 1 after a 1: sample 0 hits its
    # second position, sample 1 its first two; the third sample is masked.
    assert float(accuracy) == pytest.approx((1 / 3 + 2 / 3) / 2)


@pytest.mark.parametrize("coefficient", [0.0, 0.25])
def test_next_token_adds_the_auxiliary_term(coefficient):
    want, _ = _hand_nll()
    params = {"auxiliary_coefficient": coefficient} if coefficient else {}
    loss = loss_next_token.training(table_apply_aux, "float32", params)
    extra = coefficient * float(jnp.sum(TABLE ** 2))
    assert float(loss(TABLE, X, Y, MASK)) == pytest.approx(want + extra, rel=1e-6)
    # The evaluation is the likelihood alone.
    evaluate = loss_next_token.evaluation(table_apply_aux, "float32", params)
    assert float(evaluate(TABLE, X, Y, MASK)[0]) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("apply,params", [
    (table_apply, {}), (table_apply_aux, {"auxiliary_coefficient": 0.25})])
def test_next_token_gradient_against_finite_differences(apply, params):
    with jax.enable_x64():
        table = jnp.asarray(TABLE, jnp.float64)
        loss = loss_next_token.training(apply, "float32", params)
        grad = np.asarray(jax.grad(loss)(table, X, Y, MASK.astype(jnp.float64)))
        step = 1e-6
        for i in range(2):
            for j in range(2):
                bump = np.zeros((2, 2))
                bump[i, j] = step
                up, down = (float(loss(table + s * bump, X, Y,
                                       MASK.astype(jnp.float64))) for s in (1, -1))
                assert grad[i, j] == pytest.approx((up - down) / (2 * step), abs=1e-8)


def test_label_is_one_target_a_sample():
    logits = jnp.asarray([[2.0, 0.0], [0.0, 1.0], [3.0, 3.0]])
    apply = lambda params, x, dtype: logits + params
    y, m = jnp.asarray([0, 0, 1]), jnp.asarray([1.0, 1.0, 0.0])
    want = (np.log(1 + np.exp(-2.0)) + np.log(1 + np.exp(1.0))) / 2
    loss = loss_label.training(apply, "float32", {})
    assert float(loss(0.0, None, y, m)) == pytest.approx(want, rel=1e-6)
    got, accuracy = loss_label.evaluation(apply, "float32", {})(0.0, None, y, m)
    assert float(got) == pytest.approx(want, rel=1e-6) and float(accuracy) == 0.5
