"""The ZAYA1 decoder (models/zaya.py) as a node's model, against its plain
reference (benchmark/reference/zaya1.py, which imports nothing of the
program) at a small size on the CPU: hidden 64, 4 query and 2 key/value
heads of 16, convolutions of 2 and 2 taps, 8 experts of width 32 with 4
held and top-1, a router 16 wide, 3 layers, vocabulary 96, 16 positions;
float32.

(a) the trees agree; ``apply`` and one local SGD step with the bias step:
logits, counts and every leaf's update; (b) causality: a later position
changes nothing before it (the convolutions and the value shift look
back, never ahead); (c) the share ties to the model: the routed parts of
``ep_rank`` 0 and 1 add up to the uncut reference's expert sublayer; (d)
top-1 routing through every step of the shared ladder, every position to
one held expert, and no held expert chosen; (e) what the model has no
equations for is refused; (f) a ``murmura run``-shaped job trains and
reports its router's counters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import murmura_tpu.utils  # noqa: F401 - the package's import order (ROADMAP D16)
from benchmark.reference import zaya1 as reference
from benchmark.reference.round import Job, batch_schedule, make_trainer
from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.config import Config
from murmura_tpu.core.rounds import build_round_program
from murmura_tpu.data.base import FederatedArrays
from murmura_tpu.models import decoder
from murmura_tpu.models.registry import build_model
from murmura_tpu.ops import attention
from murmura_tpu.ops.flatten import make_flatteners
from murmura_tpu.utils.factories import build_network_from_config

SHAPE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, num_experts_per_tok=1, router_hidden_size=16,
    cca_time0=2, cca_time1=2, partial_rotary_factor=0.5, rms_norm_eps=1e-5,
)
TINY = dict(SHAPE, vocab_size=96, num_hidden_layers=3, num_experts=8, ep_size=2,
            ep_rank=0, seq_len=16, rope_theta=5000000)
DOC = dict(SHAPE, vocab_size=96, num_layers=3, num_experts=4, published={"num_experts": 8},
           rope_parameters={"hybrid": {"rope_theta": 5000000}}, seq_len=16,
           bias_update_speed=0.001, initializer_range=0.02)


def _weights(doc=DOC, seed=0):
    """One node's weights by the reference's draw, the selection bias and
    the depth's scalars off nought (a bias of a tenth of the router's
    spread of scores, so that the choice needs both)."""
    params = reference.init(jax.random.PRNGKey(seed), doc)
    router = params["layers"]["router"]
    router["bias"] = 0.002 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                               router["bias"].shape)
    router["depth"] = 0.5 * jax.random.normal(jax.random.PRNGKey(seed + 200),
                                              router["depth"].shape)
    return params


def _ids(seed=1, batch=3, length=16, vocab=96):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, vocab)


def _close(got, want, tol=2e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tol * max(np.abs(want).max(), 1e-30) + floor)


def test_the_trees_agree_path_for_path():
    model = build_model("decoder.zaya1", TINY)
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: reference.init(k, DOC), jax.random.PRNGKey(0))
    flat = lambda t: [(jax.tree_util.keystr(p), l.shape, l.dtype)
                      for p, l in jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(mine) == flat(theirs)
    assert len(flat(mine)) == 25


def test_apply_and_every_gradient_match_the_reference():  # (a), the forward
    model, params, ids = build_model("decoder.zaya1", TINY), _weights(), _ids()
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    want, want_aux = jax.jit(lambda p, x: reference.apply(p, x, "float32"))(
        params, ids[:, :-1])
    assert logits.shape == (3, 16, 96)
    _close(logits, want)
    counts = np.asarray(aux["step"]["counts"])
    np.testing.assert_array_equal(counts, np.asarray(want_aux["step"]))
    assert counts.sum(-1).tolist() == [[16.0] * 3] * 3
    assert not np.asarray(aux["loss"]).any()
    weight = np.asarray(aux["step"]["chosen_weight"]) / 16  # a layer's mean p_chosen
    assert ((weight > 0.05) & (weight < 0.2)).all()  # near an even softmax's 1/8
    _close(jax.jit(model.apply)(params, ids[:, :-1]), want)
    loss = lambda f: lambda p: (f(p)[0] ** 2).mean()
    mine = jax.jit(jax.grad(loss(lambda p: model.apply_train(p, ids[:, :-1]))))(params)
    theirs = jax.jit(jax.grad(loss(lambda p: reference.apply(p, ids[:, :-1], "float32"))))(
        params)
    flat = jax.tree_util.tree_flatten_with_path(mine)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(theirs)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):  # the choice only: no gradient
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        assert np.abs(np.asarray(b)).max() > 0, name
        _close(a, b, 1e-4)


def _two_nodes(samples):
    """Two nodes' stacked weights and sequences, and the data as the round
    program takes it."""
    params = jax.tree_util.tree_map(lambda *l: jnp.stack(l), _weights(seed=0), _weights(seed=1))
    ids = np.asarray(_ids(seed=5, batch=2 * samples)).reshape(2, samples, 17)
    x, y = ids[..., :-1].astype(np.int32), ids[..., 1:].astype(np.int32)
    data = FederatedArrays(
        x=x, y=y, mask=np.ones((2, samples), np.float32),
        num_samples=np.full(2, samples, np.int32), num_classes=96)
    return params, data


STILL = ("['layers']['router']['depth']", "['layers']['router']['norm']")


@pytest.mark.parametrize("batch,samples", [(2, 2), (2, 4)], ids=["one_step", "two_steps"])
def test_a_local_step_matches_the_reference(batch, samples):  # (a), the step
    """Local SGD with the bias step through the round program's own
    training stage (``RoundProgram.train_flat``) against the reference's
    stepping trainer on the same batch schedule: every leaf's update, the
    next-token loss's gradient through the tied embedding's two uses."""
    model = build_model("decoder.zaya1", TINY)
    params, data = _two_nodes(samples)
    lr, seed = 0.05, 11
    program = build_round_program(
        model, build_aggregator("fedavg", {}), data, batch_size=batch, lr=lr, seed=seed)
    template = jax.tree_util.tree_map(lambda l: l[0], params)
    _, unravel, _ = make_flatteners(template)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), np.uint32(0))
    own_flat, ok = jax.jit(program.train_flat)(
        params, {}, key, jnp.ones((2, 2)) - jnp.eye(2), jnp.zeros(2), 0.0,
        {k: jnp.asarray(v) for k, v in program.data_arrays.items()})
    got = jax.vmap(unravel)(own_flat)

    job = Job(model="zaya1", rule="fedavg", rule_params={}, attack=None,
              attack_params={}, lr=lr, batch_size=batch, local_epochs=1, total_rounds=2,
              loss="next_token", loss_params={"auxiliary_coefficient": 0.0}, doc=DOC)
    arrays = {k: np.asarray(program.data_arrays[k])
              for k in ("mask", "eff_batch", "steps", "num_samples")}
    idx, bmask, live, _ = batch_schedule(seed, 0, arrays, job)
    want = make_trainer(job)(
        params, jnp.asarray(data.x), jnp.asarray(data.y),
        jnp.asarray(idx.reshape((-1,) + idx.shape[2:])), jnp.asarray(bmask),
        jnp.asarray(live))
    assert np.asarray(ok).tolist() == [1.0, 1.0]
    for (path, a), b, start in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(params)):
        update, want_update = np.asarray(a - start), np.asarray(b - start)
        # The router's depth scalars and norm take gradients (the test
        # above) too small to move a float32 state of 0.5 or 1 at this size.
        name = jax.tree_util.keystr(path)
        assert np.abs(want_update).max() > 0 or name in STILL, path
        # An update is the difference of two float32 states: it has the
        # state's last place (a norm's 1.0 +- 6e-8) beside its own.
        _close(update, want_update, 2e-4, floor=1.2e-7 * float(np.abs(start).max()))
    bias = np.asarray(got["layers"]["router"]["bias"] - params["layers"]["router"]["bias"])
    steps = samples // batch
    assert set(np.round(np.abs(bias) / 0.001).ravel().tolist()) <= set(range(steps + 1))
    assert np.abs(bias).max() == pytest.approx(0.001 * steps, rel=1e-3)


def test_a_later_position_changes_nothing_before_it():  # (b)
    model, params = build_model("decoder.zaya1", TINY), _weights()
    ids = _ids()[:1, :-1]
    logits = jax.jit(model.apply)(params, ids)
    for t in (0, 7, 14):
        other = ids.at[0, t + 1].set((ids[0, t + 1] + 1) % 96)
        moved = jax.jit(model.apply)(params, other)
        np.testing.assert_array_equal(np.asarray(moved[0, :t + 1]),
                                      np.asarray(logits[0, :t + 1]))
        assert np.abs(np.asarray(moved[0, t + 1:] - logits[0, t + 1:])).max() > 0


def test_the_shares_add_up_to_the_uncut_expert_sublayer():  # (c)
    """Every chip routes over all eight experts and computes its own four's
    part; the parts of both shares add up to the uncut reference's routed
    sublayer, and they route alike."""
    whole = dict(DOC, num_experts=8, num_layers=2)
    layer = jax.tree_util.tree_map(
        lambda l: l[1], reference.init(jax.random.PRNGKey(3), whole)["layers"])
    layer["router"]["depth"] = jnp.asarray(0.7)
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 64))
    carried = jax.random.normal(jax.random.PRNGKey(6), (16, 16))
    want, counts, averaged = reference._moe(layer, x, carried, False, whole, "float32")
    total, held = 0.0, []
    for rank in range(2):
        model = build_model("decoder.zaya1", dict(TINY, ep_rank=rank))
        chosen, weight, mine, state = model.meta["route"](layer["router"], x, carried, False)
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(counts))
        _close(state, averaged)
        share = {k: v[4 * rank:4 * rank + 4] for k, v in layer["experts"].items()}
        part, _ = model.meta["experts"](share, x, chosen[:, None], weight[:, None])
        total = total + part
        held.append(float(counts[4 * rank:4 * rank + 4].sum()))
    assert sum(held) == 16.0 and min(held) > 0
    _close(total, want)
    # One share alone is not the layer: the other's part is really left out.
    assert np.abs(np.asarray(total - part - want)).max() > 1e-3 * np.abs(np.asarray(want)).max()


# --- (d) top-1 through the shared ladder -------------------------------------

# Four held experts of eight (the first four), tiles of 4 rows, a floor of one
# even share: 16 pairs a sequence, a buffer of 16 or 28 rows.  A pattern of
# chosen experts, the ladder's step it takes and the rows it needs.
PATTERNS = {
    "none_held": ([6] * 16, 0, 0),
    "all_to_one_held": ([3] * 16, 0, 16),
    "spread_over_the_held": ([0] * 5 + [1] * 5 + [2] * 5 + [3], 1, 28),
    "half_held": ([0, 5] * 8, 0, 8),
}


def _ladder(monkeypatch):
    monkeypatch.setattr(decoder, "GROUP_ALIGN", 4)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", 1)
    assert decoder.ladder(16, 1, 4, 8) == (16, [16, 28])
    return build_model("decoder.zaya1", TINY)


@pytest.mark.parametrize("case", sorted(PATTERNS))
def test_top1_through_every_step_of_the_ladder(case, monkeypatch):
    """``decoder.experts`` at one pair a position: whichever step the
    buffer takes, every held pair's row is the expert's SwiGLU times
    ``p_chosen``, every other row 0, and the gradients are the plain sum's."""
    pattern, step, rows = PATTERNS[case]
    model = _ladder(monkeypatch)
    layer = jax.tree_util.tree_map(lambda l: l[0], _weights()["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    chosen = jnp.asarray(pattern, jnp.int32)[:, None]
    weight = jax.random.uniform(jax.random.PRNGKey(3), (16, 1), minval=0.05, maxval=0.5)

    def plain(p, x, weight):
        y = jnp.zeros_like(x)
        for e in range(4):
            expert = {k: v[e] for k, v in p.items()}
            out = decoder.swiglu(expert, x, None)
            y = y + jnp.where(chosen == e, weight, 0.0) * out
        return y

    got, took = jax.jit(model.meta["experts"])(layer["experts"], x, chosen, weight)
    assert int(took) == step
    assert -(-np.bincount(np.asarray(pattern), minlength=8)[:4] // 4).sum() * 4 == rows
    _close(got, plain(layer["experts"], x, weight))
    loss = lambda f: lambda *a: (f(*a) ** 2).sum()
    mine = jax.grad(loss(lambda *a: model.meta["experts"](*a[:1], a[1], chosen, a[2])[0]),
                    argnums=(0, 1, 2))(layer["experts"], x, weight)
    theirs = jax.grad(loss(plain), argnums=(0, 1, 2))(layer["experts"], x, weight)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        _close(a, b, 1e-5, floor=1e-12)


@pytest.mark.parametrize("expert,held", [(1, True), (6, False)],
                         ids=["every_position_to_one_held", "every_position_elsewhere"])
def test_the_model_with_every_position_on_one_expert(expert, held):
    """A selection bias that sends every position to one expert: held here,
    its group is as long as the sequence; held elsewhere, the layer's
    routed part is 0 and the experts take no gradient.  Either way the
    model gives what the reference gives."""
    model, params = build_model("decoder.zaya1", TINY), _weights()
    params["layers"]["router"]["bias"] = params["layers"]["router"]["bias"].at[
        :, expert].set(10.0)
    ids = _ids(seed=9)
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    assert np.asarray(aux["step"]["counts"])[..., expert].tolist() == [[16.0] * 3] * 3
    _close(logits, jax.jit(lambda p, x: reference.apply(p, x, "float32")[0])(params, ids[:, :-1]))
    grads = jax.jit(jax.grad(lambda p: model.apply_train(p, ids[:, :-1])[0].sum()))(params)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    per_expert = np.abs(np.asarray(grads["layers"]["experts"]["down"])).max(axis=(2, 3))
    if held:
        assert (per_expert[:, expert] > 0).all()
    else:
        assert per_expert.max() == 0


def test_bfloat16_products_follow_the_bfloat16_reference():
    """One layer, so that a last place that rounds the other way does not
    compound: the program's bf16 products are the reference's, far closer
    to its bf16 result than its float32 result is."""
    doc = dict(DOC, num_layers=1)
    model = build_model("decoder.zaya1", dict(TINY, num_hidden_layers=1,
                                               compute_dtype="bfloat16"))
    params, ids = reference.init(jax.random.PRNGKey(0), doc), _ids()
    logits, _ = jax.jit(model.apply_train)(params, ids[:, :-1])
    apply = jax.jit(reference.apply, static_argnums=2)
    want, _ = apply(params, ids[:, :-1], "bfloat16")
    exact, _ = apply(params, ids[:, :-1], "float32")
    gap = np.abs(np.asarray(logits - want)).max()
    assert gap < 0.05 * np.abs(np.asarray(exact - want)).max()


def test_attention_in_blocks_is_attention(monkeypatch):
    model, params, ids = build_model("decoder.zaya1", TINY), _weights(), _ids()
    whole = jax.jit(model.apply)(params, ids[:, :-1])
    monkeypatch.setattr(attention, "ATTENTION_BLOCK", 4)
    blocked = jax.jit(build_model("decoder.zaya1", TINY).apply)(params, ids[:, :-1])
    _close(blocked, whole)


@pytest.mark.parametrize("wrong", [
    {"sliding_window": 4096}, {"tie_word_embeddings": False}, {"num_shared_experts": 1},
    {"num_experts_per_tok": 2}, {"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]},
    {"lm_head_bias": True}, {"cca_time1": 0}, {"ep_size": 3}, {"ep_rank": 2},
    {"num_key_value_heads": 3},
], ids=lambda w: next(iter(w)))
def test_what_the_model_has_no_equations_for_is_refused(wrong):  # (e)
    with pytest.raises(ValueError):
        build_model("decoder.zaya1", dict(TINY, **wrong))


def _job():
    return {
        "experiment": {"name": "zaya", "seed": 7, "rounds": 2},
        "topology": {"type": "fully", "num_nodes": 3},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 2, "lr": 0.05},
        "data": {"adapter": "synthetic_sequences",
                 "params": {"num_samples": 36, "seq_len": 16, "vocab_size": 96,
                            "targets": "next", "holdout_fraction": 0.34}},
        "model": {"factory": "decoder.zaya1", "params": TINY},
        "backend": "simulation",
    }


def test_a_job_trains_and_reports_its_routers_counters():  # (f)
    net = build_network_from_config(Config.model_validate(_job()))
    start = np.asarray(net.params["layers"]["router"]["bias"])
    history = net.train(rounds=2, eval_every=1)
    loss = history["mean_loss"]
    assert loss[1] < loss[0] and np.isfinite(loss).all()
    bias = np.asarray(net.params["layers"]["router"]["bias"])
    assert bias.shape == (3, 3, 8) and np.abs(bias - start).max() > 1e-3
    np.testing.assert_allclose(bias[0], bias[1], atol=1e-7)  # averaged
    for name, low, high in (("moe.chosen_weight_mean", 0.05, 0.2),
                            ("moe.held_share", 0.2, 0.8), ("moe.bias_abs_max", 1e-3, 0.1),
                            ("moe.load_max_over_mean", 1.0, 8.0),
                            ("moe.rows_first_step_share", 0.0, 1.0)):
        values = history[f"agg_{name}"]
        assert len(values) == 2 and all(low < v <= high for v in values), (name, values)


def test_the_example_yaml_trains_a_round():
    """``examples/configs/decoder_zaya_tiny.yaml``, as ``murmura run``
    validates it, trains one round."""
    from pathlib import Path

    import yaml

    example = Path(__file__).resolve().parents[1] / "examples/configs/decoder_zaya_tiny.yaml"
    config = Config.model_validate(yaml.safe_load(example.read_text()))
    assert config.model.factory == "decoder.zaya1"
    history = build_network_from_config(config).train(rounds=1, eval_every=1)
    assert np.isfinite(history["mean_loss"]).all()
    assert 0 < history["agg_moe.chosen_weight_mean"][0] < 1
