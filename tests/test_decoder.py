"""The DeepSeek-V3-family decoder (models/decoder.py) as a node's model,
against its plain reference (benchmark/reference/deepseek_v3.py, which
imports nothing of the program) at a small size on the CPU: hidden 64, 2
heads, ``kv_lora_rank`` 16, 8 experts of width 32 with 4 held and top-2, 1
dense + 2 expert layers, vocabulary 96, 16 positions; float32.

(a) ``apply`` and one local SGD step with the bias step, logits and every
leaf's update; (b) the share ties to the model: the routed parts of the
shares {0..3} and {4..7}, with the shared expert counted once, add up to
the uncut reference's layer; (c) no pair is dropped with every token routed
to one held expert; (d) a ``murmura run``-shaped job trains, the bias leaf
moves and is averaged; (e) the models that were there lower to the round
and eval steps they lowered to before this path existed.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import murmura_tpu.utils  # noqa: F401 - the package's import order (ROADMAP D16)
from benchmark.reference import deepseek_v3 as reference
from benchmark.reference.round import Job, batch_schedule, make_trainer
from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.config import Config
from murmura_tpu.core import rounds
from murmura_tpu.core.rounds import build_round_program
from murmura_tpu.data.base import FederatedArrays
from murmura_tpu.data.registry import build_federated_data
from murmura_tpu.models import decoder
from murmura_tpu.models.registry import build_model
from murmura_tpu.ops import attention
from murmura_tpu.ops.flatten import make_flatteners
from murmura_tpu.ops.losses import masked_next_token_cross_entropy
from murmura_tpu.utils.factories import build_network_from_config

# The program's parameters, by the published keys; the reference's
# document, by the benchmark's (the experts held, the depth as run).
SHAPE = dict(
    hidden_size=64, first_k_dense_replace=1, intermediate_size=128,
    moe_intermediate_size=32, n_shared_experts=2, num_experts_per_tok=2,
    num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=50000, rms_norm_eps=1e-5,
    routed_scaling_factor=2.446,
)
TINY = dict(SHAPE, vocab_size=96, num_hidden_layers=3, n_routed_experts=8,
            ep_size=2, ep_rank=0, seq_len=16)
DOC = dict(SHAPE, vocab_size=96, num_layers=3, n_routed_experts=4,
           published={"n_routed_experts": 8}, seq_len=16,
           bias_update_speed=0.001, initializer_range=0.02)
COEFFICIENT = 0.0001


def _weights(doc=DOC, seed=0):
    """One node's weights by the reference's draw, the selection bias off
    nought so that the choice needs it."""
    params = reference.init(jax.random.PRNGKey(seed), doc)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                    params["moe_layers"]["router"]["bias"].shape)
    params["moe_layers"]["router"]["bias"] = bias
    return params


def _ids(seed=1, batch=3, length=16, vocab=96):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, vocab)


def _close(got, want, tol=2e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tol * max(np.abs(want).max(), 1e-30) + floor)


def test_the_trees_agree_path_for_path():
    model = build_model("decoder.deepseek_v3", TINY)
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: reference.init(k, DOC), jax.random.PRNGKey(0))
    flat = lambda t: [(jax.tree_util.keystr(p), l.shape, l.dtype)
                      for p, l in jax.tree_util.tree_flatten_with_path(t)[0]]
    assert flat(mine) == flat(theirs)
    assert len(flat(mine)) == 28


def test_apply_matches_the_reference():  # (a), the forward
    model, params, ids = build_model("decoder.deepseek_v3", TINY), _weights(), _ids()
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    want, want_aux = jax.jit(lambda p, x: reference.apply(p, x, "float32"))(
        params, ids[:, :-1])
    assert logits.shape == (3, 16, 96)
    _close(logits, want)
    np.testing.assert_array_equal(np.asarray(aux["step"]["counts"]), np.asarray(want_aux["step"]))
    assert np.asarray(aux["step"]["counts"]).sum(-1).tolist() == [[32.0, 32.0]] * 3
    _close(aux["loss"].mean(), COEFFICIENT * want_aux["loss"], 1e-6)
    _close(jax.jit(model.apply)(params, ids[:, :-1]), want)


def _two_nodes(batch, samples):
    """Two nodes' stacked weights and sequences, and the data as the round
    program takes it."""
    params = jax.tree_util.tree_map(lambda *l: jnp.stack(l), _weights(seed=0), _weights(seed=1))
    ids = np.asarray(_ids(seed=5, batch=2 * samples)).reshape(2, samples, 17)
    x, y = ids[..., :-1].astype(np.int32), ids[..., 1:].astype(np.int32)
    data = FederatedArrays(
        x=x, y=y, mask=np.ones((2, samples), np.float32),
        num_samples=np.full(2, samples, np.int32), num_classes=96)
    return params, data


@pytest.mark.parametrize("batch,samples", [(2, 2), (2, 4)], ids=["one_step", "two_steps"])
def test_a_local_step_matches_the_reference(batch, samples):  # (a), the step
    """Local SGD with the bias step through the round program's own
    training stage (``RoundProgram.train_flat``) against the reference's
    stepping trainer on the same batch schedule: every leaf's update."""
    model = build_model("decoder.deepseek_v3", TINY)
    params, data = _two_nodes(batch, samples)
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

    job = Job(model="deepseek_v3", rule="fedavg", rule_params={}, attack=None,
              attack_params={}, lr=lr, batch_size=batch, local_epochs=1, total_rounds=2,
              loss="next_token", loss_params={"auxiliary_coefficient": COEFFICIENT},
              doc=DOC)
    arrays = {k: np.asarray(program.data_arrays[k])
              for k in ("mask", "eff_batch", "steps", "num_samples")}
    idx, bmask, live, _ = batch_schedule(seed, 0, arrays, job)
    want = make_trainer(job)(
        params, jnp.asarray(data.x), jnp.asarray(data.y),
        jnp.asarray(idx.reshape((-1,) + idx.shape[2:])), jnp.asarray(bmask),
        jnp.asarray(live))
    assert np.asarray(ok).tolist() == [1.0, 1.0]
    moved = 0
    for (path, a), b, start in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(params)):
        update, want_update = np.asarray(a - start), np.asarray(b - start)
        assert np.abs(want_update).max() > 0, path
        # An update is the difference of two float32 states: it has the
        # state's last place (a norm's 1.0 +- 6e-8) beside its own.
        _close(update, want_update, 2e-4, floor=1.2e-7 * float(np.abs(start).max()))
        moved += 1
    assert moved == 28
    bias = np.asarray(got["moe_layers"]["router"]["bias"] - params["moe_layers"]["router"]["bias"])
    steps = samples // batch
    assert set(np.round(np.abs(bias) / 0.001).ravel().tolist()) <= set(range(steps + 1))
    assert np.abs(bias).max() == pytest.approx(0.001 * steps, rel=1e-4)


def test_the_shares_add_up_to_the_uncut_layer():  # (b)
    """Every chip routes over all eight experts and computes its own four's
    part; the parts of both shares and the shared expert, counted once, are
    the uncut reference's layer."""
    whole = dict(DOC, n_routed_experts=8, num_layers=1, first_k_dense_replace=0)
    layer = jax.tree_util.tree_map(
        lambda l: l[0], reference.init(jax.random.PRNGKey(3), whole)["moe_layers"])
    layer["router"]["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 64))
    want, counts, _ = reference._moe(layer, x, whole, "float32")
    total = decoder.swiglu(layer["shared"], x, None)
    held = []
    for rank in range(2):
        model = build_model("decoder.deepseek_v3", dict(TINY, ep_rank=rank))
        chosen, weights, mine, _ = model.meta["route"](layer["router"], x)
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(counts))
        share = {k: v[4 * rank:4 * rank + 4] for k, v in layer["experts"].items()}
        part, _ = model.meta["experts"](share, x, chosen, weights)
        assert np.abs(np.asarray(part)).max() > 0
        total = total + part
        held.append(float(counts[4 * rank:4 * rank + 4].sum()))
    assert sum(held) == 32.0 and min(held) > 0
    _close(total, want)
    # One share alone is not the layer: the other's part is really left out.
    assert np.abs(np.asarray(total - part - want)).max() > 1e-3 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("expert", [0, 3], ids=["first_held", "last_held"])
def test_no_pair_is_dropped_when_all_tokens_choose_one_expert(expert):  # (c)
    """A selection bias that sends every position to one held expert: its
    group is as long as the sequence, and the layer still gives what the
    reference gives with every expert of every position computed."""
    model, params = build_model("decoder.deepseek_v3", TINY), _weights()
    bias = params["moe_layers"]["router"]["bias"].at[:, expert].set(10.0)
    params["moe_layers"]["router"]["bias"] = bias
    ids = _ids(seed=9)
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    want, _ = reference.apply(params, ids[:, :-1], "float32")
    assert np.asarray(aux["step"]["counts"])[..., expert].tolist() == [[16.0, 16.0]] * 3
    _close(logits, want)
    grads = jax.jit(jax.grad(lambda p: model.apply_train(p, ids[:, :-1])[0].sum()))(params)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    per_expert = np.abs(np.asarray(grads["moe_layers"]["experts"]["down"])).max(axis=(2, 3))
    assert (per_expert[:, expert] > 0).all()


def test_no_expert_held_is_chosen_and_nothing_breaks():
    """The other end of the imbalance: every pair is held elsewhere."""
    model, params = build_model("decoder.deepseek_v3", TINY), _weights()
    bias = params["moe_layers"]["router"]["bias"].at[:, 4:6].set(10.0)
    params["moe_layers"]["router"]["bias"] = bias
    ids = _ids(seed=9)
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    assert np.asarray(aux["step"]["counts"])[..., :4].sum() == 0
    _close(logits, reference.apply(params, ids[:, :-1], "float32")[0])
    grads = jax.jit(jax.grad(lambda p: model.apply_train(p, ids[:, :-1])[0].sum()))(params)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    assert np.abs(np.asarray(grads["moe_layers"]["experts"]["down"])).max() == 0


def test_bfloat16_products_follow_the_bfloat16_reference():
    model = build_model("decoder.deepseek_v3", dict(TINY, compute_dtype="bfloat16"))
    params, ids = _weights(), _ids()
    logits, _ = jax.jit(model.apply_train)(params, ids[:, :-1])
    want, _ = reference.apply(params, ids[:, :-1], "bfloat16")
    exact, _ = reference.apply(params, ids[:, :-1], "float32")
    gap = np.abs(np.asarray(logits - want)).max()
    assert gap < 0.2 * np.abs(np.asarray(exact - want)).max()


def test_rotary_pairs_are_the_published_codes():
    """Interleaved pairs on the layout as it lies are the published code's
    de-interleave followed by its rotation of halves, up to the same
    permutation of both operands: every score is the same."""
    q = jax.random.normal(jax.random.PRNGKey(0), (16, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (16, 8))

    def published(x):
        d = x.shape[-1]
        x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
        inv = 50000.0 ** (-np.arange(0, d, 2) / d)
        angle = np.arange(x.shape[0])[:, None] * inv[None, :]
        angle = np.concatenate([angle, angle], -1).reshape(
            (x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
        half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * np.cos(angle) + half * np.sin(angle)

    mine = jnp.einsum("qhd,kd->hqk", decoder.rotate(q, 50000.0), decoder.rotate(k, 50000.0))
    theirs = jnp.einsum("qhd,kd->hqk", published(q), published(k))
    _close(mine, theirs, 1e-5)
    _close(decoder.rotate(q, 50000.0), reference._rotate(q, 50000.0), 1e-6)


def test_attention_in_blocks_is_attention(monkeypatch):
    model, params, ids = build_model("decoder.deepseek_v3", TINY), _weights(), _ids()
    whole = jax.jit(model.apply)(params, ids[:, :-1])
    monkeypatch.setattr(attention, "ATTENTION_BLOCK", 4)
    blocked = jax.jit(build_model("decoder.deepseek_v3", TINY).apply)(params, ids[:, :-1])
    _close(blocked, whole)


def test_what_the_decoder_has_no_equations_for_is_refused():
    for wrong in ({"q_lora_rank": 1536}, {"scoring_func": "softmax"}, {"n_group": 8},
                  {"topk_method": "greedy"}, {"ep_size": 3}, {"ep_rank": 2}):
        with pytest.raises(ValueError):
            build_model("decoder.deepseek_v3", dict(TINY, **wrong))


def test_next_token_loss_is_the_mean_over_positions_then_samples():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    y = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 7)
    loss, acc = masked_next_token_cross_entropy(logits, y, jnp.asarray([1.0, 0.0, 1.0]))
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    nll = -np.take_along_axis(logp, np.asarray(y)[..., None], -1)[..., 0].mean(-1)
    assert float(loss) == pytest.approx((nll[0] + nll[2]) / 2, rel=1e-6)
    hit = (np.asarray(logits).argmax(-1) == np.asarray(y)).mean(-1)
    assert float(acc) == pytest.approx((hit[0] + hit[2]) / 2, rel=1e-6)


@pytest.mark.parametrize("targets,shape", [("last", (4, 9)), ("next", (4, 9, 6))])
def test_the_sequence_adapter_gives_one_target_a_position(targets, shape):
    data = build_federated_data(
        "synthetic_sequences",
        {"num_samples": 48, "seq_len": 6, "vocab_size": 11, "targets": targets,
         "holdout_fraction": 0.25}, num_nodes=4, seed=3)
    assert data.y.shape == shape and data.y.dtype == np.int32
    assert data.x.shape == (4, 9, 6) and data.y_test.shape == (4, 3) + shape[2:]
    if targets == "next":  # a position's target is the id that follows it
        np.testing.assert_array_equal(data.y[..., :-1], data.x[..., 1:])


def test_three_sequences_a_node_split_two_and_one():
    """The benchmark's cell: 3 sequences a node, a third held out."""
    data = build_federated_data(
        "synthetic_sequences",
        {"num_samples": 9, "seq_len": 6, "vocab_size": 11, "targets": "next",
         "holdout_fraction": 0.34}, num_nodes=3, seed=3)
    assert data.x.shape == (3, 2, 6) and data.x_test.shape == (3, 1, 6)
    assert data.effective_batch(1).tolist() == [1] * 3
    assert data.steps_per_epoch(1).tolist() == [2] * 3


def _job(**training):
    return {
        "experiment": {"name": "decoder", "seed": 7, "rounds": 3},
        "topology": {"type": "fully", "num_nodes": 3},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 2, "lr": 0.05, **training},
        "data": {"adapter": "synthetic_sequences",
                 "params": {"num_samples": 36, "seq_len": 16, "vocab_size": 96,
                            "targets": "next", "holdout_fraction": 0.34}},
        "model": {"factory": "decoder.deepseek_v3", "params": TINY},
        "backend": "simulation",
    }


def test_a_job_trains_and_the_bias_is_averaged():  # (d)
    net = build_network_from_config(Config.model_validate(_job()))
    start = np.asarray(net.params["moe_layers"]["router"]["bias"])
    history = net.train(rounds=3, eval_every=1)
    loss = history["mean_loss"]
    assert loss[2] < loss[1] < loss[0] and np.isfinite(loss).all()
    bias = np.asarray(net.params["moe_layers"]["router"]["bias"])
    assert bias.shape == (3, 2, 8) and np.abs(bias - start).max() > 1e-3
    # Fully linked fedavg: every node ends the round with the mean, and a
    # mean of three nodes' steps of 0.001 is no multiple of 0.001.
    np.testing.assert_allclose(bias[0], bias[1], atol=1e-7)
    np.testing.assert_allclose(bias[0], bias[2], atol=1e-7)
    assert (np.abs(np.round(bias / 0.001) - bias / 0.001) > 0.1).any()
    for name, low, high in (("moe.load_max_over_mean", 1.0, 8.0),
                            ("moe.held_share", 0.2, 0.8), ("moe.bias_abs_max", 1e-3, 0.1),
                            ("moe.rows_first_step_share", 0.0, 1.0)):
        values = history[f"agg_{name}"]
        assert len(values) == 3 and all(low <= v <= high for v in values), (name, values)


def test_a_compromised_node_takes_no_step_of_either_kind():
    raw = _job()
    raw["attack"] = {"enabled": True, "type": "gaussian", "percentage": 0.34,
                     "params": {"noise_std": 0.0}}
    net = build_network_from_config(Config.model_validate(raw))
    frozen = int(np.flatnonzero(np.asarray(net.compromised))[0])
    template = jax.tree_util.tree_map(lambda l: l[0], net.params)
    _, unravel, _ = make_flatteners(template)
    comp = net._stage(net.compromised, net._node_s)
    args = net._round_inputs(0, comp)
    own_flat, _ = jax.jit(net.program.train_flat)(*args)
    trained = jax.vmap(unravel)(own_flat)
    for a, b in zip(jax.tree_util.tree_leaves(trained), jax.tree_util.tree_leaves(args[0])):
        np.testing.assert_array_equal(np.asarray(a)[frozen], np.asarray(b)[frozen])
    moved = np.asarray(trained["moe_layers"]["router"]["bias"]) != np.asarray(
        args[0]["moe_layers"]["router"]["bias"])
    assert moved[[i for i in range(3) if i != frozen]].any()


def test_the_fused_scan_gives_the_per_round_history():
    per_round = build_network_from_config(Config.model_validate(_job())).train(
        rounds=2, eval_every=1)
    fused = build_network_from_config(Config.model_validate(_job())).train(
        rounds=2, eval_every=1, rounds_per_dispatch=2)
    np.testing.assert_allclose(fused["mean_loss"], per_round["mean_loss"], rtol=1e-5)


# --- (e): the models that were there lower to what they lowered to -----------

OTHERS = {
    "cnn": {},
    "mlp": dict(
        model={"factory": "mlp",
               "params": {"input_dim": 10, "hidden_dims": [16], "num_classes": 3}},
        data={"adapter": "synthetic",
              "params": {"num_samples": 96, "input_dim": 10, "num_classes": 3}}),
    "char-lstm": dict(
        model={"factory": "leaf.shakespeare",
               "params": {"embed_dim": 4, "hidden": 8, "num_layers": 1, "seq_len": 6}},
        data={"adapter": "leaf.shakespeare", "params": {"num_samples": 96, "seq_len": 6}}),
}
# sha256 of the lowered round step and eval step (``as_text()``, first 16
# hex digits) at the parent commit c2c48c7, before ``local_training_by_node``
# and the decoder existed.  A PR that changes these models' programs on
# purpose records them anew (the recipe is ``_lowered_digests`` below).
PARENT = {
    "cnn": ["1be30ca3e5dd09f6", "99e7f3916443c85a"],
    "mlp": ["c1e0d1aa270e3f55", "9082e8a75739d473"],
    "char-lstm": ["fb9f05b12132a3d5", "e36ca9885c0b6425"],
}


def _lowered_digests(job):
    raw = {
        "experiment": {"name": "lowering", "seed": 1, "rounds": 2},
        "topology": {"type": "ring", "num_nodes": 4},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "leaf.femnist",
                 "params": {"num_samples": 96, "partition_method": "iid"}},
        "model": {"factory": "leaf.femnist.baseline", "params": {}},
        "backend": "simulation",
        "tpu": {"compute_dtype": "bfloat16"},
    }
    raw.update(job)
    net = build_network_from_config(Config.model_validate(raw))
    comp = net._stage(net.compromised, net._node_s)
    texts = (net._step.lower(*net._round_inputs(0, comp)).as_text(),
             net._eval.lower(net.params, net._data).as_text())
    return net, [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts], texts


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_models_lower_to_their_parents_programs(name, monkeypatch):
    """MLP, char-LSTM and CNN: the round step and the eval step are, text
    for text, what the parent commit lowered, and neither recomputes
    (``checkpoint``) nor takes the node-at-a-time path."""
    def never(*args, **kwargs):
        raise AssertionError("a model without apply_train took the decoder's loss")

    monkeypatch.setattr(rounds, "masked_next_token_cross_entropy", never)
    net, digests, texts = _lowered_digests(OTHERS[name])
    assert net.program.init_params is not None
    assert digests == PARENT[name]
    assert not any("optimization_barrier" in t for t in texts)


def test_the_decoders_step_is_the_one_that_recomputes():
    """The same reading on the decoder's job finds the path taken: layers
    recomputed (``jax.checkpoint`` lowers to an ``optimization_barrier``)."""
    net = build_network_from_config(Config.model_validate(_job()))
    comp = net._stage(net.compromised, net._node_s)
    text = net._step.lower(*net._round_inputs(0, comp)).as_text()
    assert "optimization_barrier" in text and net.program.num_nodes == 3


def test_the_example_yaml_runs_through_the_cli(tmp_path):
    """``murmura run examples/configs/decoder_moe_tiny.yaml`` on the CPU."""
    import json
    from pathlib import Path

    from click.testing import CliRunner

    from murmura_tpu.cli import app

    example = Path(__file__).resolve().parents[1] / "examples/configs/decoder_moe_tiny.yaml"
    out = tmp_path / "history.json"
    result = CliRunner().invoke(app, ["run", str(example), "-o", str(out)])
    assert result.exit_code == 0, result.output
    history = json.loads(out.read_text())
    assert len(history["mean_loss"]) == 5
    assert history["mean_loss"][-1] < history["mean_loss"][0]
    assert len(history["agg_moe.held_share"]) == 5


def test_a_large_initial_state_is_the_programs_on_the_host(monkeypatch):
    """From ``LARGE_STATE_BYTES`` on the stacked initial state is drawn and
    cast in one program and the round program keeps it on the host (the
    network's copy is the one on the device); the values are the ones a
    state under the size gets, leaf by leaf on the device."""
    model = build_model("decoder.deepseek_v3", TINY)
    _, data = _two_nodes(2, 2)
    build = lambda: build_round_program(
        model, build_aggregator("fedavg", {}), data, batch_size=2, seed=3,
        param_dtype="bfloat16")
    small = build()
    monkeypatch.setattr(rounds, "LARGE_STATE_BYTES", 1024)
    large = build()
    for a, b in zip(jax.tree_util.tree_leaves(small.init_params),
                    jax.tree_util.tree_leaves(large.init_params)):
        assert isinstance(a, jax.Array) and isinstance(b, np.ndarray)
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert rounds._state_bytes(model, 2, jnp.bfloat16) == 2 * 2 * sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(large.init_params)) // 2


def test_a_large_state_is_averaged_leaf_by_leaf_to_the_same_state(monkeypatch):
    """From ``LARGE_STATE_BYTES`` on, FedAvg's dense mean takes the state
    leaf by leaf and the round never builds its [N, P] row: the same state
    and metrics as through the row, and no concatenate of the leaves."""
    def run():
        net = build_network_from_config(Config.model_validate(_job()))
        comp = net._stage(net.compromised, net._node_s)
        text = net._step.lower(*net._round_inputs(0, comp)).as_text()
        history = net.train(rounds=2, eval_every=1)
        return text, history, [np.asarray(l) for l in jax.tree_util.tree_leaves(net.params)]

    row_text, row_history, row_state = run()
    monkeypatch.setattr(rounds, "LARGE_STATE_BYTES", 1024)
    leaf_text, leaf_history, leaf_state = run()
    width = sum(int(np.prod(l.shape[1:])) for l in row_state)
    assert f"tensor<3x{width}xf32>" in row_text
    assert f"tensor<3x{width}xf32>" not in leaf_text
    for a, b in zip(leaf_state, row_state):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * np.abs(b).max())
    for key in ("mean_loss", "agg_num_neighbors", "agg_moe.held_share"):
        np.testing.assert_allclose(leaf_history[key], row_history[key], rtol=1e-5)


def test_only_the_plain_job_goes_leaf_by_leaf(monkeypatch):
    """An attack needs the row: the job with one keeps it."""
    monkeypatch.setattr(rounds, "LARGE_STATE_BYTES", 1024)
    raw = _job()
    raw["attack"] = {"enabled": True, "type": "gaussian", "percentage": 0.34,
                     "params": {"noise_std": 0.0}}
    net = build_network_from_config(Config.model_validate(raw))
    comp = net._stage(net.compromised, net._node_s)
    text = net._step.lower(*net._round_inputs(0, comp)).as_text()
    width = sum(int(np.prod(l.shape[1:])) for l in jax.tree_util.tree_leaves(net.params))
    assert f"tensor<3x{width}xf32>" in text
    assert build_aggregator("fedavg", {}).leafwise
    assert not build_aggregator("fedavg", {"exchange_offsets": [1, 2]}).leafwise
    assert not build_aggregator("krum", {"num_compromised": 0}).leafwise


@pytest.mark.parametrize("how", ["unload", "params_none"])
def test_unload_drops_the_state_and_the_compiled_programs(how):
    """``Network.unload()``, and ``params = None`` as the benchmark's harness
    spells it before its reference runs, drop the step's and the eval's
    executables with the state; a later call compiles again and trains on."""
    net = build_network_from_config(Config.model_validate(_job()))
    net.train(rounds=1, eval_every=1)
    assert net._step._cache_size() == 1 and net._eval._cache_size() == 1
    kept = jax.tree_util.tree_map(jnp.copy, (net.params, net.agg_state))
    net._step_compiled()
    if how == "unload":
        net.unload()
    else:
        net.params = None
    assert net._step._cache_size() == 0 and net._eval._cache_size() == 0
    assert net._aot_compiled is None and net.params is None
    net.params, net.agg_state = kept
    history = net.train(rounds=1, eval_every=1)
    assert len(history["mean_loss"]) == 2 and net._step._cache_size() == 1


@pytest.mark.parametrize("align,shares", [(1, 2), (4, 2), (16, 2), (4, 0), (4, 1), (4, 8)])
def test_groups_aligned_to_any_tile_give_the_same_layer(align, shares, monkeypatch):
    """An expert's rows start at a multiple of ``GROUP_ALIGN``; whatever the
    multiple (groups over several tiles at 4, one pair a tile at 1) and
    whatever the floor of zero rows behind the last group (none at 0, under
    the rows held at 1, the whole buffer at 8), the result and the gradients
    are the reference's."""
    monkeypatch.setattr(decoder, "GROUP_ALIGN", align)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", shares)
    model, params, ids = build_model("decoder.deepseek_v3", TINY), _weights(), _ids()
    logits, _ = jax.jit(model.apply_train)(params, ids[:, :-1])
    _close(logits, reference.apply(params, ids[:, :-1], "float32")[0])
    loss = lambda f: lambda p: (f(p)[0] ** 2).mean()
    mine = jax.jit(jax.grad(loss(lambda p: model.apply_train(p, ids[:, :-1]))))(params)
    theirs = jax.grad(loss(lambda p: reference.apply(p, ids[:, :-1], "float32")))(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine["moe_layers"]["experts"]),
                    jax.tree_util.tree_leaves(theirs["moe_layers"]["experts"])):
        _close(a, b, 1e-4)


# --- the ladder of the pairs' buffer -----------------------------------------

# Two of eight experts held, tiles of 4 rows, a floor of one even share: 32
# pairs a sequence, a buffer of 12, 24 or 40 rows.
LADDER = dict(TINY, ep_size=4)
LADDER_DOC = dict(DOC, n_routed_experts=2)
# The selection bias of the two expert layers' first six experts ([2, 6]), the
# step each layer's buffer then takes and the share that fits the first.
# No held expert chosen: nothing to hold.  Every position to expert 0 and none
# to expert 1: 16 rows.  Every position to both: all 32 pairs held.
AWAY, ONE, BOTH = [0, 0, 0, 0, 10, 10], [10, -10, 0, 0, 0, 0], [10, 10, 0, 0, 0, 0]
STEPS = {
    "first": ([AWAY, AWAY], [0, 0], 1.0),
    "between": ([ONE, ONE], [1, 1], 0.0),
    "last": ([BOTH, BOTH], [2, 2], 0.0),
    "a_layer_each": ([AWAY, ONE], [0, 1], 0.5),
}


def _ladder_model(monkeypatch):
    monkeypatch.setattr(decoder, "GROUP_ALIGN", 4)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", 1)
    return build_model("decoder.deepseek_v3", LADDER)


@pytest.mark.parametrize("case", sorted(STEPS))
def test_every_step_of_the_ladder_gives_the_reference(case, monkeypatch):
    """The buffer takes the shortest of 12, 24 and 40 rows that holds the
    groups; whichever it takes, no pair is dropped: logits and the experts'
    gradients are the reference's, which computes every expert of every
    position, and ``moe.rows_first_step_share`` says which was taken."""
    bias, steps, share = STEPS[case]
    model, params, ids = _ladder_model(monkeypatch), _weights(LADDER_DOC), _ids(seed=9)
    router = params["moe_layers"]["router"]
    router["bias"] = router["bias"].at[:, :6].set(jnp.asarray(bias, jnp.float32))
    logits, aux = jax.jit(model.apply_train)(params, ids[:, :-1])
    took = np.asarray(aux["step"]["ladder"])
    assert took.shape == (3, 2, 3)
    assert took.argmax(-1).tolist() == [steps] * 3 and took.sum(-1).tolist() == [[1.0, 1.0]] * 3
    held = np.asarray(aux["step"]["counts"])[..., :2].sum(-1)
    assert held.tolist() == [[[0.0, 16.0, 32.0][s] for s in steps]] * 3
    _close(logits, reference.apply(params, ids[:, :-1], "float32")[0])
    loss = lambda f: lambda p: (f(p)[0] ** 2).mean()
    mine = jax.jit(jax.grad(loss(lambda p: model.apply_train(p, ids[:, :-1]))))(params)
    theirs = jax.grad(loss(lambda p: reference.apply(p, ids[:, :-1], "float32")))(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine["moe_layers"]["experts"]),
                    jax.tree_util.tree_leaves(theirs["moe_layers"]["experts"])):
        _close(a, b, 1e-4)
    summed = jax.tree_util.tree_map(lambda c: c.sum(0), aux["step"])
    assert float(model.step_metrics(params, summed)["moe.rows_first_step_share"]) == share


def _computations(text):
    """An HLO module's text as {computation: its lines}."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return {k: "\n".join(v) for k, v in out.items()}


def _reached(computations, name, seen):
    """``name`` and every computation it calls, however deep."""
    if name not in seen and name in computations:
        seen.add(name)
        for callee in re.findall(r"%([\w.\-]+)", computations[name]):
            _reached(computations, callee, seen)
    return seen


def test_the_compiled_gradient_switches_twice_and_keeps_each_size_apart(monkeypatch):
    """The gradient of the scanned, recomputed expert layers: the dispatch
    is one conditional in the forward scan and one in the backward (the
    recomputed forward's is dead: its result is the block's last term), and
    a branch holds arrays of its own size alone: the 12-row branches none
    of 24 or 40 rows (no zero-filled residual of a longer step)."""
    model = _ladder_model(monkeypatch)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    gradient = jax.jit(jax.grad(lambda p, x: (model.apply_train(p, x)[0] ** 2).mean()))
    text = gradient.lower(params, ids).compile().as_text()
    switches = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text)
    assert len(switches) == 2
    computations = _computations(text)
    for branches in switches:
        names = [n.strip().lstrip("%") for n in branches.split(",")]
        assert len(names) == 3
        for name, own in zip(names, (12, 24, 40)):
            body = "\n".join(computations[c] for c in _reached(computations, name, set()))
            rows = {int(d) for d in re.findall(r"\w+\[(\d+)[,\]]", body)}
            assert rows & {12, 24, 40} == {own}, (name, sorted(rows))
