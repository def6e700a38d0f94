"""The routed experts' labels and padding counter (models/decoder.py,
models/zaya.py; docs/OBSERVABILITY.md), on the tiny decoders of
``test_decoder.py`` and ``test_zaya.py`` on the CPU.

(a) One node-step's gradient, lowered for a TPU (there a grouped product is
one ``ragged_dot``): every operation under ``murmura.experts``, forward,
recomputed and backward, carries ``murmura.rows`` or ``murmura.pairs``
inside it, but for the grouped products with ``silu(gate) * up`` between
them and the conditional that picks the buffer's size; the labels are
metadata only (the program without them is the same text), and every
chain that was there keeps its operations.  (b) ``moe.padding_share`` is a recount from
the step's counts, ``ladder``'s floor and ``GROUP_ALIGN``.  (c) A network's
recorded rounds land in ``host_spans``' table ``counters``.
"""

import collections
import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import murmura_tpu.utils  # noqa: F401 - the package's import order (ROADMAP D16)
from benchmark.trace_reduce import chain_of
from murmura_tpu.config import Config
from murmura_tpu.models import decoder
from murmura_tpu.models.registry import build_model
from murmura_tpu.telemetry import host_spans
from murmura_tpu.utils.factories import build_network_from_config

import test_decoder
import test_zaya

MODELS = {
    "moonlight": ("decoder.deepseek_v3", test_decoder.TINY, test_decoder._weights),
    "zaya1": ("decoder.zaya1", test_zaya.TINY, test_zaya._weights),
}
INNER = ("murmura.rows", "murmura.pairs")
EXPERTS = "murmura.train/murmura.experts"
# What stays under ``murmura.experts`` alone, by (operation, the last part
# of its op_name): the grouped products (forward, recomputed and backward,
# the transpose a weight's gradient takes, the sum of the two products'
# gradients with respect to the buffer) and the casts of their operands,
# ``silu(gate) * up`` between them and its gradient, and the conditional
# with its index (and, where the recomputed forward's conditional is live,
# a constant of its differentiable call).
ALONE = {
    ("chlo.ragged_dot", "ragged_dot_general"), ("stablehlo.transpose", "transpose"),
    ("stablehlo.add", "add_any"), ("stablehlo.convert", "convert_element_type"),
    ("stablehlo.case", "cond"), ("stablehlo.clamp", "clamp"),
    ("stablehlo.constant", "cond"), ("stablehlo.constant", "custom_vjp_call"),
    ("func.call", "jit(silu)"), ("func.call", "jvp(jit(silu))"),
    ("func.call", "transpose(jvp(jit(silu)))"), ("stablehlo.constant", "jit:"),
    ("stablehlo.constant", "logistic"), ("stablehlo.multiply", "mul"),
    ("stablehlo.add", "add"), ("stablehlo.subtract", "sub"), ("stablehlo.divide", "div"),
    ("stablehlo.negate", "neg"), ("stablehlo.exponential", "exp"),
    ("stablehlo.broadcast_in_dim", "add"), ("stablehlo.broadcast_in_dim", "sub"),
    ("stablehlo.broadcast_in_dim", "div"),
}

# ---- the operations of a lowered module, with their chains of labels ------

_DEF = re.compile(r"^(#loc\d*) = (loc\(.*\))$", re.M)
_NAMED = re.compile(r'^loc\("((?:[^"\\]|\\.)*)"\((#loc\d*)\)\)$')
_FUNC = re.compile(r"func\.func \w+ @([\w.\-]+)")
_START = re.compile(r'^\s*(?:%[^=]+ = )?"?((?:stablehlo|chlo|func)\.[a-z_]+)"?')
_LOC = re.compile(r"loc\((#loc\d*)\)\s*$")
_CALL = re.compile(r"\bcall @([\w.\-]+)")


def operations(text):
    """``[(operation, op_name)]`` of a lowered module's text with its
    locations (``as_text(debug_info=True)``), in order, an operation with
    regions (a conditional, a loop) where it closes; a private function's
    operations once for each call of it, their op_name under the call's."""
    names = {}
    for ref, loc in _DEF.findall(text):
        named = _NAMED.match(loc)
        names[ref] = named.group(1) if named else ""
    body, func, open_ops = collections.defaultdict(list), None, []
    for line in text.splitlines():
        head = _FUNC.search(line)
        if head:
            func, open_ops = head.group(1), []
            continue
        start, loc = _START.match(line), _LOC.search(line)
        if start and not loc:
            open_ops.append(start.group(1))  # its regions follow
        elif loc and (start or (line.lstrip().startswith("}") and open_ops)):
            op = start.group(1) if start else open_ops.pop()
            call = _CALL.search(line)
            body[func].append((op, names[loc.group(1)], call and call.group(1)))
    prefixes, order = {"main": [""]}, ["main"]
    for func in order:
        for _, name, callee in body[func]:
            if callee:
                if callee not in prefixes:
                    order.append(callee)
                prefixes.setdefault(callee, []).extend(
                    p + "/" + name for p in prefixes[func])
    return [(op, prefix + "/" + name) for func in order
            for op, name, _ in body[func] if not op.endswith(".return")
            for prefix in prefixes[func]]


def _without_inner(chain):
    kept = [c for c in (chain or "").split("/") if c and c not in INNER]
    return "/".join(c for i, c in enumerate(kept) if i == 0 or c != kept[i - 1]) or None


def _lowered(name, inner=True, train=True):
    """One sequence's gradient (``train``) or forward under its stage's
    label, lowered for a TPU, with the labels inside ``murmura.experts`` or
    without: (text without locations, operations)."""
    factory, tiny, _ = MODELS[name]
    model = build_model(factory, dict(tiny, compute_dtype="bfloat16"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def loss(p, x):
        with jax.named_scope("murmura.train"):
            return (model.apply_train(p, x)[0].astype(jnp.float32) ** 2).mean()

    def forward(p, x):
        with jax.named_scope("murmura.eval"):
            return model.apply(p, x)

    with pytest.MonkeyPatch.context() as monkeypatch:
        if not inner:
            scope = jax.named_scope
            monkeypatch.setattr(jax, "named_scope",
                                lambda label: nullcontext() if label in INNER else scope(label))
        traced = jax.jit(jax.grad(loss) if train else forward).trace(params, ids)
        lowered = traced.lower(lowering_platforms=("tpu",))
    return lowered.as_text(), operations(lowered.as_text(debug_info=True))


@pytest.fixture(scope="module", params=sorted(MODELS))
def gradient(request):
    return request.param, _lowered(request.param), _lowered(request.param, inner=False)


def test_the_labels_are_metadata_and_every_chain_keeps_its_operations(gradient):
    _, (text, ops), (plain_text, plain_ops) = gradient
    assert text == plain_text
    assert [op for op, _ in ops] == [op for op, _ in plain_ops]
    for (op, name), (_, plain) in zip(ops, plain_ops):
        assert _without_inner(chain_of([name])) == chain_of([plain]), (op, name, plain)


def test_under_the_experts_label_an_inner_one_but_for_products_and_conditional(gradient):
    model, (_, ops), _ = gradient
    alone, inner = collections.Counter(), collections.Counter()
    for op, name in ops:
        chain = chain_of([name]) or ""
        if chain == EXPERTS:
            alone[(op, name.rsplit("/", 1)[-1])] += 1
        elif chain.startswith(EXPERTS + "/"):
            backward = "transpose(" in name.rsplit("murmura.experts", 1)[-1]
            inner[(chain.rsplit("/", 1)[-1], backward)] += 1
    assert set(alone) <= ALONE, sorted(set(alone) - ALONE)
    # Three products a step of the ladder, forward, recomputed forward and
    # two in the backward pass: 12 a step; 15 in ZAYA1, whose residual scale
    # needs the experts' result in the backward pass, so that the layer's
    # recomputed forward runs its conditional too.
    steps = len(decoder.ladder(16, 2 if model == "moonlight" else 1, 4, 8)[1])
    products = 12 if model == "moonlight" else 15
    assert alone[("chlo.ragged_dot", "ragged_dot_general")] == products * steps
    assert ("stablehlo.case", "cond") in alone
    # Where each pair goes is integers: it has no backward pass.
    assert set(inner) == {(label, backward) for label in INNER for backward in (False, True)
                          if label != "murmura.rows" or not backward}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_eval_carries_the_same_labels(model):
    _, ops = _lowered(model, train=False)
    chains = {chain_of([name]) for _, name in ops}
    assert {f"murmura.eval/murmura.experts/{label}" for label in INNER} <= chains
    assert "murmura.eval/murmura.experts" in chains


# ---- the padding counter ---------------------------------------------------


def _recount(counts, t, top_k, held, first_held, n):
    """Held pairs over rows multiplied, by hand: each held expert's pairs in
    whole tiles of ``GROUP_ALIGN``, at least ``ladder``'s floor, a
    sequence and a layer at a time."""
    floor_rows = decoder.ladder(t, top_k, held, n)[0]
    mine = np.asarray(counts, np.float64)[..., first_held:first_held + held]
    tiles = -(-mine // decoder.GROUP_ALIGN) * decoder.GROUP_ALIGN
    rows = np.maximum(tiles.sum(-1), floor_rows)
    return 1.0 - mine.sum() / rows.sum()


@pytest.mark.parametrize("align,shares", [(512, 2), (4, 1), (4, 0), (1, 2), (1, 0)])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_padding_share_is_a_recount_from_the_counts(model, align, shares, monkeypatch):
    monkeypatch.setattr(decoder, "GROUP_ALIGN", align)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", shares)
    factory, tiny, weights = MODELS[model]
    net = build_model(factory, tiny)
    params = weights()
    ids = jax.random.randint(jax.random.PRNGKey(5), (3, 16), 0, 96)
    _, aux = jax.jit(net.apply_train)(params, ids)
    counts = np.asarray(aux["step"]["counts"])  # [sequences, layers, experts]
    top_k = tiny.get("num_experts_per_tok", 1)
    n = tiny.get("n_routed_experts", tiny.get("num_experts"))
    held = n // tiny["ep_size"]
    summed = jax.tree_util.tree_map(lambda c: c.sum(0), aux["step"])
    got = float(net.step_metrics(params, summed)["moe.padding_share"])
    want = _recount(counts, 16, top_k, held, tiny["ep_rank"] * held, n)
    assert got == pytest.approx(want, rel=1e-6, abs=1e-7)
    assert 0.0 <= got < 1.0
    if align == 1 and not shares:  # a pair a row and no floor: no padding
        assert got == 0.0


def test_an_even_router_gives_the_issues_hand_values():
    """At the cells' shapes with every held expert holding its even share:
    Moonlight 1 - 3,072 / 8,192 (16 tiles), ZAYA1 1 - 2,048 / 6,144 (12)."""
    for t, top_k, n, held, rows, want in ((4096, 6, 64, 8, 8192, 0.625),
                                          (4096, 1, 16, 8, 6144, 1 - 2048 / 6144)):
        counts = np.zeros((1, n), np.float32)
        counts[0, :held] = t * top_k / n
        got = decoder.rows_multiplied(jnp.asarray(counts), t, top_k, held, 0, n)
        assert float(got[0]) == rows
        share = decoder.router_counters(
            jnp.asarray(counts), jnp.ones((1, 1)), got, jnp.zeros(()), 0, held
        )["moe.padding_share"]
        assert float(share) == pytest.approx(want, rel=1e-6)


# ---- the round's counters in the always-on tables ---------------------------


def test_the_recorded_rounds_land_in_the_counters_table(tmp_path):
    raw = test_decoder._job()
    net = build_network_from_config(Config.model_validate(raw))
    net.train(rounds=1, eval_every=1)
    with jax.profiler.trace(str(tmp_path)):
        net.train(rounds=2, eval_every=1)
    net.train(rounds=1, eval_every=1)
    totals = host_spans.totals()
    name = "agg_moe.padding_share"
    rounds = totals["counters"][name][0] - totals["counters_before_session"][name][0]
    rise = totals["counters"][name][1] - totals["counters_before_session"][name][1]
    window = net.history[name][-3:]
    assert rounds == 3 and rise == pytest.approx(sum(window), rel=1e-12)
    assert all(0.0 < v < 1.0 for v in window)
    for key, values in net.history.items():
        if key.startswith("agg_"):
            assert totals["counters"][key][0] >= len(values)
