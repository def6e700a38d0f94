"""Plain reference of a DeepSeek-V3-family decoder (``model_type:
deepseek_v3``; arXiv:2412.19437, and the published ``modeling_deepseek.py``
of huggingface.co/moonshotai/Moonlight-16B-A3B) as one chip's share of a
deployment runs it: straightforward ``jax.numpy`` in float32, every product
through ``precision.py``, whole ``[T, T]`` scores, the held experts one
after another with every position through each (no dispatch), no kernel.
It imports nothing of ``murmura_tpu``.

For a sequence of ids ``t[0..T)``: ``h = E[t]``; each layer ``h = h +
Attn(RMSNorm(h)); h = h + FFN(RMSNorm(h))``; logits ``= RMSNorm(h) W_head``;
RMSNorm ``x * rsqrt(mean(x^2) + rms_norm_eps) * g``.

Latent attention: ``q = x W_q -> [T, heads, nope + rope]``; ``[c, k_r] =
split(x W_kva, kv_lora_rank | rope)``; ``c = RMSNorm(c)``; ``[k_n, v] =
split(c W_kvb -> [T, heads, nope + v], nope | v)``; rotary positions
(``rope_theta``) on ``q_r`` and on ``k_r``, one vector a position for all
heads; ``s = (q_n . k_n + q_r . k_r) / sqrt(nope + rope)``, causal, softmax,
``out = (softmax(s) v) W_o``.  No bias anywhere.

Expert layer: ``sc = sigmoid(x W_r)`` over all the published experts, the
product in float32 at ``highest`` whatever the compute dtype (the published
code casts to float32 there; a choice then flips only on a true near-tie);
chosen = top-k of ``sc + b``; ``w = sc[chosen] / (sum sc[chosen] + 1e-20) *
routed_scaling_factor``, normalised over all chosen; ``y = Shared(x) + sum
over chosen e held here of w_e Expert_e(x)``, experts SwiGLU.  This chip
holds the first ``n_routed_experts`` of ``published.n_routed_experts``; what
the absent ones would add is left out, and the partial result goes on.

Training rule: ``apply`` returns ``(logits, {"loss", "step"})``: the
sequence-wise balance loss (``seq_aux``: a sequence's ``sum_e f_e P_e``,
``f_e = E / (k T)`` x the count of its positions choosing e, ``P_e`` the mean
over its positions of ``sc_e / sum sc``), averaged over the batch, and each
sample's counts ``[B, expert layers, E]``.  ``after_step``: ``b_e +=
bias_update_speed * sign(mean_e(count) - count_e)`` in every expert layer.

Departures from the published code, each without effect on a value:
- rotary pairing: interleaved (entries 2i and 2i + 1 turn together); the
  published code first de-interleaves ``[.., d/2, 2] -> [.., 2, d/2]`` and
  then rotates halves: the same pairs, and ``q_r . k_r`` is the same;
- the shared experts are one SwiGLU of width ``n_shared_experts *
  moe_intermediate_size`` (as published);
- the balance loss and the bias step are DeepSeek-V3's report's; the
  published inference code has neither (``assumed`` in the configuration);
- every layer, inside a layer every head's attention and inside an expert
  layer every expert, is recomputed in the backward pass
  (``jax.checkpoint``); the heads are a ``lax.map`` and the held experts a
  ``lax.scan`` over their stacked matrices.  At the cell's size the float32
  scores of sixteen heads at once (1.07 GiB an array, several of them in a
  layer's backward pass) and eight experts' activations, beside float32
  parameters and gradients, leave the step no room on the chip: the
  runtime reserves a program's temporaries below the lowest live array, and
  after the window the harness's own arrays leave 5.16 GiB there (PERF.md
  §6 PR 34).  What is recomputed gives the bits it gave.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.precision import HIGHEST, product


# ``apply(params, x, dtype)`` is the interface every reference has, and no
# leaf's shape gives the heads, the split of a head, the experts a token
# takes, the rotary base, the norm's epsilon or the routed scaling factor:
# they are the configuration's that ``init`` was last given (the benchmark
# draws the weights, through ``init``, before anything applies them).
_DOC: Optional[dict] = None


def _sizes(doc):
    published = doc.get("published", {})
    dense = min(int(doc["first_k_dense_replace"]), int(doc["num_layers"]))
    return {
        "dense": dense, "moe": int(doc["num_layers"]) - dense,
        "held": int(doc["n_routed_experts"]),
        "routed": int(published.get("n_routed_experts", doc["n_routed_experts"])),
        "heads": int(doc["num_attention_heads"]), "nope": int(doc["qk_nope_head_dim"]),
        "rope": int(doc["qk_rope_head_dim"]), "v": int(doc["v_head_dim"]),
        "latent": int(doc["kv_lora_rank"]), "hidden": int(doc["hidden_size"]),
    }


def init(key, doc: dict):
    """One node's parameters, the program's tree path for path: normal with
    ``initializer_range``, norms 1, the selection bias 0."""
    global _DOC
    _DOC = doc
    z = _sizes(doc)
    std = float(doc["initializer_range"])
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
    hidden, heads = z["hidden"], z["heads"]

    def block(k, layers):
        ka, kf = jax.random.split(k)
        kq, kva, kvb, ko = jax.random.split(ka, 4)
        return {
            "attn_norm": jnp.ones((layers, hidden), jnp.float32),
            "attn": {
                "q": normal(kq, (layers, hidden, heads * (z["nope"] + z["rope"]))),
                "kv_a": normal(kva, (layers, hidden, z["latent"] + z["rope"])),
                "kv_norm": jnp.ones((layers, z["latent"]), jnp.float32),
                "kv_b": normal(kvb, (layers, z["latent"], heads * (z["nope"] + z["v"]))),
                "o": normal(ko, (layers, heads * z["v"], hidden)),
            },
            "ffn_norm": jnp.ones((layers, hidden), jnp.float32),
        }, kf

    def ffn(k, lead, width):
        kg, ku, kd = jax.random.split(k, 3)
        return {"gate": normal(kg, lead + (hidden, width)),
                "up": normal(ku, lead + (hidden, width)),
                "down": normal(kd, lead + (width, hidden))}

    ke, kd, km, kh = jax.random.split(key, 4)
    params = {"embed": normal(ke, (int(doc["vocab_size"]), hidden))}
    if z["dense"]:
        layer, kf = block(kd, z["dense"])
        layer["ffn"] = ffn(kf, (z["dense"],), int(doc["intermediate_size"]))
        params["dense_layers"] = layer
    if z["moe"]:
        layer, kf = block(km, z["moe"])
        kr, ks, kx = jax.random.split(kf, 3)
        width = int(doc["moe_intermediate_size"])
        layer["router"] = {"w": normal(kr, (z["moe"], hidden, z["routed"])),
                           "bias": jnp.zeros((z["moe"], z["routed"]), jnp.float32)}
        layer["shared"] = ffn(ks, (z["moe"],), int(doc["n_shared_experts"]) * width)
        layer["experts"] = ffn(kx, (z["moe"], z["held"]), width)
        params["moe_layers"] = layer
    params["final_norm"] = jnp.ones((hidden,), jnp.float32)
    params["head"] = normal(kh, (hidden, int(doc["vocab_size"])))
    return params


def _matmul(a, b, dtype):
    return product(lambda x, y: jnp.dot(x, y, precision=HIGHEST), a, b, dtype)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """Rotary positions on the last axis of [T, ..., d]: entries 2i and
    2i + 1 of position p turn by ``p * theta ** (-2i / d)``."""
    d, t = x.shape[-1], x.shape[0]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(p, x, dtype):
    inner = jax.nn.silu(_matmul(x, p["gate"], dtype)) * _matmul(x, p["up"], dtype)
    return _matmul(inner, p["down"], dtype)


def _attention(p, x, doc, dtype):
    z, t = _sizes(doc), x.shape[0]
    theta, eps = float(doc["rope_theta"]), float(doc["rms_norm_eps"])
    q = _matmul(x, p["q"], dtype).reshape(t, z["heads"], z["nope"] + z["rope"])
    kv = _matmul(x, p["kv_a"], dtype)
    c = _rms_norm(kv[:, :z["latent"]], p["kv_norm"], eps)
    k_r = _rotate(kv[:, z["latent"]:], theta)
    kn_v = _matmul(c, p["kv_b"], dtype).reshape(t, z["heads"], z["nope"] + z["v"])
    k_n, v = kn_v[..., :z["nope"]], kn_v[..., z["nope"]:]
    q_n, q_r = q[..., :z["nope"]], _rotate(q[..., z["nope"]:], theta)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint  # a head's whole [T, T] scores, not all heads' at once
    def head(parts):
        qn, qr, kn, vh = parts  # [T, nope], [T, rope], [T, nope], [T, v]
        s = (_matmul(qn, kn.T, dtype) + _matmul(qr, k_r.T, dtype)) / math.sqrt(
            z["nope"] + z["rope"])
        return _matmul(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vh, dtype)

    by_head = lambda a: jnp.swapaxes(a, 0, 1)
    o = by_head(jax.lax.map(head, (by_head(q_n), by_head(q_r), by_head(k_n), by_head(v))))
    o = o.reshape(t, z["heads"] * z["v"])
    return _matmul(o, p["o"], dtype)


def _moe(p, x, doc, dtype):
    """The layer's result for one sequence, the counts of its choice over
    all experts, and its balance loss."""
    z, k = _sizes(doc), int(doc["num_experts_per_tok"])
    sc = jax.nn.sigmoid(jnp.dot(x, p["router"]["w"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(sc + p["router"]["bias"], k)
    picked = jnp.take_along_axis(sc, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * float(
        doc["routed_scaling_factor"])
    @jax.checkpoint  # its float32 activations are not kept for all eight at once
    def add_expert(y, held):  # expert e of every position, weighted where chosen
        e, expert = held
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        return y + w_e[:, None] * _swiglu(expert, x, dtype), None

    y, _ = jax.lax.scan(  # the held experts one after another
        add_expert, _swiglu(p["shared"], x, dtype), (jnp.arange(z["held"]), p["experts"]))
    counts = (chosen[..., None] == jnp.arange(z["routed"])).sum((0, 1)).astype(jnp.float32)
    share = (sc / sc.sum(-1, keepdims=True)).mean(0)
    balance = (counts * (z["routed"] / (k * x.shape[0])) * share).sum()
    return y, counts, balance


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda l: l[i], tree)


def _sequence(params, ids, doc, dtype):
    z, eps = _sizes(doc), float(doc["rms_norm_eps"])
    h = params["embed"][ids]

    @jax.checkpoint
    def dense_block(h, p):
        h = h + _attention(p["attn"], _rms_norm(h, p["attn_norm"], eps), doc, dtype)
        return h + _swiglu(p["ffn"], _rms_norm(h, p["ffn_norm"], eps), dtype)

    @jax.checkpoint
    def moe_block(h, p):
        h = h + _attention(p["attn"], _rms_norm(h, p["attn_norm"], eps), doc, dtype)
        y, counts, balance = _moe(p, _rms_norm(h, p["ffn_norm"], eps), doc, dtype)
        return h + y, counts, balance

    for i in range(z["dense"]):
        h = dense_block(h, _layer(params["dense_layers"], i))
    counts, balance = [], jnp.zeros((), jnp.float32)
    for i in range(z["moe"]):
        h, c, b = moe_block(h, _layer(params["moe_layers"], i))
        counts.append(c)
        balance = balance + b
    logits = _matmul(_rms_norm(h, params["final_norm"], eps), params["head"], dtype)
    counts = jnp.stack(counts) if counts else jnp.zeros((0, z["routed"]), jnp.float32)
    return logits, counts, balance


def apply(params, x, dtype: str):
    """``x`` [B, T] ids -> ``(logits [B, T, V], {"loss": the balance loss,
    the batch's mean; "step": counts [B, expert layers, experts]})``."""
    if _DOC is None:
        raise RuntimeError("deepseek_v3.apply before init(key, doc): no sizes")
    doc = _DOC
    params = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), params)
    rows = [_sequence(params, ids, doc, dtype) for ids in x]
    logits, counts, balance = (jnp.stack(part) for part in zip(*rows))
    return logits, {"loss": balance.mean(), "step": counts}


def after_step(params, counts, doc: dict):
    """The selection bias of every expert layer steps by the sign of each
    expert's load: ``counts`` [expert layers, experts] of the step's batch."""
    if "moe_layers" not in params:
        return params
    router = params["moe_layers"]["router"]
    step = float(doc["bias_update_speed"]) * jnp.sign(
        counts.mean(axis=-1, keepdims=True) - counts)
    layers = {**params["moe_layers"], "router": {**router, "bias": router["bias"] + step}}
    return {**params, "moe_layers": layers}
