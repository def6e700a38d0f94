"""Plain reference of a ZAYA1 decoder (``model_type: zaya``; Zyphra's
ZAYA1-8B ``config.json``, and compressed convolutional attention as
Figliolia et al., 2025, describe it) as one chip's share of a deployment
runs it: straightforward ``jax.numpy`` in float32, every product through
``precision.py``, whole ``[T, T]`` scores a query head at a time, the held
experts one after another with every position through each (no dispatch),
no kernel.  It imports nothing of ``murmura_tpu``.

For a sequence of ids ``t[0..T)``: ``h = E[t]``; each layer ``h = a1 h + b1
CCA(RMSNorm(h)); x = RMSNorm(h); h = a2 h + b2 MoE(x)`` (``a1, b1, a2, b2``
learned ``[hidden]`` vectors); logits ``= RMSNorm(h) E^T`` (the head is the
embedding, tied).  RMSNorm ``x * rsqrt(mean(x^2) + rms_norm_eps) * g``.
Rows before position 0 are zeros.

CCA, with ``Hq`` query heads and ``Hk`` key/value heads of ``dh``, ``G =
Hq / Hk``, ``g(h) = h // G``: ``q~ = u W_q``, ``k~ = u W_k``; values ``v[t,
g] = [u_t W_v[g, :dh/2] | u_(t-1) W_v[g, dh/2:]]`` (the value shift); ``z =
[q~ | k~]`` through a causal depthwise convolution of ``cca_time0`` taps,
then a causal convolution of ``cca_time1`` taps within each head group
(``dh`` channels in and out), split into ``q_c, k_c``; ``q[t, h] = q_c[t, h]
+ (q~[t, h] + k~[t, g(h)]) / 2``, ``k[t, g] = k_c[t, g] + (mean over the
group's query heads of q~ + k~[t, g]) / 2``; each divided by its L2 norm a
head (``x / max(|x|, 1e-12)``); rotary positions on the first ``dh *
partial_rotary_factor`` channels (``rope_parameters.hybrid.rope_theta``);
``s = tau_h q . k`` causal, softmax, ``o = softmax(s) v``, out ``o W_o``.

Expert layer: ``r = x W_down``; ``r^ = lam r^_prev + (1 - lam) r``, ``lam =
sigmoid(gamma)`` of the layer, ``r^ = r`` in the chip's first layer; ``z =
W3 gelu(W2 gelu(W1 RMSNorm(r^)))`` (exact GELU), every router product in
float32 at ``highest`` whatever the compute dtype (a choice then flips only
on a true near-tie); ``p = softmax(z)`` over all the published experts;
chosen = argmax ``z + b``; ``y = p_chosen SwiGLU_chosen(x)`` where the chosen
expert is held here.  This chip holds the first ``num_experts`` of
``published.num_experts``; what the absent ones would add is left out.

Training rule: ``apply`` returns ``(logits, {"step": counts [B, layers,
experts]})``; no auxiliary loss.  ``after_step``: ``b_e +=
bias_update_speed * sign(mean_e(count) - count_e)`` in every layer.

Departures from the published code, each without effect on a value: the
rotary pairing is interleaved (entries 2i and 2i + 1 turn together), where
the published code pairs halves: one fixed permutation of a head's rotary
channels, the same in ``q`` and ``k``, which relabels drawn weights; every
layer, inside a layer every head's attention and every expert, is
recomputed in the backward pass (``jax.checkpoint``): what is recomputed
gives the bits it gave.  The layers are a loop over static slices of the
stacked leaves and not a ``lax.scan``: a scan over the float32 stacks the
trainer hands ``apply`` holds a float32 copy of every layer's weights
beside their gradients (compiled for a v5e, one node's two steps at the
cell's widths: 1.11 GB of temporaries a layer against 0.51 for the loop),
and at the cell's size the trainer stands beside the three nodes' states
and the blocks it has trained.  ``_values``, ``_convolved`` and
``_depth_average`` are the mechanisms' own steps, one function each.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference.precision import HIGHEST, product

# ``apply(params, x, dtype)`` is the interface every reference has, and no
# leaf's shape gives the rotary base, the rotary share of a head, the norm's
# epsilon or the published expert count: they are the configuration's that
# ``init`` was last given (the benchmark draws the weights, through
# ``init``, before anything applies them).
_DOC: Optional[dict] = None


def _sizes(doc):
    published = doc.get("published", {})
    return {
        "layers": int(doc["num_layers"]), "held": int(doc["num_experts"]),
        "routed": int(published.get("num_experts", doc["num_experts"])),
        "hq": int(doc["num_attention_heads"]), "hk": int(doc["num_key_value_heads"]),
        "dh": int(doc["head_dim"]), "hidden": int(doc["hidden_size"]),
        "width": int(doc["moe_intermediate_size"]), "router": int(doc["router_hidden_size"]),
        "time0": int(doc["cca_time0"]), "time1": int(doc["cca_time1"]),
        "rotary": int(int(doc["head_dim"]) * float(doc["partial_rotary_factor"])),
        "vocab": int(doc["vocab_size"]),
    }


def init(key, doc: dict):
    """One node's parameters, the program's tree path for path: matrices
    normal with ``initializer_range``; a convolution's taps uniform within
    ``1 / sqrt(fan_in)`` (fan_in: channels in x taps); norms, residual
    scales 1; temperatures ``sqrt(dh)``; ``gamma`` and the selection bias
    0."""
    global _DOC
    _DOC = doc
    z = _sizes(doc)
    std, n = float(doc["initializer_range"]), z["layers"]
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
    taps = lambda k, shape, fan_in: jax.random.uniform(
        k, shape, jnp.float32, -1.0, 1.0) / fan_in ** 0.5
    ones = lambda *shape: jnp.ones((n,) + shape, jnp.float32)
    hidden, hq, hk, dh, r = z["hidden"], z["hq"], z["hk"], z["dh"], z["router"]
    ke, kq, kk, kv, k0, k1, ko, kd, kr1, kr2, kr3, kx = jax.random.split(key, 12)
    kg, ku, kdn = jax.random.split(kx, 3)
    return {
        "embed": normal(ke, (z["vocab"], hidden)),
        "layers": {
            "attn_norm": ones(hidden),
            "cca": {
                "q": normal(kq, (n, hidden, hq * dh)),
                "k": normal(kk, (n, hidden, hk * dh)),
                "v": normal(kv, (n, hidden, hk * dh)),
                "conv0": taps(k0, (n, z["time0"], (hq + hk) * dh), z["time0"]),
                "conv1": taps(k1, (n, z["time1"], hq + hk, dh, dh), z["time1"] * dh),
                "temperature": jnp.full((n, hq), dh ** 0.5, jnp.float32),
                "o": normal(ko, (n, hq * dh, hidden)),
            },
            "scales": {"attn_h": ones(hidden), "attn_out": ones(hidden),
                       "moe_h": ones(hidden), "moe_out": ones(hidden)},
            "ffn_norm": ones(hidden),
            "router": {
                "down": normal(kd, (n, hidden, r)),
                "depth": jnp.zeros((n,), jnp.float32),
                "norm": ones(r),
                "w1": normal(kr1, (n, r, r)),
                "w2": normal(kr2, (n, r, r)),
                "w3": normal(kr3, (n, r, z["routed"])),
                "bias": jnp.zeros((n, z["routed"]), jnp.float32),
            },
            "experts": {
                "gate": normal(kg, (n, z["held"], hidden, z["width"])),
                "up": normal(ku, (n, z["held"], hidden, z["width"])),
                "down": normal(kdn, (n, z["held"], z["width"], hidden)),
            },
        },
        "final_norm": jnp.ones((hidden,), jnp.float32),
    }


def _matmul(a, b, dtype):
    return product(lambda x, y: jnp.dot(x, y, precision=HIGHEST), a, b, dtype)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _later(x, lag):
    """``x`` [T, ...] moved ``lag`` positions later, zeros before 0."""
    return jnp.pad(x, [(lag, 0)] + [(0, 0)] * (x.ndim - 1))[:x.shape[0]]


def _rotate(x, theta):
    """Rotary positions on the last axis of [T, d]: entries 2i and 2i + 1 of
    position p turn by ``p * theta ** (-2i / d)``."""
    d, t = x.shape[-1], x.shape[0]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _values(v):
    """The value shift: a head's first half of channels from the current
    position, its second half from the one before.  ``v`` [T, Hk, dh]."""
    half = v.shape[-1] // 2
    return jnp.concatenate([v[..., :half], _later(v[..., half:], 1)], axis=-1)


def _convolved(z, a, b, dtype):
    """The two causal convolutions of ``z`` [T, groups, dh]: depthwise with
    taps ``a`` [taps, groups * dh], then within each group with taps ``b``
    [taps, groups, dh out, dh in]; tap ``lag`` meets position ``t - lag``."""
    a = a.reshape((a.shape[0],) + z.shape[1:])
    z0 = sum(a[lag] * _later(z, lag) for lag in range(a.shape[0]))
    within = lambda x, w: jnp.einsum("tgj,gij->tgi", x, w, precision=HIGHEST)
    return sum(product(within, _later(z0, lag), b[lag], dtype) for lag in range(b.shape[0]))


def _depth_average(r, carried, gamma, first):
    """``r^ = lam r^_prev + (1 - lam) r``, ``lam = sigmoid(gamma)``; ``r``
    itself in the chip's first layer."""
    lam = jnp.where(first, 0.0, jax.nn.sigmoid(gamma))
    return lam * carried + (1.0 - lam) * r


def _cca(p, u, doc, dtype):
    z, t = _sizes(doc), u.shape[0]
    hq, hk, dh, rot = z["hq"], z["hk"], z["dh"], z["rotary"]
    theta = float(doc["rope_parameters"]["hybrid"]["rope_theta"])
    q0 = _matmul(u, p["q"], dtype).reshape(t, hq, dh)
    k0 = _matmul(u, p["k"], dtype).reshape(t, hk, dh)
    v = _values(_matmul(u, p["v"], dtype).reshape(t, hk, dh))
    mixed = _convolved(jnp.concatenate([q0, k0], axis=1), p["conv0"], p["conv1"], dtype)
    group = hq // hk
    of = jnp.arange(hq) // group  # a query head's key/value head
    q = mixed[:, :hq] + (q0 + k0[:, of]) / 2
    k = mixed[:, hq:] + (q0.reshape(t, hk, group, dh).mean(axis=2) + k0) / 2
    turn = lambda x: jnp.concatenate([_rotate(x[..., :rot], theta), x[..., rot:]], axis=-1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint  # a head's whole [T, T] scores, not all heads' at once
    def head(parts):
        qh, kh, vh, tau = parts  # [T, dh] each, and the head's temperature
        s = tau * _matmul(turn(_unit(qh)), turn(_unit(kh)).T, dtype)
        return _matmul(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vh, dtype)

    by_head = lambda a: jnp.swapaxes(a, 0, 1)
    o = by_head(jax.lax.map(head, (by_head(q), by_head(k[:, of]), by_head(v[:, of]),
                                   p["temperature"])))
    return _matmul(o.reshape(t, hq * dh), p["o"], dtype)


def _moe(p, x, carried, first, doc, dtype):
    """The layer's routed part for one sequence, the counts of its choice
    over all experts, and the depth-averaged state it carries on."""
    z, router = _sizes(doc), p["router"]
    dot = lambda a, b: jnp.dot(a, b, precision=HIGHEST)
    gelu = partial(jax.nn.gelu, approximate=False)
    averaged = _depth_average(dot(x, router["down"]), carried, router["depth"], first)
    scores = dot(gelu(dot(gelu(dot(_rms_norm(averaged, router["norm"],
                                              float(doc["rms_norm_eps"])),
                                    router["w1"])), router["w2"])), router["w3"])
    chosen = jnp.argmax(scores + router["bias"], axis=-1)
    weight = jnp.take_along_axis(jax.nn.softmax(scores, axis=-1), chosen[:, None], -1)[:, 0]

    @jax.checkpoint  # its float32 activations are not kept for all experts at once
    def add_expert(y, held):  # expert e of every position, weighted where chosen
        e, expert = held
        inner = jax.nn.silu(_matmul(x, expert["gate"], dtype)) * _matmul(
            x, expert["up"], dtype)
        return y + jnp.where(chosen == e, weight, 0.0)[:, None] * _matmul(
            inner, expert["down"], dtype), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(z["held"]), p["experts"]))
    counts = (chosen[:, None] == jnp.arange(z["routed"])).sum(0).astype(jnp.float32)
    return y, counts, averaged


def _sequence(params, ids, doc, dtype):
    z, eps = _sizes(doc), float(doc["rms_norm_eps"])

    @jax.checkpoint
    def layer(h, carried, p, first):
        s = p["scales"]
        h = s["attn_h"] * h + s["attn_out"] * _cca(
            p["cca"], _rms_norm(h, p["attn_norm"], eps), doc, dtype)
        y, counts, carried = _moe(p, _rms_norm(h, p["ffn_norm"], eps), carried, first,
                                  doc, dtype)
        return s["moe_h"] * h + s["moe_out"] * y, carried, counts

    h = params["embed"][ids]
    carried = jnp.zeros((ids.shape[0], z["router"]), jnp.float32)
    counts = []
    for i in range(z["layers"]):
        h, carried, c = layer(h, carried, jax.tree_util.tree_map(lambda l: l[i], params["layers"]),
                              i == 0)
        counts.append(c)
    logits = _matmul(_rms_norm(h, params["final_norm"], eps), params["embed"].T, dtype)
    return logits, jnp.stack(counts)


def apply(params, x, dtype: str):
    """``x`` [B, T] ids -> ``(logits [B, T, V], {"step": counts [B, layers,
    experts]})``."""
    if _DOC is None:
        raise RuntimeError("zaya1.apply before init(key, doc): no sizes")
    doc = _DOC
    params = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), params)
    rows = [_sequence(params, ids, doc, dtype) for ids in x]
    logits, counts = (jnp.stack(part) for part in zip(*rows))
    return logits, {"step": counts}


def after_step(params, counts, doc: dict):
    """The selection bias of every layer steps by the sign of each expert's
    load: ``counts`` [layers, experts] of the step's batch."""
    router = params["layers"]["router"]
    step = float(doc["bias_update_speed"]) * jnp.sign(
        counts.mean(axis=-1, keepdims=True) - counts)
    layers = {**params["layers"], "router": {**router, "bias": router["bias"] + step}}
    return {**params, "layers": layers}
