"""A ZAYA1 decoder (``model_type: zaya``) as a node's model: compressed
convolutional attention (CCA) with grouped query heads, then a layer of
routed experts under a router that is a small MLP over a state carried
from layer to layer; learned scales on both terms of every residual sum;
the head is the embedding's transpose.

For one sequence of ids ``t[0..T)``: ``h = E[t]``; each layer

    h = a1 * h + b1 * CCA(RMSNorm(h));  x = RMSNorm(h)
    h = a2 * h + b2 * Experts(x)

with ``a1, b1, a2, b2`` learned ``[hidden]`` vectors (initial value 1);
logits ``= RMSNorm(h) E^T``.  Products take operands in the compute dtype
and accumulate in float32 (``decoder._einsum``); norms, the softmaxes, the
router and the residual stream are float32.  Rows before position 0 are
zeros.  Notation: ``Hq`` query heads and ``Hk`` key/value heads of ``dh``
channels, ``G = Hq / Hk``, ``g(h) = h // G``.

- *CCA* (Figliolia et al., 2025, "Compressed Convolutional Attention",
  as this configuration's keys set it).  ``q~ = u W_q [T, Hq, dh]``,
  ``k~ = u W_k [T, Hk, dh]``.  Values: each key/value head's first
  ``dh / 2`` channels from the current position, its last from the one
  before (``v[t, g] = [u_t W_v[g, :dh/2] | u_(t-1) W_v[g, dh/2:]]``).
  ``z = [q~ | k~]`` (``Hq + Hk`` head groups) is mixed by two causal
  convolutions: depthwise over time with ``cca_time0`` taps, ``z0[t, c] =
  sum_tau a[tau, c] z[t - tau, c]``; then within each head group over time
  with ``cca_time1`` taps, ``z1[t, g, i] = sum_tau sum_j B[tau, g, i, j]
  z0[t - tau, g, j]``; ``z1`` splits into ``q_c, k_c``.  The q-k mean in
  its grouped form: ``q[t, h] = q_c[t, h] + (q~[t, h] + k~[t, g(h)]) / 2``,
  ``k[t, g] = k_c[t, g] + (mean_{h: g(h) = g} q~[t, h] + k~[t, g]) / 2``.
  ``q`` and ``k`` are divided by their L2 norm a head (``x / max(|x|,
  1e-12)``); rotary positions then turn the first ``dh *
  partial_rotary_factor`` channels of every head (``decoder.rotate``'s
  interleaved pairs 2i, 2i + 1 at ``pos * rope_theta^(-2i / rotary)``: the
  published half-split pairing up to one fixed permutation of those
  channels, the same in ``q`` and ``k``).  ``s[h, t, t'] = tau_h q[t, h] .
  k[t', g(h)]`` for ``t' <= t`` with ``tau_h`` a learned temperature a
  query head, softmax in float32, ``o[t, h] = sum softmax(s) v[t', g(h)]``,
  out ``o W_o``: ``ops.attention.causal_attention``, the temperatures
  applied to the float32 scores; on a TPU a flash kernel.
- *Router.*  ``r_l = x W_down [T, router_hidden_size]``; the depth average
  ``r^_l = lam_l r^_(l-1) + (1 - lam_l) r_l``, ``lam_l = sigmoid(gamma_l)``
  (``gamma`` a learned scalar a layer, initial 0), ``r^ = r`` in the
  chip's first layer; ``z = W3 gelu(W2 gelu(W1 RMSNorm(r^)))`` (exact
  GELU), ``p = softmax(z)`` over all ``num_experts``; chosen = argmax of
  ``z + b`` (``b``: the selection bias, for the choice only).  ``y =
  p_chosen Expert_chosen(x)`` where the chosen expert is held here, 0
  elsewhere (no renormalisation: at top-1 it would make the weight 1 and
  leave the router no gradient).  The held experts (``ep_size``,
  ``ep_rank``) and the dispatch through the grouped product are
  ``decoder.experts``, the same code as ``decoder.deepseek_v3``'s.
- *Training rule.*  No auxiliary loss; after each SGD step a node takes,
  every layer's ``b_e += bias_update_speed * sign(mean_e(count) -
  count_e)`` (``decoder.bias_step``).
- *Memory.*  Layers are one scanned stack, each recomputed in the
  backward pass but for attention's result and log-sum-exp, which are
  kept; the carry is ``(h, r^)``.

Labels (``jax.named_scope``; docs/OBSERVABILITY.md): ``murmura.cca`` (the
whole attention sublayer), inside it ``murmura.mix`` (the two
convolutions, the value shift and the q-k mean: what no other attention
has), ``murmura.router``, ``murmura.experts`` (with ``decoder.experts``'
labels inside it, and the residual scales' sum under ``murmura.pairs``),
``murmura.head``.
"""

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from murmura_tpu.models.core import Model, resolve_dtype
from murmura_tpu.models.decoder import (
    HIGHEST, _einsum, bias_step, experts, ladder, rms_norm, rotate, router_counters,
    rows_multiplied,
)
from murmura_tpu.ops.attention import KEEP_RESIDUALS, causal_attention


def shifted(x, lag):
    """``x`` [T, ...] moved ``lag`` positions later, zeros before 0."""
    if lag == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:lag]), x[:-lag]], axis=0)


def l2_normalized(x):
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), 1e-12)


def _dot(a, b):
    return jnp.dot(a, b.astype(jnp.float32), precision=HIGHEST)


def make_zaya1(
    vocab_size: int,
    hidden_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    num_key_value_heads: int,
    head_dim: int,
    moe_intermediate_size: int,
    num_experts: int,
    num_experts_per_tok: int,
    router_hidden_size: int,
    cca_time0: int,
    cca_time1: int,
    seq_len: int,
    partial_rotary_factor: float = 0.5,
    rope_theta: float = 5000000.0,
    rms_norm_eps: float = 1e-5,
    tie_word_embeddings: bool = True,
    sliding_window: Optional[int] = None,
    num_shared_experts: int = 0,
    attention_bias: bool = False,
    lm_head_bias: bool = False,
    hidden_act: str = "silu",
    layer_types: Optional[List[str]] = None,
    bias_update_speed: float = 0.001,
    ep_size: int = 1,
    ep_rank: int = 0,
    initializer_range: float = 0.02,
    name: str = "decoder.zaya1",
    compute_dtype=None,
) -> Model:
    """The model from the published configuration's own keys (the
    ``config.json`` of a ``model_type: zaya`` checkpoint; ``rope_theta`` as
    its ``rope_parameters.hybrid`` gives it) and this node's share of a
    deployment: ``vocab_size`` its rows of the vocabulary,
    ``num_hidden_layers`` the layers it runs, ``ep_size`` and ``ep_rank``
    its experts."""
    rotary = int(head_dim * partial_rotary_factor)
    refused = {
        "sliding_window": sliding_window is not None,
        "tie_word_embeddings": not tie_word_embeddings,
        "num_shared_experts": num_shared_experts != 0,
        "attention_bias/lm_head_bias": attention_bias or lm_head_bias,
        "hidden_act": hidden_act != "silu",
        "layer_types": any(k != "hybrid" for k in layer_types or []),
        "num_experts_per_tok": num_experts_per_tok != 1,
        "partial_rotary_factor": rotary % 2 or not 0 < rotary <= head_dim,
        "cca_time0/cca_time1": min(cca_time0, cca_time1) < 1,
    }
    if any(refused.values()):
        raise ValueError(
            f"decoder.zaya1 has no equations for "
            f"{sorted(k for k, v in refused.items() if v)} as given: it runs "
            "full causal attention in every layer ('hybrid'), a tied head without "
            "bias, top-1 of the routed experts and no shared one, SwiGLU experts, "
            "an even rotary width and convolutions of one tap or more"
        )
    if num_experts % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(
            f"ep_size {ep_size} does not divide num_experts {num_experts}, or "
            f"ep_rank {ep_rank} is not one of its ranks"
        )
    if num_attention_heads % num_key_value_heads:
        raise ValueError(
            f"{num_key_value_heads} key/value heads do not divide "
            f"{num_attention_heads} query heads"
        )
    cd = resolve_dtype(compute_dtype)
    hq, hk, dh, layers = num_attention_heads, num_key_value_heads, head_dim, num_hidden_layers
    group = hq // hk
    held = num_experts // ep_size
    first_held = ep_rank * held
    dispatch = dict(held=held, first_held=first_held, top_k=1,
                    n_routed_experts=num_experts, dtype=cd)
    eps = rms_norm_eps

    # ---- parameters -------------------------------------------------------
    def init(key: jax.Array):
        normal = lambda k, shape: initializer_range * jax.random.normal(
            k, shape, jnp.float32
        )
        # A convolution's taps as a 1-d convolution is drawn by default
        # (uniform within 1 / sqrt(fan_in), fan_in = channels in x taps).
        taps = lambda k, shape, fan_in: jax.random.uniform(
            k, shape, jnp.float32, -1.0, 1.0
        ) / math.sqrt(fan_in)
        ones = lambda *shape: jnp.ones((layers,) + shape, jnp.float32)
        ke, kq, kk, kv, k0, k1, ko, kd, kr1, kr2, kr3, kx = jax.random.split(key, 12)
        kg, ku, kdn = jax.random.split(kx, 3)
        return {
            "embed": normal(ke, (vocab_size, hidden_size)),
            "layers": {
                "attn_norm": ones(hidden_size),
                "cca": {
                    "q": normal(kq, (layers, hidden_size, hq * dh)),
                    "k": normal(kk, (layers, hidden_size, hk * dh)),
                    "v": normal(kv, (layers, hidden_size, hk * dh)),
                    "conv0": taps(k0, (layers, cca_time0, (hq + hk) * dh), cca_time0),
                    "conv1": taps(k1, (layers, cca_time1, hq + hk, dh, dh), cca_time1 * dh),
                    "temperature": jnp.full((layers, hq), math.sqrt(dh), jnp.float32),
                    "o": normal(ko, (layers, hq * dh, hidden_size)),
                },
                "scales": {"attn_h": ones(hidden_size), "attn_out": ones(hidden_size),
                           "moe_h": ones(hidden_size), "moe_out": ones(hidden_size)},
                "ffn_norm": ones(hidden_size),
                "router": {
                    "down": normal(kd, (layers, hidden_size, router_hidden_size)),
                    "depth": jnp.zeros((layers,), jnp.float32),
                    "norm": ones(router_hidden_size),
                    "w1": normal(kr1, (layers, router_hidden_size, router_hidden_size)),
                    "w2": normal(kr2, (layers, router_hidden_size, router_hidden_size)),
                    "w3": normal(kr3, (layers, router_hidden_size, num_experts)),
                    "bias": jnp.zeros((layers, num_experts), jnp.float32),
                },
                "experts": {
                    "gate": normal(kg, (layers, held, hidden_size, moe_intermediate_size)),
                    "up": normal(ku, (layers, held, hidden_size, moe_intermediate_size)),
                    "down": normal(kdn, (layers, held, moe_intermediate_size, hidden_size)),
                },
            },
            "final_norm": jnp.ones((hidden_size,), jnp.float32),
        }

    # ---- one sequence [T] through the layers ------------------------------
    def mix(p, q0, k0, v):
        """The convolutions, the value shift and the q-k mean: ``q`` [T, Hk,
        G, dh], ``k`` and ``v`` [T, Hk, dh] before the norms."""
        half = dh // 2
        v = jnp.concatenate([v[..., :half], shifted(v[..., half:], 1)], axis=-1)
        z = jnp.concatenate([q0, k0], axis=1)  # [T, Hq + Hk, dh]
        a = p["conv0"].astype(jnp.float32).reshape(cca_time0, hq + hk, dh)
        z0 = sum(a[lag] * shifted(z, lag) for lag in range(cca_time0))
        lags = jnp.stack([shifted(z0, lag) for lag in range(cca_time1)])
        z1 = _einsum("stgj,sgij->tgi", lags, p["conv1"], cd)
        t = z.shape[0]
        q0 = q0.reshape(t, hk, group, dh)
        q = z1[:, :hq].reshape(t, hk, group, dh) + (q0 + k0[:, :, None]) / 2
        k = z1[:, hq:] + (q0.mean(axis=2) + k0) / 2
        return q, k, v

    def turned(x):
        return jnp.concatenate(
            [rotate(x[..., :rotary], rope_theta), x[..., rotary:]], axis=-1
        )

    def cca(p, u):
        t = u.shape[0]
        q0 = _einsum("th,hd->td", u, p["q"], cd).reshape(t, hq, dh)
        k0 = _einsum("th,hd->td", u, p["k"], cd).reshape(t, hk, dh)
        v = _einsum("th,hd->td", u, p["v"], cd).reshape(t, hk, dh)
        with jax.named_scope("murmura.mix"):
            q, k, v = mix(p, q0, k0, v)
        q, k = turned(l2_normalized(q)), turned(l2_normalized(k))
        heads_first = lambda a: a.transpose(1, 0, 2)
        # Query head h = g * G + r: the temperatures' order, and k's g.
        o = causal_attention(heads_first(q.reshape(t, hq, dh)), heads_first(k),
                             heads_first(v), p["temperature"], cd)
        return _einsum("td,dh->th", heads_first(o).reshape(t, hq * dh), p["o"], cd)

    def route(p, x, carried, first):
        """The choice [T], its weight ``p_chosen`` [T], the counts of the
        choice over all experts, and the depth-averaged state the next
        layer takes as ``carried``; ``first``: the chip's first layer."""
        r = _dot(x, p["down"])
        lam = jnp.where(first, 0.0, jax.nn.sigmoid(p["depth"].astype(jnp.float32)))
        averaged = lam * carried + (1.0 - lam) * r
        a = rms_norm(averaged, p["norm"], eps)
        gelu = partial(jax.nn.gelu, approximate=False)
        z = _dot(gelu(_dot(gelu(_dot(a, p["w1"])), p["w2"])), p["w3"])
        chosen = jnp.argmax(z + p["bias"].astype(jnp.float32), axis=-1)
        weight = jnp.take_along_axis(
            jax.nn.softmax(z, axis=-1), chosen[:, None], axis=-1
        )[:, 0]
        counts = jnp.zeros((num_experts,), jnp.float32).at[chosen].add(1.0)
        return chosen, weight, counts, averaged

    def layer(carry, xs):
        h, carried = carry
        p, first = xs
        s = p["scales"]
        with jax.named_scope("murmura.cca"):
            h = s["attn_h"] * h + s["attn_out"] * cca(p["cca"], rms_norm(h, p["attn_norm"], eps))
        x = rms_norm(h, p["ffn_norm"], eps)
        with jax.named_scope("murmura.router"):
            chosen, weight, counts, carried = route(p["router"], x, carried, first)
        with jax.named_scope("murmura.experts"):
            with jax.named_scope("murmura.pairs"):  # top-1: a pair a position
                pair_expert, pair_weight = chosen[:, None], weight[:, None]
            y, step = experts(p["experts"], x, pair_expert, pair_weight, **dispatch)
            with jax.named_scope("murmura.pairs"):
                h = s["moe_h"] * h + s["moe_out"] * y
        return (h, carried), (counts, weight.sum(), step)

    def sequence(params, ids):
        """logits [T, V]; the choice's counts [layers, experts], the sum of
        ``p_chosen`` over positions [layers], the step of the buffer's
        ladder each layer took [layers, steps] (one-hot), the rows its
        grouped products multiplied [layers]."""
        with jax.named_scope("murmura.head"):
            h = params["embed"][ids].astype(jnp.float32)
        carried = jnp.zeros((ids.shape[0], router_hidden_size), jnp.float32)
        (h, _), (counts, chosen_weight, step) = jax.lax.scan(
            jax.checkpoint(layer, policy=KEEP_RESIDUALS), (h, carried),
            (params["layers"], jnp.arange(layers) == 0),
        )
        steps = len(ladder(ids.shape[0], 1, held, num_experts)[1])
        rows = rows_multiplied(counts, ids.shape[0], 1, held, first_held, num_experts)
        with jax.named_scope("murmura.head"):
            logits = _einsum(
                "th,vh->tv", rms_norm(h, params["final_norm"], eps), params["embed"], cd
            )
        return logits, {"counts": counts, "chosen_weight": chosen_weight,
                        "ladder": jax.nn.one_hot(step, steps, dtype=jnp.float32),
                        "rows": rows}

    def apply_train(params, x, key=None):
        """``(logits [B, T, V], auxiliary)``: ``"loss"`` [B], zeros (no
        auxiliary loss), and ``"step"``, what ``after_step`` and
        ``step_metrics`` take summed over the samples the batch's mask
        keeps: ``"counts"`` [B, layers, experts], ``"chosen_weight"`` [B,
        layers], ``"ladder"`` [B, layers, steps] and ``"rows"`` [B, layers]
        (``sequence``)."""
        logits, step = jax.lax.map(lambda ids: sequence(params, ids), x)
        return logits, {"loss": jnp.zeros((x.shape[0],), jnp.float32), "step": step}

    def apply(params, x, key=None, train=False):
        return apply_train(params, x, key)[0]

    def after_step(params, step):
        with jax.named_scope("murmura.router"):
            router = params["layers"]["router"]
            moved = bias_step(router["bias"], step["counts"], bias_update_speed)
        stack = {**params["layers"], "router": {**router, "bias": moved}}
        return {**params, "layers": stack}

    def step_metrics(params, step) -> Dict[str, Any]:
        """The router's counters of one node (docs/OBSERVABILITY.md) and
        ``moe.chosen_weight_mean``, the mean ``p_chosen`` over its
        positions and layers in the round: a top-1 router whose softmax
        goes to 1 stops learning."""
        counts = step["counts"]
        return {
            **router_counters(counts, step["ladder"], step["rows"],
                              params["layers"]["router"]["bias"], first_held, held),
            "moe.chosen_weight_mean": step["chosen_weight"].sum()
            / jnp.maximum(counts.sum(), 1.0),
        }

    return Model(
        name=name,
        init=init,
        apply=apply,
        evidential=False,
        input_shape=(seq_len,),
        num_classes=vocab_size,
        meta={
            # One expert layer's parts, for the test that ties a share to
            # the model: route(router, x, carried, first) -> chosen, weight,
            # counts, the averaged state; experts(held experts, x, chosen
            # [T, 1], weight [T, 1]) -> this share's part and its step.
            "route": route, "experts": partial(experts, **dispatch),
        },
        apply_train=apply_train,
        after_step=after_step,
        step_metrics=step_metrics,
    )
