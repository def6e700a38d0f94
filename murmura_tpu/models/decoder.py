"""A decoder of the DeepSeek-V3 family as a node's model: latent attention
(MLA), leading dense layers, then layers of routed experts beside shared
ones, a next-token output ``[B, T, V]``, and a training rule that is more
than ``p - lr g`` (the router's selection bias steps by the sign of each
expert's load and takes no gradient).

For one sequence of ids ``t[0..T)``: ``h = E[t]``; each layer
``h = h + Attn(RMSNorm(h)); h = h + FFN(RMSNorm(h))``; logits
``= RMSNorm(h) W_head``.  RMSNorm is ``x * rsqrt(mean(x^2) + eps) * g``.
Products take operands in the compute dtype and accumulate in float32;
norms, the softmax, the router and the residual stream are float32.

- *Latent attention.*  ``q = x W_q -> [T, heads, nope + rope]``;
  ``[c, k_r] = split(x W_kva, kv_lora_rank | rope)``; ``c = RMSNorm(c)``;
  ``[k_n, v] = split(c W_kvb -> [T, heads, nope + v], nope | v)``.  Rotary
  positions on ``q_r`` and on ``k_r``, which is one vector a position
  shared by all heads; the pairing is interleaved (entries 2i and 2i + 1
  turn by ``pos * theta^(-2i/rope)``), which is the published code's result
  (it de-interleaves, then rotates halves: the same pairs).
  ``s = (q_n . k_n + q_r . k_r) / sqrt(nope + rope)``, causal, softmax in
  float32, ``out = (softmax(s) v) W_o``: ``ops.attention.causal_attention``
  of ``q = [q_n | q_r]`` over ``k = [k_n | k_r]`` (``k_r`` broadcast over
  the heads), the scale applied to the float32 scores; on a TPU a flash
  kernel, so no ``[heads, T, T]`` array exists and the blocks above the
  diagonal are never multiplied.  ``q_lora_rank`` is null in the
  configurations this serves (a low-rank query projection is refused, not
  guessed).
- *Expert layer.*  ``sc = sigmoid(x W_r)`` in float32 over **all**
  ``n_routed_experts``; chosen = top-k of ``sc + b`` (``b``: the selection
  bias, for the choice only); ``w = sc[chosen] / (sum sc[chosen] + 1e-20)
  * routed_scaling_factor``, normalised over all chosen whether held here
  or not.  ``y = Shared(x) + sum over chosen e held here of w_e
  Expert_e(x)``.  **The layer is told which experts it holds**: the
  published code's own ``ep_size`` and ``ep_rank``, experts ``[ep_rank *
  n / ep_size, (ep_rank + 1) * n / ep_size)``.  It routes over all of them
  and computes its own experts' part; what the absent ones would add is
  left out (no stand-in for absent chips), and that partial result goes
  on.  No pair (token, held expert) is dropped whatever the imbalance:
  every held pair gets a row of a buffer in which an expert's rows lie
  together from a multiple of ``GROUP_ALIGN`` on, and one
  ``lax.ragged_dot`` a projection multiplies each expert's rows by its
  own matrices; on a TPU that is the compiler's grouped-product kernel,
  whose work follows the rows the groups hold (what lies behind the last
  group is not multiplied), a tile of ``GROUP_ALIGN`` rows at a time.  The
  last group is lengthened by rows of zeros up to a floor of
  ``GROUP_FLOOR_SHARES`` even shares of the pairs: a step's time follows
  the routing only over that floor (with a router far from even, as at
  drawn weights on Zipf-distributed ids, a layer meets 8 to 21 tiles
  where an even one meets 8, and a round's time would differ by 2 % from
  one draw of the weights to the next).  The gathers and elementwise
  passes around the products do run over the whole buffer, so the buffer
  takes, a layer and a step, the shortest of a ladder of static sizes
  that holds the groups as they were routed: the floor, doubled up to
  the size with a row for every pair and every group's padding, which is
  always the last step (at Moonlight's share 8,192, 16,384 and 28,672
  rows where 2,700 to 3,400 pairs are held).  The step is chosen on the
  device (``lax.switch``), a pair's row is the same in every one, and
  the last holds any routing: that is why no pair is dropped.  The
  dispatch (``ladder``, ``experts``), the bias step and the router's
  counters are this module's own functions: ``models/zaya.py`` runs them
  too, at one pair a position.
- *Training rule* (``Model.apply_train``, ``after_step``).  The loss gains
  ``aux_loss_alpha`` times DeepSeek-V3's sequence-wise balance loss
  (``seq_aux``): a sequence's ``sum_e f_e P_e``, ``f_e = n / (k T)`` times
  the count of its positions choosing ``e``, ``P_e`` the mean over its
  positions of ``sc_e / sum sc``.  After each SGD step a node takes, every
  expert layer's ``b_e += bias_update_speed * sign(mean_e(count) -
  count_e)`` over all experts, from that step's batch: a leaf that rides
  the training carry, is exchanged, averaged and checkpointed like every
  other.
- *Memory.*  Each layer is recomputed in the backward pass
  (``jax.checkpoint`` around the block; attention's result and the
  log-sum-exp of its scores are kept, so the backward pass runs no
  attention forward again), the expert layers are one scanned
  stack, and the round trains and evaluates such a model one node at a
  time (core/rounds.py ``local_training_by_node``): its products are
  already ``[T, hidden]`` wide, and a node axis would only multiply what
  is resident.  A state of a GiB or more under a rule that can take it
  leaf by leaf (FedAvg's dense mean) is never flattened to ``[N, P]``
  either (``_round_body_by_leaf``).

Labels (``jax.named_scope``; docs/OBSERVABILITY.md): ``murmura.attention``,
``murmura.router`` (scores, top-k, counts; the bias step in
``after_step``), ``murmura.experts`` (the grouped products with
``silu(gate) * up`` between them, the conditional that chooses the
buffer's size, and inside it, forward and backward, ``murmura.rows``:
where each pair goes; ``murmura.pairs``: the pairs' side, the gather of
``x`` into the buffer, the weighted combine of the ``down`` product's rows
back into ``[T, hidden]`` (``ops.row_scatter.row_scatter_add`` where a
position has several pairs) and their cotangents: no array of a row for
every pair, ``[T * top_k, hidden]``, is written),
``murmura.ffn`` (the dense layers' and the shared experts'
SwiGLU), ``murmura.head`` (lookup, last norm, logits).
"""

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from murmura_tpu.models.core import Model, resolve_dtype
from murmura_tpu.ops.attention import KEEP_RESIDUALS, causal_attention
from murmura_tpu.ops.row_scatter import row_scatter_add

# Rows to which an expert's group is aligned: the tile of rows in which the
# TPU compiler's grouped product works (read off its compiled metadata: 55
# entries for 24,576 rows in 8 groups, 48 tiles + 7).
GROUP_ALIGN = 512
# The rows a layer's grouped products multiply at the least, in even router's
# shares of the pairs (beside half a tile an expert held for its last, partly
# filled one).  Under it a step's time does not follow the routing; over it
# (no pair is dropped) it does, a tile at a time.  0: no floor.
GROUP_FLOOR_SHARES = 2
HIGHEST = jax.lax.Precision.HIGHEST


def _product(f, a, b, dtype, *traced):
    """Bilinear ``f(a, b, *traced)`` with operands, and in the backward pass
    the cotangent too, in the compute ``dtype`` and float32 results: both
    operands of every product the chip runs are then MXU-native.  What
    ``f`` takes beside the operands and is traced (a grouped product's
    sizes) is an argument of its own: a function with a backward pass of
    its own may not close over it."""
    if dtype is None:
        return f(a.astype(jnp.float32), b.astype(jnp.float32), *traced)

    @jax.custom_vjp
    def op(a, b, *traced):
        return f(a.astype(dtype), b.astype(dtype), *traced)

    def forward(a, b, *traced):
        qa, qb = a.astype(dtype), b.astype(dtype)
        # Zero-size stand-ins carry the operands' dtypes to the backward.
        stand_ins = jnp.zeros((0,), a.dtype), jnp.zeros((0,), b.dtype)
        return f(qa, qb, *traced), (qa, qb, traced, stand_ins)

    def backward(saved, g):
        qa, qb, traced, (like_a, like_b) = saved
        da, db = jax.vjp(lambda x, y: f(x, y, *traced), qa, qb)[1](
            g.astype(dtype).astype(g.dtype)
        )
        return (da.astype(like_a.dtype), db.astype(like_b.dtype)) + (None,) * len(traced)

    op.defvjp(forward, backward)
    return op(a, b, *traced)


def _grouped(rows, w, sizes, dtype):
    """``lax.ragged_dot``: the rows of group g (``sizes[g]`` of them, one
    group after another) times ``w[g]``."""
    return _product(
        lambda a, b, n: jax.lax.ragged_dot(
            a, b, n, preferred_element_type=jnp.float32
        ),
        rows, w, dtype, sizes,
    )


def _einsum(spec, a, b, dtype):
    return _product(
        lambda x, y: jnp.einsum(spec, x, y, preferred_element_type=jnp.float32),
        a, b, dtype,
    )


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(
        jnp.float32
    )


def rotate(x, theta):
    """Rotary positions on the last axis of ``x`` [T, ..., d], interleaved
    pairs, position = the index on the first axis."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def swiglu(p, x, dtype):
    gate = _einsum("th,hf->tf", x, p["gate"], dtype)
    up = _einsum("th,hf->tf", x, p["up"], dtype)
    return _einsum("tf,fh->th", jax.nn.silu(gate) * up, p["down"], dtype)


def _to_positions(rows, scale, place, pair_of, row_of, mine):
    """Each position's held pairs' rows of ``rows`` times their ``scale``
    [T, top_k], summed: [T, hidden] float32.  ``place`` and ``pair_of``
    [rows] are a row's position (-1 where it holds no pair) and pair,
    ``row_of`` and ``mine`` [pairs] a pair's row and whether it is held; a
    row that holds no pair never enters a sum.  At one pair a position, a
    gather of each position's row, which writes [T, hidden]; at several,
    where a gather would write a row for every pair ([T * top_k, hidden]),
    the rows are added into their positions (``row_scatter_add``)."""
    t, top_k = scale.shape
    if top_k == 1:
        return jnp.where(mine[:, None], scale * rows[row_of], 0.0)
    return row_scatter_add(rows, place, scale.reshape(-1)[pair_of], t)


@jax.custom_vjp
def _into_buffer(x, place, pair_of, row_of, mine):
    """The pairs' buffer straight from the sequence ``x`` [T, hidden]: a row
    holds its pair's position's ``x``, zeros where it holds no pair.  The
    cotangent of ``x`` is the buffer's cotangent taken back to the
    positions (``_to_positions``), where autodiff would scatter."""
    return jnp.where((place >= 0)[:, None], x[jnp.maximum(place, 0)], 0.0)


def _into_buffer_fwd(x, *pairs_at):
    # A zero-size stand-in carries the sequence's length and dtype.
    return _into_buffer(x, *pairs_at), (pairs_at, jnp.zeros((x.shape[0], 0), x.dtype))


def _into_buffer_bwd(saved, g):
    pairs_at, like = saved
    t = like.shape[0]
    ones = jnp.ones((t, pairs_at[-1].shape[0] // t), jnp.float32)
    return (_to_positions(g, ones, *pairs_at).astype(like.dtype),) + (None,) * len(pairs_at)


_into_buffer.defvjp(_into_buffer_fwd, _into_buffer_bwd)


@jax.custom_vjp
def _combine(down, weights, place, pair_of, row_of, mine):
    """The routed result [T, hidden]: ``_to_positions`` of ``down`` with the
    pairs' ``weights`` [T, top_k].  Both cotangents are gathers: ``down``'s
    a row's weight times its position's cotangent, over the buffer's rows;
    that of ``weights`` a row's dot with its position's cotangent, taken
    over the buffer's rows and back to the row's pair (at one pair a
    position, over the positions, as the combine itself)."""
    return _to_positions(down, weights, place, pair_of, row_of, mine)


def _combine_fwd(*args):
    return _combine(*args), args


def _combine_bwd(saved, g):
    down, weights, place, pair_of, row_of, mine = saved
    held = (place >= 0)[:, None]
    at = jnp.maximum(place, 0)
    if weights.shape[1] == 1:
        # One pair a position: the weight and the dot are taken at the
        # positions, [T, hidden], and only the product goes to the rows.
        d_down = jnp.where(held, (weights * g)[at], 0.0)
        dot = (down[row_of] * g).sum(axis=-1, keepdims=True)
        return d_down, jnp.where(mine[:, None], dot, 0.0), None, None, None, None
    g_rows = jnp.where(held, g[at], 0.0)
    weight = weights.reshape(-1)[pair_of]
    d_down = jnp.where(held, weight[:, None] * g_rows, 0.0)
    # What lies in a row that holds no pair is never taken.
    dot = (jnp.where(held, down, 0.0) * g_rows).sum(axis=-1)
    d_weights = jnp.where(mine, dot[row_of], 0.0).reshape(weights.shape)
    return d_down, d_weights, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _switched(branches, index, ints, floats):
    """``branches[index](ints, floats)`` by ``lax.switch`` on the device,
    differentiable in ``floats``, for branches that give the same result
    by the same sums at static sizes of their own.  The backward pass is a
    ``lax.switch`` too, each branch ``jax.vjp`` of its own forward, and its
    residuals are the arguments: autodiff through a ``lax.switch`` would
    give every branch a slot for every other branch's residuals and fill
    it with zeros, so that the smallest wrote arrays of the largest's
    shapes.  One branch: that branch, and plain autodiff."""
    if len(branches) == 1:
        return branches[0](ints, floats)

    @jax.custom_vjp
    def run(index, ints, floats):
        return jax.lax.switch(index, branches, ints, floats)

    def forward(index, ints, floats):
        return run(index, ints, floats), (index, ints, floats)

    def backward(saved, g):
        index, ints, floats = saved
        back = [
            lambda ints, floats, g, f=f: jax.vjp(partial(f, ints), floats)[1](g)[0]
            for f in branches
        ]
        return None, None, jax.lax.switch(index, back, ints, floats, g)

    run.defvjp(forward, backward)
    return run(index, ints, floats)


# ---- the routed experts' dispatch, shared by every decoder with a router --
#
# A layer's share of the experts: ``held`` experts of ``n_routed_experts``
# from ``first_held`` on, ``top_k`` chosen a position, products in the
# compute ``dtype``.


def ladder(t, top_k, held, n_routed_experts):
    """The static sizes the pairs' buffer may take for a sequence of ``t``
    positions, shortest first: the floor under the grouped products,
    doubled up to ``rows_in_all`` (a row for every pair and the padding of
    every group), which is always the last.  Without a floor the buffer has
    the one size."""
    pairs = t * top_k
    rows_in_all = -(-(pairs + held * (GROUP_ALIGN - 1)) // GROUP_ALIGN) * GROUP_ALIGN
    if not GROUP_FLOOR_SHARES:
        return 0, [rows_in_all]
    floor_rows = GROUP_FLOOR_SHARES * pairs * held / n_routed_experts
    floor_rows += held * GROUP_ALIGN / 2
    floor_rows = min(rows_in_all, -(-int(floor_rows) // GROUP_ALIGN) * GROUP_ALIGN)
    sizes, size = [], floor_rows
    while size < rows_in_all:
        sizes.append(size)
        size *= 2
    return floor_rows, sizes + [rows_in_all]


def experts_at(buffer_rows, top_k, dtype, ints, floats):
    """``experts`` through a buffer of ``buffer_rows`` rows that holds
    every group as routed: ``row_of`` [pairs] a held pair's row."""
    (row_of, mine, sizes), (p, x, weights) = ints, floats
    pairs = x.shape[0] * top_k
    with jax.named_scope("murmura.rows"):
        row_of = jnp.where(mine, row_of, buffer_rows)
        pair_of = jnp.zeros((buffer_rows,), jnp.int32).at[row_of].set(
            jnp.arange(pairs, dtype=jnp.int32), mode="drop"
        )
        filled = jnp.zeros((buffer_rows,), bool).at[row_of].set(True, mode="drop")
        row_of = jnp.minimum(row_of, buffer_rows - 1)
    with jax.named_scope("murmura.pairs"):
        pairs_at = (jnp.where(filled, pair_of // top_k, -1), pair_of, row_of, mine)
        rows = _into_buffer(x, *pairs_at)
    grouped = lambda a, w: _grouped(a, w, sizes, dtype)
    inner = jax.nn.silu(grouped(rows, p["gate"])) * grouped(rows, p["up"])
    down = grouped(inner, p["down"])
    # What a product leaves behind the last group, forward or backward,
    # is not defined: only rows that hold a pair are ever taken back
    # (``_combine`` reads no other row: a where or a row skipped, never a
    # product with 0).
    with jax.named_scope("murmura.pairs"):
        return _combine(down, weights, *pairs_at)


def experts(p, x, chosen, weights, *, held, first_held, top_k, n_routed_experts,
            dtype):
    """The held experts' part of the layer's result for one sequence
    (``chosen`` and ``weights`` [T, top_k]), and the index of the ladder's
    step its buffer took.

    Every (position, chosen expert) pair whose expert is held gets a row of
    a buffer in which expert g's rows start at a multiple of
    ``GROUP_ALIGN`` (the rows between a group's end and the next multiple
    are exact zeros and belong to the group): the grouped product then
    meets whole tiles, the same number of them whatever the routing as long
    as no expert's share crosses a multiple, and rows behind the last group
    are never multiplied.  The last group is lengthened by rows of zeros up
    to the floor (``GROUP_FLOOR_SHARES``), under which the products meet
    the same tiles whatever the routing.  The buffer is the shortest of
    ``ladder`` that holds the groups as routed, chosen on the device; the
    last has a row for every pair and the padding of every group, so no
    pair is dropped at any imbalance.  A pair's row does not depend on the
    buffer's size, so neither does the result."""
    with jax.named_scope("murmura.rows"):
        local = chosen.reshape(-1) - first_held  # a pair's expert, from the first held
        mine = (local >= 0) & (local < held)
        one_hot = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
        rank = jnp.take_along_axis(  # a pair's place among its expert's pairs
            jnp.cumsum(one_hot, axis=0), jnp.clip(local, 0, held - 1)[:, None], axis=1
        )[:, 0] - 1
        sizes = -(-one_hot.sum(axis=0) // GROUP_ALIGN) * GROUP_ALIGN
        starts = jnp.cumsum(sizes) - sizes
        needed = sizes.sum()
        floor_rows, steps = ladder(x.shape[0], top_k, held, n_routed_experts)
        # Rows of zeros behind the last expert's own, up to the floor.
        sizes = sizes.at[held - 1].add(jnp.maximum(floor_rows - needed, 0))
        row_of = starts[jnp.clip(local, 0, held - 1)] + rank
        step = (needed > jnp.asarray(steps[:-1], jnp.int32)).sum()
    branches = [partial(experts_at, n, top_k, dtype) for n in steps]
    return _switched(branches, step, (row_of, mine, sizes), (p, x, weights)), step


def bias_step(bias, counts, speed):
    """The selection bias steps towards an even load: down by ``speed`` for
    an expert chosen more often than the mean of ``counts`` [..., experts],
    up for one chosen less."""
    step = speed * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)
    return bias + step.astype(bias.dtype)


def rows_multiplied(counts, t, top_k, held, first_held, n_routed_experts):
    """The rows the grouped products multiply for one sequence of ``t``
    positions in each expert layer, from the counts of its choice [layers,
    experts]: every held expert's pairs in whole tiles, at least the floor
    (``experts``' ``sizes`` summed)."""
    floor_rows, _ = ladder(t, top_k, held, n_routed_experts)
    mine = counts[..., first_held:first_held + held]
    return jnp.maximum((jnp.ceil(mine / GROUP_ALIGN) * GROUP_ALIGN).sum(axis=-1),
                       float(floor_rows))


def router_counters(counts, took, rows, bias, first_held, held) -> Dict[str, Any]:
    """A node's router counters (docs/OBSERVABILITY.md) from the counts of
    its choice [layers, experts], the ladder's steps taken [layers, steps]
    and the rows the grouped products multiplied [layers] over the steps
    of a round, and its selection bias."""
    total = jnp.maximum(counts.sum(), 1.0)
    mean = jnp.maximum(counts.mean(axis=-1), 1e-30)
    pairs = counts[:, first_held:first_held + held].sum()
    return {
        "moe.load_max_over_mean": (counts.max(axis=-1) / mean).max(),
        "moe.held_share": pairs / total,
        "moe.bias_abs_max": jnp.abs(bias.astype(jnp.float32)).max(),
        "moe.rows_first_step_share": took[:, 0].sum() / jnp.maximum(took.sum(), 1.0),
        "moe.padding_share": 1.0 - pairs / jnp.maximum(rows.sum(), 1.0),
    }


def make_deepseek_v3(
    vocab_size: int,
    hidden_size: int,
    num_hidden_layers: int,
    intermediate_size: int,
    moe_intermediate_size: int,
    n_routed_experts: int,
    n_shared_experts: int,
    num_experts_per_tok: int,
    num_attention_heads: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    seq_len: int,
    first_k_dense_replace: int = 1,
    q_lora_rank: Optional[int] = None,
    rope_theta: float = 10000.0,
    rms_norm_eps: float = 1e-6,
    routed_scaling_factor: float = 1.0,
    norm_topk_prob: bool = True,
    scoring_func: str = "sigmoid",
    topk_method: str = "noaux_tc",
    n_group: int = 1,
    topk_group: int = 1,
    seq_aux: bool = True,
    aux_loss_alpha: float = 0.0001,
    bias_update_speed: float = 0.001,
    ep_size: int = 1,
    ep_rank: int = 0,
    initializer_range: float = 0.02,
    name: str = "decoder.deepseek_v3",
    compute_dtype=None,
) -> Model:
    """The model from the published configuration's own keys (the
    ``config.json`` of a ``model_type: deepseek_v3`` checkpoint) and this
    node's share of a deployment: ``vocab_size`` its rows of the
    vocabulary, ``num_hidden_layers`` the layers it runs, ``ep_size`` and
    ``ep_rank`` its experts.  ``aux_loss_alpha`` and ``bias_update_speed``
    are DeepSeek-V3's report's (arXiv:2412.19437)."""
    refused = {
        "q_lora_rank": q_lora_rank is not None,
        "scoring_func": scoring_func != "sigmoid",
        "topk_method": topk_method != "noaux_tc",
        "n_group/topk_group": (n_group, topk_group) != (1, 1),
        "norm_topk_prob": not norm_topk_prob,
        "seq_aux": not seq_aux,
    }
    if any(refused.values()):
        raise ValueError(
            f"decoder.deepseek_v3 has no equations for "
            f"{sorted(k for k, v in refused.items() if v)} as given: it runs "
            "q_lora_rank null, sigmoid scores, noaux_tc with one group, "
            "normalised top-k weights and the sequence-wise balance loss"
        )
    if n_routed_experts % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(
            f"ep_size {ep_size} does not divide n_routed_experts "
            f"{n_routed_experts}, or ep_rank {ep_rank} is not one of its ranks"
        )
    cd = resolve_dtype(compute_dtype)
    heads, nope, rope, vdim = (
        num_attention_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    )
    dense_layers = min(first_k_dense_replace, num_hidden_layers)
    moe_layers = num_hidden_layers - dense_layers
    held = n_routed_experts // ep_size
    first_held = ep_rank * held
    top_k = num_experts_per_tok
    dispatch = dict(held=held, first_held=first_held, top_k=top_k,
                    n_routed_experts=n_routed_experts, dtype=cd)
    shared_width = n_shared_experts * moe_intermediate_size
    scale = 1.0 / math.sqrt(nope + rope)

    # ---- parameters -------------------------------------------------------
    def init(key: jax.Array):
        normal = lambda k, shape: initializer_range * jax.random.normal(
            k, shape, jnp.float32
        )

        def attention(k, layers):
            kq, ka, kb, ko = jax.random.split(k, 4)
            return {
                "q": normal(kq, (layers, hidden_size, heads * (nope + rope))),
                "kv_a": normal(ka, (layers, hidden_size, kv_lora_rank + rope)),
                "kv_norm": jnp.ones((layers, kv_lora_rank), jnp.float32),
                "kv_b": normal(kb, (layers, kv_lora_rank, heads * (nope + vdim))),
                "o": normal(ko, (layers, heads * vdim, hidden_size)),
            }

        def ffn(k, lead, width):
            kg, ku, kd = jax.random.split(k, 3)
            return {
                "gate": normal(kg, lead + (hidden_size, width)),
                "up": normal(ku, lead + (hidden_size, width)),
                "down": normal(kd, lead + (width, hidden_size)),
            }

        def block(k, layers):
            ka, kf = jax.random.split(k)
            return {
                "attn_norm": jnp.ones((layers, hidden_size), jnp.float32),
                "attn": attention(ka, layers),
                "ffn_norm": jnp.ones((layers, hidden_size), jnp.float32),
            }, kf

        ke, kd, km, kh = jax.random.split(key, 4)
        params = {"embed": normal(ke, (vocab_size, hidden_size))}
        if dense_layers:
            layer, kf = block(kd, dense_layers)
            layer["ffn"] = ffn(kf, (dense_layers,), intermediate_size)
            params["dense_layers"] = layer
        if moe_layers:
            layer, kf = block(km, moe_layers)
            kr, ks, kx = jax.random.split(kf, 3)
            layer["router"] = {
                "w": normal(kr, (moe_layers, hidden_size, n_routed_experts)),
                "bias": jnp.zeros((moe_layers, n_routed_experts), jnp.float32),
            }
            layer["shared"] = ffn(ks, (moe_layers,), shared_width)
            layer["experts"] = ffn(kx, (moe_layers, held), moe_intermediate_size)
            params["moe_layers"] = layer
        params["final_norm"] = jnp.ones((hidden_size,), jnp.float32)
        params["head"] = normal(kh, (hidden_size, vocab_size))
        return params

    # ---- one sequence [T] through the layers ------------------------------
    def attention(p, x):
        t = x.shape[0]
        q = _einsum("th,hd->td", x, p["q"], cd).reshape(t, heads, nope + rope)
        kv = _einsum("th,hd->td", x, p["kv_a"], cd)
        c = rms_norm(kv[:, :kv_lora_rank], p["kv_norm"], rms_norm_eps)
        k_r = rotate(kv[:, kv_lora_rank:], rope_theta)  # [T, rope], all heads'
        kn_v = _einsum("tc,cd->td", c, p["kv_b"], cd).reshape(t, heads, nope + vdim)
        k_n, v = kn_v[..., :nope], kn_v[..., nope:]
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], rope_theta)], axis=-1)
        k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, None], (t, heads, rope))], axis=-1)
        heads_first = lambda a: a.transpose(1, 0, 2)
        o = causal_attention(heads_first(q), heads_first(k), heads_first(v),
                             jnp.full((heads,), scale, jnp.float32), cd)
        return _einsum("td,dh->th", heads_first(o).reshape(t, heads * vdim), p["o"], cd)

    def route(p, x):
        """Scores, the choice and its weights over all experts; the counts
        of the choice; this sequence's balance loss."""
        sc = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["w"].astype(jnp.float32), precision=HIGHEST
        ))
        _, chosen = jax.lax.top_k(sc + p["bias"].astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(sc, chosen, axis=-1)
        weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * routed_scaling_factor
        counts = jnp.zeros((n_routed_experts,), jnp.float32).at[
            jax.lax.stop_gradient(chosen).reshape(-1)
        ].add(1.0)
        share = (sc / sc.sum(-1, keepdims=True)).mean(0)
        balance = (counts * (n_routed_experts / (top_k * x.shape[0])) * share).sum()
        return chosen, weights, counts, balance

    def dense_block(h, p):
        with jax.named_scope("murmura.attention"):
            h = h + attention(p["attn"], rms_norm(h, p["attn_norm"], rms_norm_eps))
        with jax.named_scope("murmura.ffn"):
            return h + swiglu(p["ffn"], rms_norm(h, p["ffn_norm"], rms_norm_eps), cd)

    def moe_block(h, p):
        with jax.named_scope("murmura.attention"):
            h = h + attention(p["attn"], rms_norm(h, p["attn_norm"], rms_norm_eps))
        x = rms_norm(h, p["ffn_norm"], rms_norm_eps)
        with jax.named_scope("murmura.router"):
            chosen, weights, counts, balance = route(p["router"], x)
        with jax.named_scope("murmura.ffn"):
            h = h + swiglu(p["shared"], x, cd)
        with jax.named_scope("murmura.experts"):
            routed, step = experts(p["experts"], x, chosen, weights, **dispatch)
        return h + routed, (counts, balance, step)

    def sequence(params, ids):
        """logits [T, V], the choice's counts [moe layers, experts], which
        step of the buffer's ladder each expert layer took [moe layers,
        steps] (one-hot), the rows its grouped products multiplied [moe
        layers], the balance loss summed over the expert layers."""
        with jax.named_scope("murmura.head"):
            h = params["embed"][ids].astype(jnp.float32)
        if dense_layers:
            h, _ = jax.lax.scan(
                lambda h, p: (jax.checkpoint(dense_block, policy=KEEP_RESIDUALS)(h, p), None),
                h, params["dense_layers"],
            )
        steps = len(ladder(ids.shape[0], top_k, held, n_routed_experts)[1])
        counts = jnp.zeros((0, n_routed_experts), jnp.float32)
        took = jnp.zeros((0, steps), jnp.float32)
        balance = jnp.zeros((), jnp.float32)
        if moe_layers:
            h, (counts, per_layer, step) = jax.lax.scan(
                jax.checkpoint(moe_block, policy=KEEP_RESIDUALS), h, params["moe_layers"]
            )
            took = jax.nn.one_hot(step, steps, dtype=jnp.float32)
            balance = per_layer.sum()
        rows = rows_multiplied(counts, ids.shape[0], top_k, held, first_held, n_routed_experts)
        with jax.named_scope("murmura.head"):
            logits = _einsum(
                "th,hv->tv", rms_norm(h, params["final_norm"], rms_norm_eps),
                params["head"], cd,
            )
        return logits, {"counts": counts, "ladder": took, "rows": rows}, balance

    def apply_train(params, x, key=None):
        """``(logits [B, T, V], auxiliary)``: ``"loss"`` [B], a sample's
        weighted balance loss, which the round adds to its likelihood, and
        ``"step"``, what ``after_step`` and ``step_metrics`` take summed
        over the samples the batch's mask keeps: ``"counts"`` [B, moe
        layers, experts] of the choice, ``"ladder"`` [B, moe layers,
        steps], one-hot, the step of the buffer's ladder a layer took, and
        ``"rows"`` [B, moe layers], the rows its grouped products
        multiplied (``rows_multiplied``)."""
        # One sequence after another: a sequence's products are as wide as
        # the chip wants them, and a batch axis would multiply what is live.
        logits, step, balance = jax.lax.map(lambda ids: sequence(params, ids), x)
        return logits, {"loss": aux_loss_alpha * balance, "step": step}

    def apply(params, x, key=None, train=False):
        return apply_train(params, x, key)[0]

    def after_step(params, step):
        """The selection bias steps towards an even load: down for an
        expert chosen more often than the mean, up for one chosen less."""
        if not moe_layers:
            return params
        with jax.named_scope("murmura.router"):
            router = params["moe_layers"]["router"]
            moved = bias_step(router["bias"], step["counts"], bias_update_speed)
        layers = {**params["moe_layers"], "router": {**router, "bias": moved}}
        return {**params, "moe_layers": layers}

    def step_metrics(params, step) -> Dict[str, Any]:
        """The router's counters of one node (docs/OBSERVABILITY.md), from
        the counts of the steps it took this round and its trained state."""
        if not moe_layers:
            return {}
        return router_counters(step["counts"], step["ladder"], step["rows"],
                               params["moe_layers"]["router"]["bias"], first_held, held)

    return Model(
        name=name,
        init=init,
        apply=apply,
        evidential=False,
        input_shape=(seq_len,),
        num_classes=vocab_size,
        meta={
            "vocab_size": vocab_size, "hidden": hidden_size,
            "layers": num_hidden_layers, "experts_held": (first_held, held),
            # One expert layer's parts, for the test that ties a share to
            # the model: route(router, x), experts(held experts, x, chosen,
            # weights) -> this share's routed part of the layer's result
            # and the step of the buffer's ladder it took.
            "route": route, "experts": partial(experts, **dispatch),
        },
        apply_train=apply_train,
        after_step=after_step,
        step_metrics=step_metrics,
    )
