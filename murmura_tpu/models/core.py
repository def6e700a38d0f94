"""Functional model abstraction and layer primitives.

Models are (init, apply) pairs over plain dict pytrees — no module classes,
no mutable state. This is what makes the framework's core trick cheap:
stacking N nodes' parameters along a leading axis and vmap/shard_map-ing
``apply`` over it (the reference instead deep-copies nn.Modules and calls
``load_state_dict`` per neighbor per round — murmura/aggregation/
evidential_trust.py:236-260, a cost this design eliminates).

Conventions:
- ``init(key) -> params`` (nested dict of float32 arrays);
- ``apply(params, x, key, train) -> outputs`` where ``train`` is a Python
  bool (static under trace) and ``key`` drives dropout when training;
- images are NHWC; convs/matmuls stay large and batched for the MXU.

Normalization: models use LayerNorm instead of the reference's BatchNorm1d
(murmura/examples/wearables/models.py:208). BatchNorm's integer
``num_batches_tracked`` buffer forces the reference to special-case
non-float state in every aggregator (aggregation/base.py:100-113); LayerNorm
keeps the whole state float, aggregatable, and jit-friendly.
"""

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Any


@dataclass(frozen=True)
class Model:
    """A functional model: pure init/apply plus metadata.

    Attributes:
        name: registry id.
        init: key -> params pytree.
        apply: (params, x, key, train) -> [B, K] logits, or Dirichlet alphas
            when ``evidential`` is True.
        evidential: whether outputs are Dirichlet concentration parameters.
        input_shape: per-sample input shape (no batch dim).
        num_classes: output arity.
        apply_stacked: (params[N, ...], x[N, B, ...], keys, train) ->
            [N, B, K], the forward of N nodes at once for a model whose
            layers batch badly under ``vmap(apply)`` (convolutions: see
            ``conv2d_folded``); ``None`` where ``vmap(apply)`` is the
            stacked forward.  Nodes share nothing in it.
        apply_train: (params, x, key) -> (outputs, auxiliary), the training
            forward of a model whose training rule is more than
            ``p - lr g`` (models/decoder.py): outputs [B, T, V] with one
            target a position, ``auxiliary["loss"]`` [B], a sample's part
            of the loss that is the model's own, weighted already (a
            router's balance loss), and ``auxiliary["step"]``, a tree of
            counts with the batch's axis first.  The round then trains
            and evaluates one node after another (core/rounds.py
            ``local_training_by_node``): such a model's products are wide
            already, and a node axis would multiply what is live.  ``None``
            (every other model): ``apply`` under ``vmap``.
        after_step: (params, counts) -> params, what the training rule
            moves after each SGD step a node takes, from that step's
            counts summed over the samples the batch's mask keeps (a state
            that takes no gradient).  Read only with ``apply_train``.
        step_metrics: (params, counts) -> {name: scalar}, a node's counters
            of a round, from the counts summed over the steps it took and
            its trained state; they ride the round's metrics as
            ``agg_<name>``.  Read only with ``apply_train``.
    """

    name: str
    init: Callable[[jax.Array], Params]
    apply: Callable[[Params, jnp.ndarray, Optional[jax.Array], bool], jnp.ndarray]
    evidential: bool = False
    input_shape: Tuple[int, ...] = ()
    num_classes: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)
    apply_stacked: Optional[Callable] = None
    apply_train: Optional[Callable] = None
    after_step: Optional[Callable] = None
    step_metrics: Optional[Callable] = None


# ---------------------------------------------------------------------------
# Layer primitives
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, in_dim: int, out_dim: int) -> Params:
    """He-uniform linear layer init (matches torch.nn.Linear's default
    kaiming-uniform fan_in scaling)."""
    kw, kb = jax.random.split(key)
    bound = 1.0 / jnp.sqrt(in_dim)
    return {
        "w": jax.random.uniform(kw, (in_dim, out_dim), jnp.float32, -bound, bound),
        "b": jax.random.uniform(kb, (out_dim,), jnp.float32, -bound, bound),
    }


def resolve_dtype(compute_dtype) -> Optional[jnp.dtype]:
    """Config string -> matmul compute dtype (None = full precision).

    bfloat16 is the MXU-native input precision; params stay float32 and all
    accumulations are forced to float32 via preferred_element_type, so only
    the multiplicand precision drops (standard TPU mixed precision).
    """
    if compute_dtype in (None, "float32", jnp.float32):
        return None
    if compute_dtype in ("bfloat16", jnp.bfloat16):
        return jnp.bfloat16
    raise ValueError(f"Unknown compute_dtype: {compute_dtype!r}")


def dense(p: Params, x: jnp.ndarray, dtype=None) -> jnp.ndarray:
    if dtype is None:
        return x @ p["w"] + p["b"]
    y = jnp.dot(
        x.astype(dtype), p["w"].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return y + p["b"]


def conv_init(key: jax.Array, kh: int, kw: int, c_in: int, c_out: int) -> Params:
    """5x5/3x3 conv init, kaiming-uniform over fan_in."""
    k1, k2 = jax.random.split(key)
    fan_in = kh * kw * c_in
    bound = 1.0 / jnp.sqrt(fan_in)
    return {
        "w": jax.random.uniform(
            k1, (kh, kw, c_in, c_out), jnp.float32, -bound, bound
        ),
        "b": jax.random.uniform(k2, (c_out,), jnp.float32, -bound, bound),
    }


def conv2d(
    p: Params, x: jnp.ndarray, padding: str = "SAME", dtype=None
) -> jnp.ndarray:
    """NHWC conv with HWIO kernel.

    Mixed precision note: unlike dot, conv's VJP rejects mixed-dtype
    operands under preferred_element_type, so the low-precision path keeps
    the conv uniformly in ``dtype`` (MXU accumulates f32 internally) and
    casts the result back to float32.
    """
    return conv2d_folded(
        {"w": p["w"][None], "b": p["b"][None]}, x, padding, dtype
    )


def fold_nodes(x: jnp.ndarray) -> jnp.ndarray:
    """[N, B, H, W, C] -> [B, H, W, N*C], the node the major factor of the
    folded axis (index n*C + c): the layout ``vmap``'s rule for
    ``conv_general_dilated`` gives a grouped convolution's operand, so a
    block sharding of the node axis stays a block sharding of the folded
    one, and p consecutive nodes are p*C consecutive channels: a group of
    ``conv2d_folded`` is a slice of this axis whatever p is."""
    n, b, h, w, c = x.shape
    return x.transpose(1, 2, 3, 0, 4).reshape(b, h, w, n * c)


def unfold_nodes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """[B, H, W, N*C] -> [N, B, H, W, C], the inverse of ``fold_nodes``."""
    b, h, w, nc = x.shape
    return x.reshape(b, h, w, n, nc // n).transpose(3, 0, 1, 2, 4)


LANES = 128  # the minor dimension of a TPU tile, and the MXU's width


def nodes_a_group(held: int, narrowest: int) -> int:
    """How many nodes share a group of ``conv2d_folded``, for a stack of
    convolutions whose narrowest output has ``narrowest`` channels a node,
    on a device that holds ``held`` nodes (``parallel.mesh.nodes_a_device``).
    The one place the rule is written; it reads shapes, nothing a user sets.

    The TPU compiler lays a grouped convolution's operands out as
    [B, H, W, G, C/G] with (G, C/G) in the tile, so one node a group puts
    a node's channels alone on the 128 lanes: 32 channels fill a quarter
    of every tile that local SGD streams through HBM, and a quarter of
    the MXU's columns.  p nodes a group make that p * narrowest lanes, at
    p times the products.  The rule is what the chip measured (one
    local-SGD step, PERF.md §6 PR 33): four nodes a group, or two where
    four do not divide ``held``, for a stack whose narrowest output fills
    less than half a tile (``small`` 16/32 channels, ``baseline`` 32/64,
    CelebA 32/64/128: a step 32, 29 and 46 % shorter at four, 12 and 27 %
    at two, and eight lost to four); one where it fills half or more
    (``large`` 64/128 gained 5 % at two and lost 8 % at three, ``xlarge``
    64/128/256 lost 7 % at two: the widest layers pay the products and
    had no padding to lose), where four nodes would still fill under half
    a tile (``tiny``, 8/16: not measured, and the CPU suite's cost), and
    where ``held`` is odd.  One p for the whole stack: consecutive layers
    that grouped otherwise would relayout every activation between them.

    A group never spans two devices: under a mesh the node axis is
    block-sharded, and p divides the block (``held``), so the convolution
    stays local to each device.
    """
    if narrowest >= LANES // 2 or 4 * narrowest < LANES // 2:
        return 1
    return next((p for p in (4, 2) if held % p == 0), 1)


def _by_channel(flag: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """A node's flag on each of its channels of a folded [B, H, W, N*C]."""
    return jnp.repeat(flag, x.shape[-1] // flag.shape[0])


def _finite_nodes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """[N] bool: which nodes of a folded [B, H, W, N*C] hold finite values
    only (by channel first: the reduction leaves the lanes where they are)."""
    return jnp.isfinite(x).all(axis=(0, 1, 2)).reshape(n, -1).all(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _finite_cotangent(y: jnp.ndarray, n: int) -> jnp.ndarray:
    """The identity on a folded [B, H, W, N*C], whose cotangent loses (to
    exact zeros) every node whose sum by channel is not finite: the sum
    the bias's gradient takes anyway, not finite wherever a term is not."""
    return y


def _finite_cotangent_fwd(y, n):
    return y, None


def _finite_cotangent_bwd(n, _, dy):
    whole = jnp.isfinite(dy.sum(axis=(0, 1, 2))).reshape(n, -1).all(axis=1)
    return (jnp.where(_by_channel(whole, dy), dy, 0),)


_finite_cotangent.defvjp(_finite_cotangent_fwd, _finite_cotangent_bwd)


def conv2d_folded(
    p: Params, x: jnp.ndarray, padding: str = "SAME", dtype=None,
    per_group: int = 1,
) -> jnp.ndarray:
    """``conv2d`` of N nodes as one grouped convolution whose activations
    stay folded: kernels [N, kh, kw, Cin, Cout], biases [N, Cout],
    x [B, H, W, N*Cin] -> [B, H, W, N*Cout]; group g holds the
    ``per_group`` nodes from g * per_group on (one node a group by default;
    the rule for a stack is ``nodes_a_group`` above).

    ``vmap(conv2d)`` runs the same grouped convolution but hands its result
    back as [N, B, H, W, Cout]: bias, relu and pooling then work on a minor
    dimension of Cout (a quarter of a 128-lane tile at 32 channels) between
    two materialised transposes (PERF.md §6 PR 31).  The operations and
    their precisions are ``conv2d``'s (its mixed precision note holds).

    With several nodes a group the group's kernel is block-diagonal: node
    r's [kh, kw, Cin, Cout] kernel sits at rows r*Cin.., columns r*Cout..
    of a [kh, kw, p*Cin, p*Cout] kernel and exact zeros elsewhere, built
    here in the compute dtype (label ``murmura.pack``), so the group's
    activations are p*C channels wide (all 128 lanes at 4 x 32) and the
    layout of everything around the convolution is unchanged.  A product
    with an exact zero adds an exact zero to the float32 accumulator: the
    result is one node a group's up to the order of summation inside a
    product.  Autodiff transposes the packing into taking the gradient's
    diagonal blocks back out, so parameters, their gradients and the flat
    [N, P] row keep their shapes.

    A node at fault stays alone, as under one node a group.  The zero
    blocks multiply the other nodes' activations (forward) and output
    cotangents (backward), and 0 * inf is NaN, so a value that is not
    finite must never reach the product.  Forward: a node whose input
    holds one enters as zeros and leaves as NaN, all of it.  Backward: a
    node whose output cotangent holds one enters the transposed
    convolution as zeros (``_finite_cotangent``), while its bias, added
    after, takes the cotangent as it came.  The other nodes of the group
    get what they get beside a healthy node; the node at fault ends the step
    with parameters that are not finite, as under ``vmap(conv2d)``, for
    ``faults.nan_quarantine`` to count it and only it (what differs: its
    kernel takes a zero gradient where ``vmap`` gives it a non-finite
    one, its bias and the layers behind take the non-finite ones).
    ``where``, never a product with a mask: core/rounds.py's rule for the
    flat rows, for the same reason.
    """
    w = p["w"]
    n, kh, kw, cin, cout = w.shape
    w = w.transpose(1, 2, 3, 0, 4)  # [kh, kw, Cin, N, Cout]
    if per_group > 1:
        with jax.named_scope("murmura.pack"):
            # [kh, kw, q, Cin, g, r, Cout]: node (g, r)'s kernel where the
            # input block q is its own, zero where it is a neighbour's.
            own = jnp.eye(per_group, dtype=bool)[:, None, None, :, None]
            w = w.reshape(kh, kw, 1, cin, n // per_group, per_group, cout)
            w = jnp.where(own, w.astype(dtype or w.dtype), 0)
    w = w.reshape(kh, kw, per_group * cin, n * cout)
    if dtype is not None:
        x = x.astype(dtype)
        w = w.astype(dtype)
    if per_group > 1:
        sound = _finite_nodes(x, n)
        x = jnp.where(_by_channel(sound, x), x, 0)
    y = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=n // per_group,
    )
    if dtype is not None:
        y = y.astype(jnp.float32)
    if per_group > 1:
        y = jnp.where(_by_channel(sound, y), y, jnp.nan)
        y = _finite_cotangent(y, n)
    return y + p["b"].reshape(n * cout)


def max_pool(x: jnp.ndarray, window: int = 2, stride: int = 2) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def layernorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dropout(
    key: Optional[jax.Array], x: jnp.ndarray, rate: float, train: bool
) -> jnp.ndarray:
    if not train or rate <= 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def evidential_head(p: Params, x: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """Dense -> softplus evidence -> alpha = evidence + 1
    (reference: murmura/examples/wearables/models.py:18-46)."""
    return jax.nn.softplus(dense(p, x, dtype)) + 1.0


def split_keys(key: jax.Array, n: int) -> Sequence[jax.Array]:
    return jax.random.split(key, n)
