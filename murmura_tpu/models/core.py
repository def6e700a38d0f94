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
    """

    name: str
    init: Callable[[jax.Array], Params]
    apply: Callable[[Params, jnp.ndarray, Optional[jax.Array], bool], jnp.ndarray]
    evidential: bool = False
    input_shape: Tuple[int, ...] = ()
    num_classes: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)
    apply_stacked: Optional[Callable] = None


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
    block sharding of the node axis stays a block sharding of the folded one."""
    n, b, h, w, c = x.shape
    return x.transpose(1, 2, 3, 0, 4).reshape(b, h, w, n * c)


def unfold_nodes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """[B, H, W, N*C] -> [N, B, H, W, C], the inverse of ``fold_nodes``."""
    b, h, w, nc = x.shape
    return x.reshape(b, h, w, n, nc // n).transpose(3, 0, 1, 2, 4)


def conv2d_folded(
    p: Params, x: jnp.ndarray, padding: str = "SAME", dtype=None
) -> jnp.ndarray:
    """``conv2d`` of N nodes as one grouped convolution whose activations
    stay folded: kernels [N, kh, kw, Cin, Cout], biases [N, Cout],
    x [B, H, W, N*Cin] -> [B, H, W, N*Cout], group g is node g.

    ``vmap(conv2d)`` runs the same grouped convolution but hands its result
    back as [N, B, H, W, Cout]: bias, relu and pooling then work on a minor
    dimension of Cout (a quarter of a 128-lane tile at 32 channels) between
    two materialised transposes (PERF.md §6 PR 31).  The operations and
    their precisions are ``conv2d``'s (its mixed precision note holds).
    """
    w = p["w"]
    n, kh, kw, cin, cout = w.shape
    w = w.transpose(1, 2, 3, 0, 4).reshape(kh, kw, cin, n * cout)
    if dtype is not None:
        x = x.astype(dtype)
        w = w.astype(dtype)
    y = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=n,
    )
    if dtype is not None:
        y = y.astype(jnp.float32)
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
