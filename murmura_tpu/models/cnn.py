"""FEMNIST CNN family and CelebA CNN.

Architectural parity with the reference LEAF models
(murmura/examples/leaf/datasets.py:204-297, murmura/examples/leaf/models.py:12-192):
- femnist baseline: conv5x5x32 -> pool -> conv5x5x64 -> pool -> fc2048 -> fc62
  (~6.5M params);
- scaling variants tiny (8/16/fc256), small (16/32/fc512), large (64/128/fc4096),
  xlarge (3x3 convs 64/128/256 + fc4096 + fc2048).

All convs are NHWC with SAME padding; 28x28 grayscale in, two 2x2 max-pools
down to 7x7 before the dense stack — shapes that tile cleanly onto the MXU.
"""

from typing import Sequence

import jax
import jax.numpy as jnp

from murmura_tpu.models.core import (
    Model,
    conv2d_folded,
    conv_init,
    dense,
    dense_init,
    fold_nodes,
    max_pool,
    nodes_a_group,
    resolve_dtype,
    unfold_nodes,
)
from murmura_tpu.parallel.mesh import nodes_a_device

FEMNIST_VARIANTS = {
    # variant: (conv_channels, kernel, fc_dims)
    "tiny": ((8, 16), 5, (256,)),
    "small": ((16, 32), 5, (512,)),
    "baseline": ((32, 64), 5, (2048,)),
    "large": ((64, 128), 5, (4096,)),
    "xlarge": ((64, 128, 256), 3, (4096, 2048)),
}


def _stacked_forward(pool_after: Sequence[bool], cd):
    """The one definition of both CNNs, over leaves with a leading node axis:
    (params[N, ...], x[N, B, H, W, C], keys, train) -> [N, B, K], a relu
    after every convolution and a 2x2 max-pool where ``pool_after`` says.

    The convolution stack keeps the node folded into the channel axis from
    the input to the flatten before the first dense layer (``conv2d_folded``),
    every convolution with the same number of nodes a group
    (``nodes_a_group``, from the stack's narrowest output: 4 at 16 and 32
    channels, 1 at 8, from 64 on and at N = 1); the dense layers are
    ``vmap(dense)``.  ``murmura.conv`` and
    ``murmura.dense`` label the two stacks in a trace (and their backward
    passes: docs/OBSERVABILITY.md).
    """

    def forward(params, x, keys=None, train=False):
        if x.ndim == 4:  # grayscale without a channel axis
            x = x[..., None]
        n, b = x.shape[:2]
        per = nodes_a_group(
            nodes_a_device(n), min(c["w"].shape[-1] for c in params["convs"]))
        with jax.named_scope("murmura.conv"):
            x = fold_nodes(x)
            for conv_p, pool in zip(params["convs"], pool_after):
                x = conv2d_folded(conv_p, x, dtype=cd, per_group=per)
                x = jax.nn.relu(x)
                if pool:
                    x = max_pool(x)
            # Flatten order (h, w, c) a node, as x.reshape((B, -1)) of NHWC.
            x = unfold_nodes(x, n).reshape(n, b, -1)
        with jax.named_scope("murmura.dense"):
            layer = jax.vmap(lambda fc, h: dense(fc, h, cd))
            for fc in params["fcs"][:-1]:
                x = jax.nn.relu(layer(fc, x))
            return layer(params["fcs"][-1], x)

    return forward


def _one_node(forward):
    """``apply`` of one node: the stacked forward at N = 1."""

    def apply(params, x, key=None, train=False):
        stacked = jax.tree_util.tree_map(lambda l: l[None], params)
        return forward(stacked, x[None], key, train)[0]

    return apply


def make_femnist_cnn(
    num_classes: int = 62,
    variant: str = "baseline",
    image_size: int = 28,
    channels_in: int = 1,
    name: str = None,
    compute_dtype=None,
) -> Model:
    """Build a FEMNIST CNN ``Model`` for 28x28x1 inputs."""
    if variant not in FEMNIST_VARIANTS:
        raise ValueError(
            f"Unknown FEMNIST variant '{variant}' (choose from {list(FEMNIST_VARIANTS)})"
        )
    conv_channels, kernel, fc_dims = FEMNIST_VARIANTS[variant]
    cd = resolve_dtype(compute_dtype)
    final_hw = image_size // 4
    flat_dim = final_hw * final_hw * conv_channels[-1]
    dense_dims = [flat_dim] + list(fc_dims) + [num_classes]

    def init(key: jax.Array):
        n_conv = len(conv_channels)
        n_fc = len(dense_dims) - 1
        keys = jax.random.split(key, n_conv + n_fc)
        params = {"convs": [], "fcs": []}
        c_prev = channels_in
        for i, c in enumerate(conv_channels):
            params["convs"].append(conv_init(keys[i], kernel, kernel, c_prev, c))
            c_prev = c
        for j in range(n_fc):
            params["fcs"].append(
                dense_init(keys[n_conv + j], dense_dims[j], dense_dims[j + 1])
            )
        return params

    # xlarge applies conv1,conv2 then pool, conv3 then pool (reference:
    # examples/leaf/models.py:159-169); others pool after every conv.
    pool_after = (True, True) if len(conv_channels) == 2 else (False, True, True)
    stacked = _stacked_forward(pool_after, cd)

    return Model(
        name=name or f"leaf.femnist.{variant}",
        init=init,
        apply=_one_node(stacked),
        evidential=False,
        input_shape=(image_size, image_size, channels_in),
        num_classes=num_classes,
        meta={"variant": variant},
        apply_stacked=stacked,
    )


def make_celeba_cnn(
    num_classes: int = 2,
    image_size: int = 84,
    channels: Sequence[int] = (32, 64, 128),
    fc_dim: int = 256,
    name: str = "leaf.celeba",
    compute_dtype=None,
) -> Model:
    """LeNet-style CelebA CNN for 84x84 RGB
    (reference: murmura/examples/leaf/datasets.py:235-297)."""
    cd = resolve_dtype(compute_dtype)
    n_conv = len(channels)
    final_hw = image_size // (2**n_conv)
    flat_dim = final_hw * final_hw * channels[-1]

    def init(key: jax.Array):
        keys = jax.random.split(key, n_conv + 2)
        params = {"convs": [], "fcs": []}
        c_prev = 3
        for i, c in enumerate(channels):
            params["convs"].append(conv_init(keys[i], 3, 3, c_prev, c))
            c_prev = c
        params["fcs"].append(dense_init(keys[n_conv], flat_dim, fc_dim))
        params["fcs"].append(dense_init(keys[n_conv + 1], fc_dim, num_classes))
        return params

    stacked = _stacked_forward((True,) * n_conv, cd)

    return Model(
        name=name,
        init=init,
        apply=_one_node(stacked),
        evidential=False,
        input_shape=(image_size, image_size, 3),
        num_classes=num_classes,
        apply_stacked=stacked,
    )
