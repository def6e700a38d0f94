"""Plain reference of LEAF's FEMNIST CNN (arXiv:1812.01097,
models/femnist/cnn.py): conv5x5 -> relu -> pool2 -> conv5x5 -> relu ->
pool2 -> dense -> relu -> dense.  NHWC, SAME padding, HWIO kernels.

``params`` is ``{"convs": [{"w", "b"}, ...], "fcs": [{"w", "b"}, ...]}``
for one node; ``x`` is [B, H, W, C] (or [B, H, W]).

``init`` draws one node's parameters from the sizes in the configuration's
file: weights and biases uniform within 1 / sqrt(fan_in) of nought (the
default of the paper's own models).
"""

import jax
import jax.numpy as jnp

from benchmark.reference.precision import HIGHEST, matmul, product


def _layer(key, shape, fan_in):
    kw, kb = jax.random.split(key)
    bound = fan_in ** -0.5
    return {"w": jax.random.uniform(kw, shape, jnp.float32, -bound, bound),
            "b": jax.random.uniform(kb, shape[-1:], jnp.float32, -bound, bound)}


def init(key, doc: dict):
    k, c_in, side = doc["kernel_size"], doc["channels_in"], doc["image_size"]
    widths = list(doc["dense_units"]) + [doc["num_classes"]]
    keys = iter(jax.random.split(key, len(doc["conv_channels"]) + len(widths)))
    params = {"convs": [], "fcs": []}
    for c_out in doc["conv_channels"]:  # SAME conv, then a 2x2 pool
        params["convs"].append(_layer(next(keys), (k, k, c_in, c_out), k * k * c_in))
        c_in, side = c_out, side // 2
    width = side * side * c_in
    for units in widths:
        params["fcs"].append(_layer(next(keys), (width, units), width))
        width = units
    return params


def _conv(p, x, dtype):
    conv = lambda a, w: jax.lax.conv_general_dilated(
        a, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST,
    )
    return product(conv, x, p["w"], dtype) + p["b"].astype(jnp.float32)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(params, x, dtype: str):
    if x.ndim == 3:
        x = x[..., None]
    for conv in params["convs"]:
        x = _pool(jax.nn.relu(_conv(conv, x, dtype)))
    x = x.reshape(x.shape[0], -1)
    for fc in params["fcs"][:-1]:
        x = jax.nn.relu(matmul(x, fc["w"], dtype) + fc["b"].astype(jnp.float32))
    last = params["fcs"][-1]
    return matmul(x, last["w"], dtype) + last["b"].astype(jnp.float32)
