"""Labelled clusters: ``x = centre[y] + cluster_std * noise`` with class
centres of ``centre_std`` an element and uniform labels, one label a
sample.  Parameters in the configuration's ``data``: ``centre_std``,
``cluster_std``, and ``params.input_shape`` / ``params.num_classes`` (which
the program's adapter takes too)."""

import functools

import jax
import jax.numpy as jnp

from benchmark.data import split_sizes, stream_key


@functools.partial(jax.jit, static_argnames=("n", "train", "held", "shape", "classes"))
def _clusters(key, n, train, held, shape, classes, centre_std, cluster_std):
    kc, ky, kx = jax.random.split(key, 3)
    centres = centre_std * jax.random.normal(kc, (classes,) + shape, jnp.float32)
    y = jax.random.randint(ky, (n, train + held), 0, classes, jnp.int32)
    noise = jax.random.normal(kx, (n, train + held) + shape, jnp.float32)
    x = centres[y] + cluster_std * noise
    return x[:, :train], y[:, :train], x[:, train:], y[:, train:]


def make(doc: dict, n: int, seed: int):
    data = doc["data"]
    train, held = split_sizes(doc)
    x, y, ex, ey = _clusters(
        stream_key(seed, 2), n, train, held, tuple(data["params"]["input_shape"]),
        int(data["params"]["num_classes"]), float(data["centre_std"]),
        float(data["cluster_std"]),
    )
    return {"x": x, "y": y, "eval_x": ex, "eval_y": ey}
