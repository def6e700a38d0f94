"""Gaussian attack: a compromised node broadcasts its state plus
N(0, noise_std^2) noise.  The draw is the reference's own: the rules of
the benchmark's cells reject such a broadcast whatever its values."""

import jax
import jax.numpy as jnp


def apply(rows, key, params, round_idx, context):
    """``rows``: the compromised nodes' own states [C, P], float32."""
    noise = jax.random.normal(key, rows.shape, jnp.float32)
    return rows + noise * float(params.get("noise_std", 10.0))


def scale(params) -> float:
    """The factor on the sender's state: the noise is added, not scaled."""
    return 1.0
