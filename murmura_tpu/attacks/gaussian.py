"""Gaussian noise attack (reference: murmura/attacks/gaussian.py:10-90).

Compromised nodes broadcast state + N(0, noise_std^2) noise; all parameters
here are float (no BatchNorm integer buffers — see models/core.py), so the
reference's dtype special-casing (gaussian.py:82-88) has no counterpart.
"""

import jax
import jax.numpy as jnp
import numpy as np

from murmura_tpu.attacks.base import Attack, select_compromised


def make_gaussian_attack(
    num_nodes: int,
    attack_percentage: float,
    noise_std: float = 10.0,
    seed: int = 42,
) -> Attack:
    compromised = select_compromised(num_nodes, attack_percentage, seed)
    comp_idx = np.flatnonzero(compromised)

    # Static one-hot scatter matrix [N, C]: row expansion happens as a
    # matmul instead of a scatter-add.  The scatter is both slower (~4x on
    # a [20, 6.5M] state) and poisons XLA's layout choice for every [N, P]
    # tensor downstream — scatter prefers a node-minor tiled layout that
    # pads the node axis to 128 lanes (2x HBM at N=64, an OOM at 64 nodes
    # on the chip), and the layout copy propagates through the whole
    # exchange.
    scatter = np.zeros((num_nodes, len(comp_idx)), dtype=np.float32)
    scatter[comp_idx, np.arange(len(comp_idx))] = 1.0

    def apply(flat, compromised_mask, key, round_idx):
        if flat.shape[0] == num_nodes and len(comp_idx):
            # Full-network view (the jitted round step): the compromised set
            # is static, so draw noise for those C rows only — a [C, P]
            # threefry instead of [N, P] (RNG generation is a measurable
            # slice of the round on TPU: PERF.md §5, the attack's noise).
            # The traced mask still gates the add, so semantics match the
            # dense path.
            noise = (
                jax.random.normal(key, (len(comp_idx),) + flat.shape[1:], flat.dtype)
                * noise_std
                * compromised_mask[comp_idx, None]
            )
            return flat + (
                jnp.asarray(scatter, flat.dtype) @ noise
            ).astype(flat.dtype)
        # Per-node views (ZMQ backend passes [1, P] with a ones mask).
        noise = jax.random.normal(key, flat.shape, flat.dtype) * noise_std
        return jnp.where(compromised_mask[:, None] > 0, flat + noise, flat)

    return Attack(name="gaussian", compromised=compromised, apply=apply)
