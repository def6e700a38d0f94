"""Plain Sketchguard (murmura's sketchguard.py): neighbours are filtered by
the distance between Count-Sketches of the states, the accepted ones are
averaged in full and blended with the own state.

The hash and sign tables are the rule's definition, not the program's
work: ``RandomState(network_seed)`` draws ``randint(0, S, P)`` and then
``choice([-1, 1], P)``.
"""

import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import HIGHEST


def _defaults(params: dict) -> dict:
    return {
        "sketch_size": 1000, "gamma": 2.0, "kappa": 1.0, "alpha": 0.5,
        "min_neighbors": 1, "network_seed": 42, "attack_detection_window": 5,
        **params,
    }


def recover(share, linked, params) -> np.ndarray:
    """The rule gives alpha to the own state and (1 - alpha) / k to each
    of the k accepted neighbours: a neighbour counts as accepted where
    its start's share in the node's state is over half the least weight
    a neighbour can have; a node that accepted none kept its own state."""
    alpha = float(_defaults(params)["alpha"])
    n = share.shape[0]
    degree = np.maximum(linked.sum(axis=1), 1)
    accepted = linked & (share > (1.0 - alpha) / (2.0 * degree)[:, None])
    count = accepted.sum(axis=1)
    weights = accepted * ((1.0 - alpha) / np.maximum(count, 1))[:, None]
    weights[np.arange(n), np.arange(n)] = np.where(count > 0, alpha, 1.0)
    return weights.astype(np.float32)


def init_state(num_nodes: int, params: dict) -> dict:
    window = max(1, int(_defaults(params)["attack_detection_window"]))
    return {
        "acc_window": jnp.zeros((num_nodes, window), jnp.float32),
        "window_len": jnp.zeros((num_nodes,), jnp.int32),
    }


def aggregate(own, bcast, adj, round_idx, state, params, total_rounds, context):
    p = _defaults(params)
    size, dim = int(p["sketch_size"]), own.shape[1]
    rng = np.random.RandomState(int(p["network_seed"]))
    hashes = rng.randint(0, size, size=dim)
    signs = rng.choice([-1, 1], size=dim).astype(np.float32)

    def sketch(rows):
        """Count-sketches of rows (in the resident dtype) on the host, a
        bucket's signed sum in float64: a scatter of 6.6M values a row is
        what the chip does worst."""
        return jnp.asarray(np.stack([
            np.bincount(hashes, weights=signs * row.astype(np.float32), minlength=size)
            for row in np.asarray(rows)
        ]), jnp.float32)

    # Only an attacked row's broadcast differs from the own state.
    attacked = np.flatnonzero(np.asarray(jnp.any(own != bcast, axis=1)))
    own_sk = bcast_sk = sketch(own)
    if len(attacked):
        bcast_sk = own_sk.at[attacked].set(sketch(bcast[attacked]))
    own, bcast = own.astype(jnp.float32), bcast.astype(jnp.float32)

    window = state["acc_window"].shape[1]
    recent = state["acc_window"][:, -3:].mean(axis=1)
    boosted = (state["window_len"] >= 3) & (window >= 3) & (recent < 0.3)
    threshold = (
        p["gamma"] * jnp.exp(-p["kappa"] * round_idx / max(1, total_rounds))
        * jnp.where(boosted, 1.5, 1.0) * jnp.linalg.norm(own_sk, axis=-1)
    )
    diff = own_sk[:, None, :] - bcast_sk[None, :, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    linked = jnp.asarray(adj) > 0
    accepted = linked & (dist <= threshold[:, None])
    closest = jnp.argmin(jnp.where(linked, dist, jnp.inf), axis=1)
    fallback = (accepted.sum(1) < p["min_neighbors"]) & linked.any(1)
    accepted = accepted | (
        fallback[:, None] & (jnp.arange(own.shape[0])[None, :] == closest[:, None])
    )
    weights = accepted.astype(jnp.float32)
    count = weights.sum(axis=1)
    mean = jnp.dot(weights, bcast, precision=HIGHEST) / jnp.maximum(count, 1.0)[:, None]
    new = jnp.where(
        (count > 0)[:, None], p["alpha"] * own + (1.0 - p["alpha"]) * mean, own
    )
    rate = count / jnp.maximum(linked.sum(axis=1), 1)
    state = {
        "acc_window": jnp.concatenate(
            [state["acc_window"][:, 1:], rate[:, None]], axis=1
        ),
        "window_len": jnp.minimum(state["window_len"] + 1, window),
    }
    return new, state, {"acceptance_rate": rate}
