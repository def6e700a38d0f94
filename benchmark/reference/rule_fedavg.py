"""Plain FedAvg (murmura's fedavg.py): the mean of the own state and the
neighbours' broadcasts, every one with the same weight 1 / (1 + degree),
summed in float32 and rounded once to the dtype the state is resident in
(what the round's ``stored`` would do to a float32 mean: it then finds the
dtype there already).  The columns go through a block at a time into one
array that each block's program updates in place, so that three nodes of
half a billion parameters never stand in float32, nor in pieces, beside
themselves: the new state and a block's float32 copies are all that is
added to what the round holds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import HIGHEST

BLOCK = 1 << 24  # columns a block


def init_state(num_nodes, params):
    return {}


def recover(share, linked, params):
    """The rule's weights do not depend on what was sent."""
    weights = np.eye(linked.shape[0]) + linked
    return (weights / weights.sum(axis=1, keepdims=True)).astype(np.float32)


@functools.partial(jax.jit, donate_argnums=0, static_argnames="block")
def _put_mean(new, own, bcast, adj, start, block):
    """``new`` with the mean of ``block`` columns from ``start`` on."""
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 1).astype(jnp.float32)
    total = cut(own) + jnp.dot(adj, cut(bcast), precision=HIGHEST)
    mean = total / (1.0 + adj.sum(axis=1))[:, None]
    return jax.lax.dynamic_update_slice_in_dim(new, mean.astype(new.dtype), start, 1)


def aggregate(own, bcast, adj, round_idx, state, params, total_rounds, context):
    adj = jnp.asarray(adj, jnp.float32)
    columns = own.shape[1]
    block = min(BLOCK, columns)
    new = jnp.zeros_like(own)
    for a in range(0, columns, block):
        # The last block starts early enough to be whole: it writes some
        # columns a second time, with the same values.
        new = _put_mean(new, own, bcast, adj, min(a, columns - block), block)
    return new, state, {"num_neighbors": adj.sum(axis=1)}
