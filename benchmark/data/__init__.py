"""Generators of a cell's samples, found by the name a configuration's
``data.generator`` gives: ``make(doc, n, seed) -> {x, y, eval_x, eval_y}``,
every array ``[n, samples, ...]``, drawn on the device in one jitted call.
``x``/``y`` are the first ``samples_per_node - held_out_per_node`` samples
of a node (trained on), ``eval_x``/``eval_y`` the rest (held out)."""

import jax


def stream_key(seed: int, stream: int):
    """The key of one of a seed's streams: 1 the weights, 2 the samples."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)


def split_sizes(doc: dict):
    """``(trained on, held out)`` samples a node."""
    held = int(doc["data"]["held_out_per_node"])
    return int(doc["data"]["samples_per_node"]) - held, held
