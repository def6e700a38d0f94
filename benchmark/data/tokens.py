"""Token sequences: integer ids ``[n, samples, T + 1]`` over the
configuration's (possibly sliced) vocabulary.

A position is, with probability ``dependence``, the successor of the id
before it (``successor``: a permutation of the vocabulary drawn from the
seed, the same for every node), and otherwise a fresh draw from Zipf's law
(``p(rank r) ~ r ** -zipf_exponent``; the ranks are the ids).  So a
sequence has a first-order dependence a model can learn (its loss can fall
below the unigram entropy), and the fresh draws are Zipf-distributed; the
marginal is their geometric mixture along the successor's orbits.

``x`` is a sequence's first ``T`` ids.  ``"targets": "next"``: ``y`` is
its last ``T`` ids, one target a position.  ``"targets": "last"``: ``y``
is the one id after them (LEAF Shakespeare's own task shape).

Parameters in the configuration's ``data``, all required (the generator
has no default of its own: the configuration that uses it states each and
names where it took it from): ``seq_len`` (T), ``vocab_size``,
``targets``, ``zipf_exponent``, ``dependence``.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.data import split_sizes, stream_key

TARGETS = ("next", "last")
REQUIRED = ("seq_len", "vocab_size", "targets", "zipf_exponent", "dependence")


@functools.partial(jax.jit, static_argnames=("n", "samples", "length", "vocab"))
def _sequences(key, n, samples, length, vocab, exponent, dependence):
    ks, kz, kd = jax.random.split(key, 3)
    successor = jax.random.permutation(ks, vocab).astype(jnp.int32)
    mass = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
    cdf = jnp.cumsum(mass) / jnp.sum(mass)
    shape = (length, n, samples)  # time first: the scan runs over it
    fresh = jnp.minimum(
        jnp.searchsorted(cdf, jax.random.uniform(kz, shape)), vocab - 1
    ).astype(jnp.int32)
    follows = jax.random.uniform(kd, shape) < dependence

    def step(before, now):
        drawn, follow = now
        ids = jnp.where(follow, successor[before], drawn)
        return ids, ids

    _, rest = jax.lax.scan(step, fresh[0], (fresh[1:], follows[1:]))
    return jnp.moveaxis(jnp.concatenate([fresh[:1], rest]), 0, -1)


def make(doc: dict, n: int, seed: int):
    data = doc["data"]
    missing = [k for k in REQUIRED if k not in data]
    if missing:
        raise ValueError(f"data lacks {missing}: generator 'tokens' has no defaults")
    train, held = split_sizes(doc)
    length, targets = int(data["seq_len"]), data["targets"]
    if targets not in TARGETS:
        raise ValueError(f"data.targets {targets!r} is not one of {TARGETS}")
    ids = _sequences(
        stream_key(seed, 2), n, train + held, length + 1, int(data["vocab_size"]),
        float(data["zipf_exponent"]), float(data["dependence"]),
    )
    x = ids[..., :length]
    y = ids[..., 1:] if targets == "next" else ids[..., length]
    return {"x": x[:, :train], "y": y[:, :train],
            "eval_x": x[:, train:], "eval_y": y[:, train:]}
