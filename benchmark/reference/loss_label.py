"""One label a sample: the mean negative log-likelihood of a sample's
label over the samples its mask keeps; accuracy by the largest logit.

A loss module gives, for a model's ``apply(params, x, dtype)``:
``training(apply, dtype, params) -> loss(model_params, x, y, m)``, the
scalar a step differentiates (``x`` [B, ...], ``y`` as the generator made
it, ``m`` [B] the batch's mask), and ``evaluation(apply, dtype, params) ->
node(model_params, x, y, m) -> (loss, accuracy)`` of one node's held-out
samples.  ``params`` is the configuration's ``loss_params``.
"""

import jax
import jax.numpy as jnp


def training(apply, dtype, params):
    def loss(model_params, x, y, m):
        logp = jax.nn.log_softmax(apply(model_params, x, dtype), axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)

    return loss


def evaluation(apply, dtype, params):
    def node(model_params, x, y, m):
        logits = apply(model_params, x, dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        total = jnp.maximum(m.sum(), 1.0)
        hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        return (nll * m).sum() / total, (hit * m).sum() / total

    return node
