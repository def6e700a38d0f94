"""One target a position: ``y`` [B, T] holds the id that follows each of a
sample's T positions, the logits are [B, T, V].  A sample's loss is the
mean negative log-likelihood over its positions; the batch's, the mean of
its samples' under the samples' mask ``m`` [B].  Accuracy is per position.

An ``apply`` may return ``(logits, auxiliary)``, the auxiliary a scalar of
the batch (a router's balance loss); training adds it times
``loss_params["auxiliary_coefficient"]`` (default 0).  The evaluation is
the likelihood alone.  The interface: ``loss_label.py``.
"""

import jax
import jax.numpy as jnp


def _logits(out):
    return out if not isinstance(out, tuple) else out[0]


def _per_sample_nll(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0].mean(axis=-1)


def training(apply, dtype, params):
    coefficient = float(params.get("auxiliary_coefficient", 0.0))

    def loss(model_params, x, y, m):
        out = apply(model_params, x, dtype)
        nll = (_per_sample_nll(_logits(out), y) * m).sum() / jnp.maximum(m.sum(), 1.0)
        if isinstance(out, tuple) and coefficient:
            nll = nll + coefficient * out[1]
        return nll

    return loss


def evaluation(apply, dtype, params):
    def node(model_params, x, y, m):
        logits = _logits(apply(model_params, x, dtype))
        total = jnp.maximum(m.sum(), 1.0)
        hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32).mean(axis=-1)
        return (_per_sample_nll(logits, y) * m).sum() / total, (hit * m).sum() / total

    return node
