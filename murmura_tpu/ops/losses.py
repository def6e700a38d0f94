"""Losses and evidential uncertainty, as masked pure functions.

- masked cross-entropy mirrors the reference's CE eval sweep
  (murmura/utils/metrics.py:9-53);
- the evidential loss is Sensoy et al.'s MSE + annealed KL(Dir(alpha_tilde)||Dir(1))
  (reference: murmura/examples/wearables/models.py:89-179);
- uncertainty metrics are the Dirichlet vacuity/entropy/strength used by
  evidential evaluation and trust scoring (reference:
  murmura/examples/wearables/models.py:49-86, murmura/core/node.py:134-196).

All functions take a sample-validity ``mask`` so padded batch slots
contribute nothing to means.
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma, gammaln


def _safe_mean(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    denom = jnp.maximum(mask.sum(), 1.0)
    return (values * mask).sum() / denom


def masked_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean CE loss and accuracy over valid samples.

    Args:
        logits: [B, K] unnormalized scores.
        labels: [B] int class ids.
        mask: [B] validity (0/1).

    Returns:
        (mean_loss, accuracy) scalars.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = _safe_mean(nll, mask)
    acc = _safe_mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32), mask)
    return loss, acc


def masked_next_token_cross_entropy(
    logits: jnp.ndarray, targets: jnp.ndarray, mask: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean CE loss and accuracy with one target a position.

    A sample's loss is the mean negative log-likelihood over its positions
    (its accuracy likewise); the batch's, the mean over valid samples.

    Args:
        logits: [B, T, V] unnormalized scores.
        targets: [B, T] int ids, the one that follows each position.
        mask: [B] sample validity (0/1).

    Returns:
        (mean_loss, accuracy) scalars.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    return _safe_mean(nll.mean(axis=-1), mask), _safe_mean(hit.mean(axis=-1), mask)


def uncertainty_metrics(alpha: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Dirichlet uncertainty decomposition (reference: wearables/models.py:49-86).

    Args:
        alpha: [B, K] Dirichlet concentration parameters.

    Returns:
        dict with per-sample 'probs' [B, K], 'vacuity' [B], 'entropy' [B],
        'strength' [B].
    """
    S = alpha.sum(-1, keepdims=True)
    K = alpha.shape[-1]
    probs = alpha / S
    vacuity = K / S[..., 0]
    entropy = -(probs * jnp.log(probs + 1e-10)).sum(-1)
    return {
        "probs": probs,
        "vacuity": vacuity,
        "entropy": entropy,
        "strength": S[..., 0],
    }


def evidential_loss(
    alpha: jnp.ndarray,
    labels: jnp.ndarray,
    mask: jnp.ndarray,
    num_classes: int,
    lambda_t: jnp.ndarray,
) -> jnp.ndarray:
    """Evidential MSE + annealed KL regularizer
    (reference: wearables/models.py:118-179).

    L = mean_b[ sum_k (y - p)^2 ] + lambda_t * mean_b[ KL(Dir(alpha~)||Dir(1)) ]
    where alpha~ removes evidence for the true class.

    Args:
        alpha: [B, K] Dirichlet parameters.
        labels: [B] int labels.
        mask: [B] validity.
        num_classes: K.
        lambda_t: annealing coefficient (already scaled by lambda_weight).
    """
    y = jax.nn.one_hot(labels, num_classes)
    S = alpha.sum(-1, keepdims=True)
    p = alpha / S
    mse = ((y - p) ** 2).sum(-1)

    alpha_tilde = y + (1.0 - y) * alpha
    kl = _kl_dirichlet_to_uniform(alpha_tilde)

    return _safe_mean(mse, mask) + lambda_t * _safe_mean(kl, mask)


def _kl_dirichlet_to_uniform(alpha: jnp.ndarray) -> jnp.ndarray:
    """Per-sample KL(Dir(alpha) || Dir(1)) (reference: wearables/models.py:158-179)."""
    K = alpha.shape[-1]
    sum_alpha = alpha.sum(-1)
    return (
        gammaln(sum_alpha)
        - gammaln(jnp.asarray(float(K)))
        - gammaln(alpha).sum(-1)
        + ((alpha - 1.0) * (digamma(alpha) - digamma(sum_alpha)[..., None])).sum(-1)
    )
