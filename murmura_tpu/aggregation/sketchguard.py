"""Sketchguard: Count-Sketch compressed filtering
(reference: murmura/aggregation/sketchguard.py:13-274).

Filtering decisions run on [sketch_size] Count-Sketch compressions of the
flattened states (what would travel on the wire — sketchguard.py:126-155);
aggregation itself is BALANCE-style on the full states (sketchguard.py:236-261).
The adaptive threshold boosts by 1.5x when the mean of the last 3 acceptance
rates drops below 0.3 (attack detection — sketchguard.py:189-204); that
3-round window is this rule's carried state.
"""

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from murmura_tpu.aggregation.balance import accept_with_closest_fallback
from murmura_tpu.aggregation.base import (
    AggContext,
    AggregatorDef,
    InfluenceDecl,
    blend_with_own,
    circulant_masked_mean,
    circulant_neighbor_distances,
    masked_neighbor_mean,
    pairwise_l2_distances,
)
from murmura_tpu.ops.sketch import count_sketch, make_sketch_tables


def make_sketchguard(
    model_dim: int,
    sketch_size: int = 1000,
    gamma: float = 2.0,
    kappa: float = 1.0,
    alpha: float = 0.5,
    min_neighbors: int = 1,
    network_seed: int = 42,
    attack_detection_window: int = 5,
    exchange_offsets: Optional[Sequence[int]] = None,
    sparse_exchange: bool = False,
    **_params,
) -> AggregatorDef:
    hash_np, sign_np = make_sketch_tables(model_dim, sketch_size, network_seed)
    hash_table = jnp.asarray(hash_np)
    sign_table = jnp.asarray(sign_np)
    offsets = None if exchange_offsets is None else [int(o) for o in exchange_offsets]
    if sparse_exchange and offsets is None:
        raise ValueError("sparse_exchange requires exchange_offsets")

    # The reference keeps a deque(maxlen=attack_detection_window) of
    # acceptance rates but its threshold logic only reads the last 3
    # (sketchguard.py:64, 197-201); a window < 3 therefore disables the
    # attack factor entirely.  We carry the full window for parity.
    window = max(1, int(attack_detection_window))

    def init_state(num_nodes: int):
        return {
            # rolling acceptance-rate history, most recent last
            "acc_window": np.zeros((num_nodes, window), dtype=np.float32),
            "window_len": np.zeros((num_nodes,), dtype=np.int32),
        }

    def aggregate(own, bcast, adj, round_idx, state, ctx: AggContext):
        own_sk = count_sketch(own, hash_table, sign_table, sketch_size)
        bcast_sk = count_sketch(bcast, hash_table, sign_table, sketch_size)

        own_sk_norm = jnp.sqrt(jnp.sum(own_sk * own_sk, axis=-1))

        lambda_t = round_idx / jnp.maximum(1, ctx.total_rounds)
        time_factor = gamma * jnp.exp(-kappa * lambda_t)
        # Attack detection: boost threshold when the mean of the last 3
        # acceptance rates dropped below 0.3, once >= 3 rounds are in the
        # window (sketchguard.py:195-201).
        window_active = (state["window_len"] >= 3) & (window >= 3)
        recent = state["acc_window"][:, -3:].mean(axis=1)
        attack_factor = jnp.where(window_active & (recent < 0.3), 1.5, 1.0)
        threshold = time_factor * attack_factor * own_sk_norm

        if sparse_exchange:
            # Sparse exchange mode: the distance filter itself runs in
            # *circulant* sketch space — [k, N] per-offset sketch distances
            # via rolls instead of the [N, N] pairwise matrix — so nothing
            # O(N^2) is ever materialized and the whole rule stays
            # ppermute-only (the 'sparse' collectives declaration below).
            # The direct elementwise norm differs from the Gram-identity
            # path in f32 rounding, so sparse-vs-circulant parity for this
            # rule is allclose, not byte-exact.
            edge_b = adj > 0  # [k, N]
            d_k = circulant_neighbor_distances(
                own_sk, bcast_sk, offsets
            )  # [k, N]
            accept_k_b = edge_b & (d_k <= threshold[None, :])
            count = accept_k_b.sum(axis=0)
            closest = jnp.argmin(jnp.where(edge_b, d_k, jnp.inf), axis=0)
            has_any = edge_b.any(axis=0)
            fallback = (
                ((count < min_neighbors) & has_any)[None, :]
                & (jnp.arange(len(offsets))[:, None] == closest[None, :])
                & edge_b
            )
            accept_k = (accept_k_b | fallback).astype(own.dtype)
            neighbor_avg = circulant_masked_mean(bcast, accept_k, offsets)
            has_accepted = accept_k.sum(axis=0) > 0
            new_flat = blend_with_own(own, neighbor_avg, has_accepted, alpha)

            degree = jnp.maximum(adj.sum(axis=0), 1.0)
            acc_rate = accept_k.sum(axis=0) / degree
            new_state = {
                "acc_window": jnp.concatenate(
                    [state["acc_window"][:, 1:], acc_rate[:, None]], axis=1
                ),
                "window_len": jnp.minimum(state["window_len"] + 1, window),
            }
            stats = {
                "acceptance_rate": acc_rate,
                "threshold": threshold,
                "compression_ratio": jnp.full(
                    (own.shape[0],), model_dim / sketch_size, dtype=own.dtype
                ),
            }
            return new_flat, new_state, stats

        sk_dist = pairwise_l2_distances(own_sk, bcast_sk)
        accepted = accept_with_closest_fallback(sk_dist, adj, threshold, min_neighbors)

        if offsets is not None:
            # The filter ran in cheap sketch space ([N, S]); only the
            # full-state mean is heavy. On a circulant graph the accepted
            # mask is nonzero only at the k offsets — extract those columns
            # and accumulate rolled copies instead of an [N, N] @ [N, P]
            # gather (tpu.exchange: ppermute).
            n = own.shape[0]
            cols = (
                jnp.arange(n)[None, :] + jnp.asarray(offsets)[:, None]
            ) % n  # [k, N]
            accept_k = accepted[jnp.arange(n)[None, :], cols]  # [k, N]
            neighbor_avg = circulant_masked_mean(bcast, accept_k, offsets)
        else:
            neighbor_avg = masked_neighbor_mean(bcast, accepted)
        has_accepted = accepted.sum(axis=1) > 0
        new_flat = blend_with_own(own, neighbor_avg, has_accepted, alpha)

        degree = jnp.maximum(adj.sum(axis=1), 1.0)
        acc_rate = accepted.sum(axis=1) / degree
        new_state = {
            "acc_window": jnp.concatenate(
                [state["acc_window"][:, 1:], acc_rate[:, None]], axis=1
            ),
            "window_len": jnp.minimum(state["window_len"] + 1, window),
        }
        stats = {
            "acceptance_rate": acc_rate,
            "threshold": threshold,
            "compression_ratio": jnp.full(
                (own.shape[0],), model_dim / sketch_size, dtype=own.dtype
            ),
        }
        return new_flat, new_state, stats

    return AggregatorDef(
        name="sketchguard",
        aggregate=aggregate,
        init_state=init_state,
        state_kind={"acc_window": "node", "window_len": "node"},
        # MUR202: the distance filter runs in dense *sketch* space ([N, S],
        # S << P) by design, so even the circulant mode gathers/reduces the
        # small sketches — only the heavy [N, P] mean must stay ppermute.
        # The sparse mode filters in *circulant* sketch space instead
        # (rolled per-offset distances), so it is ppermute-only (MUR601).
        collectives={
            "dense": {"all_gather", "all_reduce"},
            "circulant": {"all_gather", "all_reduce", "ppermute"},
            "sparse": {"ppermute"},
        },
        # MUR800: BALANCE-style distance filtering in sketch space — the
        # accept set is data-dependent and spans the whole neighborhood on
        # benign inputs; declared unbounded (the BALANCE rationale).
        influence=InfluenceDecl(
            "unbounded",
            note="sketch-space distance accept-filter: benign inputs "
            "accept every neighbor; exclusion is data-dependent",
        ),
    )
