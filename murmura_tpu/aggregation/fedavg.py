"""FedAvg: equal-weight mean of own + neighbor states
(reference: murmura/aggregation/fedavg.py:19-42).

Vectorized over the whole network: own state plus one adjacency matmul over
the broadcast tensor, normalized by 1 + degree.

``exchange_offsets`` (tpu.exchange: ppermute): on a circulant graph the
adjacency matmul is a sum of fixed circular shifts; ``jnp.roll`` along the
sharded node axis lowers to boundary-slice collective-permutes over ICI —
O(degree) bytes per device instead of the all-gathered [N, P] tensor
(SURVEY.md §7 "use ppermute neighbor-only exchange for sparse topologies").
"""

from typing import Optional, Sequence

import jax.numpy as jnp

from murmura_tpu.aggregation.base import (
    AggContext,
    AggregatorDef,
    InfluenceDecl,
    circulant_weighted_sum,
)


def make_fedavg(
    exchange_offsets: Optional[Sequence[int]] = None,
    sparse_exchange: bool = False,
    **_params,
) -> AggregatorDef:
    offsets = None if exchange_offsets is None else [int(o) for o in exchange_offsets]
    if sparse_exchange and offsets is None:
        raise ValueError("sparse_exchange requires exchange_offsets")

    def aggregate(own, bcast, adj, round_idx, state, ctx: AggContext):
        if own.ndim > 2:
            # ``leafwise`` (below): a stacked leaf [N, ...] handed over as
            # it lies.  The same mean, contracting the node axis alone: no
            # reshape, which on a TPU would relayout the whole leaf.
            degree = adj.sum(axis=1)
            neighbor_sum = jnp.tensordot(
                adj.astype(bcast.dtype), bcast, axes=(1, 0),
                preferred_element_type=jnp.float32,
            )
            by_node = (1.0 + degree).reshape((-1,) + (1,) * (own.ndim - 1))
            new = ((own + neighbor_sum) / by_node).astype(own.dtype)
            return new, state, {"num_neighbors": degree}
        if sparse_exchange:
            # Sparse exchange mode (topology/sparse.py): ``adj`` is the
            # [k, N] per-offset edge mask, never [N, N]; its rows weight
            # the rolled neighbor sum directly, so inactive edges (one_peer
            # rounds, fault-dropped links) contribute nothing.  An all-ones
            # mask reproduces the circulant path bit-for-bit (1.0 * x is
            # exact).
            degree = adj.sum(axis=0)
        else:
            degree = adj.sum(axis=1)
        if offsets is not None:
            # roll(bcast, -o)[i] == bcast[(i+o) % N]: node i's neighbor at
            # circulant offset o; the shared kernel chunks P at large N*P.
            # f32 weights force f32 per-chunk accumulation over the k adds
            # (matching the dense branch's preferred_element_type) while
            # out_dtype keeps the stored sum — and any chunked [N, P]
            # buffer — in the resident param dtype.
            if sparse_exchange:
                w_k = adj.astype(jnp.float32)
            else:
                w_k = jnp.ones((len(offsets), own.shape[0]), jnp.float32)
            neighbor_sum = circulant_weighted_sum(
                bcast, w_k, offsets, out_dtype=own.dtype
            )
        else:
            # bf16 operands with f32 accumulation (MXU-native); an f32 adj
            # operand would promote the gathered [N, P] tensor before the
            # matmul and double its HBM reads (MUR201).
            neighbor_sum = jnp.dot(
                adj.astype(bcast.dtype), bcast,
                preferred_element_type=jnp.float32,
            )
        # The 1/(1+degree) weights stay f32; only the stored mean returns
        # to the resident param dtype so the exchange never upcasts.
        new_flat = ((own + neighbor_sum) / (1.0 + degree)[:, None]).astype(
            own.dtype
        )
        return new_flat, state, {"num_neighbors": degree}

    return AggregatorDef(
        name="fedavg",
        aggregate=aggregate,
        # MUR202 contract: the dense mean is one gathered matmul; the
        # circulant path must stay boundary ppermutes — an all_gather there
        # is the exact regression tpu.exchange: ppermute exists to avoid.
        collectives={
            "dense": {"all_gather", "all_reduce"},
            "circulant": {"ppermute"},
        },
        # Compressed exchange: the circulant path touches the broadcast
        # only through the shared roll kernels, which move the int8
        # payload (MUR700).
        quantized_exchange=offsets is not None,
        # The dense mean's weights are the graph's alone (base.py).
        leafwise=offsets is None and not sparse_exchange,
        # MUR800: plain averaging has no Byzantine filter at all — every
        # neighbor's state enters the 1/(1+degree) mean.  Declared
        # unbounded on purpose: the flow analyzer must never be able to
        # "prove" fedavg robust.
        influence=InfluenceDecl(
            "unbounded",
            note="every neighbor's state enters the degree-normalized "
            "mean; a single Byzantine row moves it arbitrarily",
        ),
    )
