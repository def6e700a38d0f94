"""Pipelined rounds: hide exchange/aggregation behind local training
(ISSUE 14; docs/PERFORMANCE.md "Pipelined rounds").

Every in-jit backend runs the round strictly serialized: train, then
exchange the broadcast, then aggregate — inside one fused scan step the
collectives sit on the critical path between the training matmuls and
the parameter update, so compression (PR 7) cut exchanged *bytes* but
not wall-clock.  The delayed-averaging line — "Improving Efficiency in
Large-Scale Decentralized Distributed Training" (arXiv:2002.01119) and
the async half of asynchronous quantized decentralized SGD
(arXiv:1910.12308, whose quantized half is PR 7 and whose staleness
half is PR 13) — shows convergence survives applying the *previous*
round's aggregation displacement while the current round trains.

This module implements that as a **double-buffered pipeline stage riding
the round program's carried state** under the reserved
:data:`PIPELINE_STATE_KEYS` (the ``STALE_STATE_KEYS`` pattern): because
it lives in ``agg_state``, the fused ``lax.scan`` carry, gang vmap,
MUR900 snapshot completeness and durability resume all cover it with no
special cases, and chunk boundaries need no explicit warm-up/drain —
the buffer simply rides the carry across dispatches.

Semantics (the docs/PERFORMANCE.md table; machine-checked by MUR120x,
analysis/pipeline.py).  Let ``Q_r = Train_r(P_r)`` be round ``r``'s
locally trained (post-quarantine-scrub) flat params and
``(B_r, A_r)`` the broadcast/adjacency pair the round *produces* —
post-attack, post-sentinel, post-codec, post-stale-fold: exactly what
the serialized program's aggregation would have consumed.  Then:

- serialized:  ``P_{r+1} = Agg(Q_r, B_r, A_r)``  (guards folded);
- pipelined:   ``P_{r+1} = Q_r + valid * (Agg(Q_{r-1}, B_{r-1},
  A_{r-1}) - Q_{r-1})`` — round ``r`` trains on params that already
  include round ``r-2``'s aggregation displacement, while round
  ``r-1``'s buffered exchange is aggregated *concurrently* with the
  training matmuls (no data dependence between the two stages; the
  program issues the aggregation's collectives on the buffered tensor
  before the training scan consumes params, so XLA's async dispatch is
  free to overlap them).

Round 0 is the warm-up: the buffer starts invalid (``pipe_valid`` 0),
the displacement is ``where``-gated to exactly zero, and
``P_1 = Q_0`` — pure local training.  There is no drain round: the last
round's broadcast is produced into the buffer and never aggregated
(visible as one un-consumed buffer in the final snapshot — a resumed
run aggregates it on its first round, which is why SIGKILL at any
boundary resumes byte-identically).

Scrub discipline: the sentinels run at *production* time, before the
buffer write — a quarantined or attack-scrubbed row never enters the
buffer, so the delayed aggregation can never replay a caught row even
though its verdict was computed one round before the aggregation runs
(the MUR1203 taint contract; the MUR1103 replay-hole discipline).

Buffer reuse (core/stale.py): with bounded staleness armed, the stale
fold's payload cache already stores exactly the post-fold broadcast the
buffer needs (``stale_cache`` after round ``r-1`` *is* ``B_{r-1}``), so
the pipeline reads its broadcast buffer from ``STALE_STATE_KEYS``
instead of carrying a duplicate [N, P] tensor — ``pipe_bcast`` exists
only in staleness-free builds.
"""

from typing import Dict, Optional, Tuple

import numpy as np

# Reserved round-program-level agg_state keys (the DMTT_STATE_KEYS /
# COMPRESS_STATE_KEYS / STALE_STATE_KEYS pattern, core/rounds.py):
# carried by the round step but never handed to the aggregation rule's
# state dict, and registered in durability/snapshot.
# RESERVED_AGG_STATE_KEY_GROUPS so the MUR900 snapshot-completeness
# bijection — and therefore SIGKILL/--resume with a populated pipeline
# buffer — covers them for free (MUR1200, analysis/pipeline.py).
ADJ_KEY = "pipe_adj"
BCAST_KEY = "pipe_bcast"
OWN_KEY = "pipe_own"
VALID_KEY = "pipe_valid"
PIPELINE_STATE_KEYS = (ADJ_KEY, BCAST_KEY, OWN_KEY, VALID_KEY)


def pipeline_state_keys(stale: bool) -> Tuple[str, ...]:
    """The PIPELINE_STATE_KEYS subset a build actually carries.

    With bounded staleness armed the broadcast buffer IS the stale
    fold's payload cache (``stale_cache`` holds the post-fold exchanged
    tensor the next round's delayed aggregation consumes), so
    ``pipe_bcast`` would be a byte-for-byte duplicate [N, P] tensor —
    it is dropped and the round program reads
    ``agg_state["stale_cache"]`` instead (module docstring).
    """
    if stale:
        return tuple(k for k in PIPELINE_STATE_KEYS if k != BCAST_KEY)
    return PIPELINE_STATE_KEYS


def init_pipeline_state(
    num_nodes: int,
    model_dim: int,
    dtype,
    *,
    sparse_offsets: Tuple[int, ...] = (),
    stale: bool = False,
) -> Dict[str, np.ndarray]:
    """Initial ``agg_state`` entries for a pipelined program.

    The buffer starts *invalid* (``pipe_valid`` 0): round 0's delayed
    aggregation runs on these placeholder values — a full base-like
    graph over the initial broadcast, so every rule's math is finite —
    and its displacement is ``where``-discarded, making warm-up exact
    (``P_1 = Q_0``) rather than approximately-zero (a multiplicative
    gate would propagate a hypothetical NaN through ``0 * nan``; the
    ``where`` is the same static-scrub contract MUR803 interval-checks
    on the fault sentinels).

    ``pipe_adj`` is stored **node-leading**: ``[N, N]`` dense, or
    ``[N, k]`` in sparse mode (the transpose of the round input's
    ``[k, N]`` edge mask) so the mesh's leading-axis sharding
    (parallel/mesh._shard_leading_axis) places it on the node axis like
    every other carried row.
    """
    init_flat = np.zeros((num_nodes, model_dim), dtype)
    if sparse_offsets:
        adj0 = np.ones((num_nodes, len(sparse_offsets)), np.float32)
    else:
        adj0 = np.ones((num_nodes, num_nodes), np.float32) - np.eye(
            num_nodes, dtype=np.float32
        )
    state = {
        ADJ_KEY: adj0,
        OWN_KEY: init_flat,
        VALID_KEY: np.zeros((), np.float32),
    }
    if not stale:
        state[BCAST_KEY] = init_flat.copy()
    return state


# ---------------------------------------------------------------------------
# The explicit one-round-delayed averaging reference (tests/test_pipeline.py).
# ---------------------------------------------------------------------------


def run_delayed_reference(
    net,
    rounds: int,
    eval_every: int = 1,
):
    """Drive a SERIALIZED network's round program through the explicit
    one-round-delayed averaging recursion (module docstring) and return
    ``(params, history)`` — the independent implementation the pipelined
    program must match bit-for-bit on CPU.

    ``net`` must be a :class:`~murmura_tpu.core.network.Network` built
    WITHOUT ``exchange.pipeline`` (its ``train_step`` is the serialized
    round, its ``train_flat`` the training-only stage).  The driver runs,
    per round ``r``:

    1. ``own_r  = train_flat(P_r, ...)`` — the trained post-scrub flat
       params (a pure sub-computation of the serialized step);
    2. ``S_r, state' = train_step(P_r, state, ...)`` — the full
       serialized round, whose output IS the guarded aggregation of
       round ``r``'s exchange and whose state update IS the production
       sequence (codec EF, stale cache, rule state);
    3. ``P_{r+1} = own_r + disp``; ``disp`` then advances to
       ``ravel(S_r) - own_r`` for the next round (zero on round 0) —
       with the faulted builds' keep-mask applied exactly as the
       pipelined combine applies it.

    The recursion never touches the pipelined code path: steps 1-2 are
    the pre-existing serialized program, step 3 is four elementwise jnp
    ops — which is what makes a bit-for-bit match meaningful evidence
    that the fused double-buffered program computes one-round-delayed
    averaging and nothing else.
    """
    import jax
    import jax.numpy as jnp

    from murmura_tpu.core.network import record_round_metrics
    from murmura_tpu.ops.flatten import make_flatteners

    prog = net.program
    if prog.pipelined:
        raise ValueError(
            "run_delayed_reference drives the SERIALIZED round program "
            "through the delayed recursion; build the reference network "
            "without exchange.pipeline"
        )
    template = jax.tree_util.tree_map(lambda l: l[0], prog.init_params)
    ravel, unravel, _dim = make_flatteners(template)
    v_ravel = jax.jit(jax.vmap(ravel))
    v_unravel = jax.jit(jax.vmap(unravel))
    step = jax.jit(prog.train_step)
    tflat = jax.jit(prog.train_flat)
    ev = jax.jit(prog.eval_step)

    params = jax.tree_util.tree_map(jnp.asarray, prog.init_params)
    agg_state = {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()}
    d = {k: jnp.asarray(v) for k, v in prog.data_arrays.items()}
    comp = jnp.asarray(net.compromised)
    base_key = jax.random.PRNGKey(net.seed)

    from murmura_tpu.core.network import empty_history

    history = empty_history()
    disp = jnp.zeros_like(v_ravel(params))
    for r in range(rounds):
        key = jax.random.fold_in(base_key, r)
        ridx = jnp.asarray(float(r), jnp.float32)
        adj = jnp.asarray(net._adjacency_for_round(r))
        args = [params, agg_state, key, adj, comp]
        targs = [params, agg_state, key, adj, comp]
        alive = None
        if prog.faulted:
            alive = jnp.asarray(net._alive_for_round(r))
            args.append(alive)
            targs.append(alive)
        own, train_ok = tflat(*targs, ridx, d)
        s_params, agg_state, _m = step(*args, ridx, d)
        new_flat = own + disp
        if alive is not None:
            # nan_quarantine scrubbed own back to the pre-round value
            # and the serialized keep-guard froze those rows; own ==
            # pre_flat there, so the keep-mask reduces to discarding
            # the displacement — exactly the pipelined combine.
            keep = (alive > 0) & (train_ok > 0)
            new_flat = jnp.where(keep[:, None], new_flat, own)
        disp = v_ravel(s_params) - own
        params = v_unravel(new_flat)
        if (r + 1) % eval_every == 0:
            metrics = jax.device_get(ev(params, d))
            record_round_metrics(
                history, r + 1, metrics, net.compromised,
                prog.evidential, net.attack is not None,
            )
    return params, history


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="pipeline",
    module="murmura_tpu.core.pipeline",
    state_keys_group="PIPELINE_STATE_KEYS",
    stage="murmura.pipeline",
    verdicts={
        "adaptive": refuses(
            "exchange.pipeline does not compose with attack.adaptive: "
            "the acceptance feedback would observe round r-1's "
            "aggregation after round r's production already ran, "
            "changing the closed loop's timing semantics — run "
            "adaptive experiments serialized"
        ),
        "compression": composes(),
        "dmtt": refuses(
            "exchange.pipeline does not compose with dmtt (claim "
            "verification gates each round's exchange between "
            "production and aggregation; delaying the aggregation "
            "would verify claims against a different round's graph)"
        ),
        "faults": composes(),
        "mobility": composes(),
    },
)
