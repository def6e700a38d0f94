"""Bounded-staleness gossip: the stale-tolerant exchange layer (ISSUE 13;
docs/ROBUSTNESS.md "Bounded staleness").

Every in-jit backend runs strictly synchronous rounds: a neighbor whose
payload misses the round — a straggler, a crashed node, a dropped link —
is simply masked out of the adjacency, so under churn the effective graph
thins and learning is gated on the slowest healthy path.  The
asynchronous quantized decentralized SGD line (arXiv:1910.12308, whose
quantized half is PR 7's codec) and delayed-averaging schemes
(arXiv:2002.01119) show convergence survives *bounded* delay: a receiver
may aggregate a neighbor's round-``(r - a)`` payload for small ``a``
instead of dropping the edge.

This module implements that as a **payload cache riding the round
program's carried state** under the reserved :data:`STALE_STATE_KEYS`
(the ``COMPRESS_STATE_KEYS`` pattern): because it lives in ``agg_state``,
the fused ``lax.scan`` carry, gang vmap, MUR900 snapshot completeness and
durability resume all cover it with no special cases.

Semantics (the docs/ROBUSTNESS.md table; machine-checked by MUR110x,
analysis/staleness.py):

- ``stale_cache`` [N, P] holds each sender's last broadcast that was
  **delivered** — it cleared the NaN/attack sentinels and reached at
  least one live receiver; ``stale_age`` [N] counts rounds since.
- A sender whose round-``r`` payload is *not* delivered (straggling,
  crashed, isolated by link drops, quarantined, scrubbed) has its
  base-topology in-edges re-added with weight
  ``discount ** age`` for every alive receiver, **provided** the cached
  payload is no older than ``max_staleness`` AND the sender was not
  scrubbed/quarantined *this round* — a caught row must not survive via
  its cached copy (the replay hole adaptive attackers would otherwise
  exploit; MUR1103 taint-kills it).
- Ages past ``max_staleness`` degrade to today's drop-the-edge behavior.

Granularity: the cache is **sender-granular** — one payload version per
sender per round, because every aggregation rule consumes the exchange as
a per-sender ``[N, P]`` tensor (aggregation/base.py) and no rule's math
can rank two versions of the same neighbor in one round.  Delivery is
therefore inferred from the folded adjacency itself (a sender with zero
live out-edges did not deliver), which yields the *relayed-gossip*
reading of per-edge link drops: a link-dropped edge whose sender still
reached some receiver stays dropped for the round (the fresh version did
not cross this edge and the cache may be newer than what this edge last
carried), while a fully-disrupted sender's last delivered payload — which
by construction exists somewhere in the network — is served to every
alive base-graph receiver.  This is exactly the jitted twin of the ZMQ
backend's deadline semantics with a bounded redelivery window: the
straggler schedule becomes a *delay* model (the payload lands next round
at age 1) instead of a pure drop.

Discount weighting: mean-family rules (fedavg, BALANCE/UBAR blends,
evidential trust) honor the fractional re-added weight directly;
selection rules (krum, median, trimmed mean) treat any positive weight as
a full candidate — a candidate cannot be 0.8-selected — so for them
``staleness_discount`` only controls nothing vs something.

Pipeline buffer reuse (ISSUE 14; core/pipeline.py): the cache-advance
invariant below — after the fold, ``stale_cache`` holds EXACTLY the
post-fold broadcast receivers aggregated this round — is what lets
pipelined rounds (``exchange.pipeline``) use this cache as their
broadcast buffer: round r+1's delayed aggregation reads the cache
before round r+1's fold advances it, getting round r's served payload
byte-for-byte, so a staleness-composed pipelined build carries no
duplicate ``pipe_bcast`` tensor (core/pipeline.pipeline_state_keys).
"""

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

# Reserved round-program-level agg_state keys (the DMTT_STATE_KEYS /
# COMPRESS_STATE_KEYS pattern, core/rounds.py): carried by the round step
# but never handed to the aggregation rule's state dict, and registered
# in durability/snapshot.RESERVED_AGG_STATE_KEY_GROUPS so the MUR900
# snapshot-completeness bijection — and therefore SIGKILL/--resume with a
# populated cache — covers them for free (MUR1100, analysis/staleness.py).
CACHE_KEY = "stale_cache"
AGE_KEY = "stale_age"
STALE_STATE_KEYS = (AGE_KEY, CACHE_KEY)


@dataclass(frozen=True)
class StalenessSpec:
    """Trace-time bounded-staleness spec (config: ``exchange:``).

    Static under trace — the staleness bound, discount and the base
    exchange graph are program structure; everything data-dependent (the
    cache, ages, which edges are stale this round) is traced values, so
    rounds never recompile across staleness variation (MUR1101).

    ``base_mask`` is the UNFAULTED exchange graph the re-added edges are
    drawn from: the static ``[N, N]`` topology mask (dense mode, zero
    diagonal) or the static all-active ``[k, N]`` edge mask (sparse
    exponential mode).  Staleness therefore requires a static topology —
    mobility's per-round G^t and one_peer's round-varying mask have no
    trace-time base graph (config/schema.py rejects them loudly).
    """

    max_staleness: int
    discount: float = 1.0
    base_mask: Optional[np.ndarray] = field(default=None, repr=False)

    def __post_init__(self):
        if self.max_staleness < 1:
            raise ValueError(
                f"max_staleness must be >= 1 to arm the stale exchange "
                f"(0 disables it at the config layer), got "
                f"{self.max_staleness}"
            )
        if not 0.0 < self.discount <= 1.0:
            raise ValueError(
                f"staleness_discount must be in (0, 1], got {self.discount}"
            )

    @property
    def age_cap(self) -> float:
        """Saturation value for the age counter: one past the bound is
        already "expired", so ages stay small exact integers in float32
        regardless of run length."""
        return float(self.max_staleness + 1)


def init_stale_state(
    spec: Optional[StalenessSpec], num_nodes: int, model_dim: int, dtype
) -> Dict[str, np.ndarray]:
    """Initial ``agg_state`` entries for a stale-enabled program.

    The cache starts empty (zeros) with every age at the expired sentinel
    ``max_staleness + 1``: an edge disrupted before its sender ever
    delivered degrades to the drop-the-edge behavior — round 0 has no
    payload to replay.
    """
    if spec is None:
        return {}
    return {
        CACHE_KEY: np.zeros((num_nodes, model_dim), dtype),
        AGE_KEY: np.full((num_nodes,), spec.age_cap, np.float32),
    }


def make_stale_fold(
    spec: StalenessSpec,
    sparse_offsets: Tuple[int, ...] = (),
    audit: bool = False,
):
    """Build the traced staleness fold for one round program.

    ``audit`` (telemetry.audit_taps — a trace-time constant, like the
    rules' ``ctx.audit``) additionally emits the per-node
    ``tap_stale_used`` / ``tap_stale_age`` stats.

    Returns ``fold(bcast, adj, state, recv_ok, scrub_ok) ->
    (bcast_eff, adj_eff, state_updates, stats)`` where:

    - ``bcast`` is the round's exchanged [N, P] tensor (post-attack,
      post-sentinel, post-codec-decode — finite by construction);
    - ``adj`` is the fully-folded adjacency ([N, N], or the [k, N] edge
      mask in sparse mode) with every fault already applied;
    - ``state`` holds the :data:`STALE_STATE_KEYS` entries;
    - ``recv_ok`` is the [N] RECEIVER eligibility mask — re-added edges
      must mirror the fresh folds' receiver side, so dead AND
      quarantined receivers (whose fresh edges were zeroed both ways)
      get no stale in-edges;
    - ``scrub_ok`` is the [N] product of this round's SENDER sentinel
      verdicts (1 = clean; 0 = quarantined or attack-scrubbed) — the
      gate that taint-kills a caught row's cached copy (MUR1103).

    All decisions are per-round *values* over [N]/[k, N] tensors: dense
    mode adds only elementwise math and one adjacency column sum; sparse
    mode only rolls of [N] rows (boundary ppermutes on a sharded node
    axis) — the stale program's traced collective inventory equals the
    drop-sync faulted program's (MUR1102).
    """
    sparse_offsets = tuple(int(o) for o in sparse_offsets)
    sparse = bool(sparse_offsets)
    base = np.asarray(spec.base_mask, dtype=np.float32)
    if sparse:
        if base.ndim != 2 or base.shape[0] != len(sparse_offsets):
            raise ValueError(
                f"sparse staleness base mask must be [k, N] with k = "
                f"{len(sparse_offsets)} offsets, got {base.shape}"
            )
    else:
        if base.ndim != 2 or base.shape[0] != base.shape[1]:
            raise ValueError(
                f"dense staleness base mask must be square [N, N], got "
                f"{base.shape}"
            )
        if np.diagonal(base).any():
            raise ValueError(
                "dense staleness base mask must have a zero diagonal "
                "(MUR301: re-added edges must never include self-loops)"
            )
    base_c = jnp.asarray(base)
    max_staleness = float(spec.max_staleness)
    age_cap = spec.age_cap
    discount = float(spec.discount)
    log_discount = float(np.log(discount)) if discount < 1.0 else 0.0

    def _sender_view(vec):  # murmura: traced
        """[k, N] sender-side view of a [N] node flag (the rounds.py
        helper): row j holds vec[(i + offsets[j]) % N] at column i."""
        return jnp.stack([jnp.roll(vec, -o) for o in sparse_offsets])

    def _sender_out_degree(adj):  # murmura: traced
        """[N] live out-edge count per SENDER under the folded adjacency:
        dense column sums, or rolls of the [k, N] edge rows back onto the
        sender index (aggregation/base.circulant_in_degree's construction
        — ppermute-only on a sharded node axis)."""
        if sparse:
            return sum(
                jnp.roll(adj[j].astype(jnp.float32), o)
                for j, o in enumerate(sparse_offsets)
            )
        return adj.sum(axis=0)

    def fold(bcast, adj, state, recv_ok, scrub_ok):  # murmura: traced
        # Static shape guard (trace-time, zero runtime cost): the base
        # mask's N axis must match this program's node axis — a [k, 1]
        # or wrong-N mask would silently BROADCAST against the [N] node
        # flags below and re-add edges of a different graph.
        n = recv_ok.shape[0]
        if base_c.shape[-1] != n:
            raise ValueError(
                f"staleness base mask covers {base_c.shape[-1]} nodes "
                f"but this program's node axis is {n}"
            )
        cache = state[CACHE_KEY]
        age = state[AGE_KEY].astype(jnp.float32)

        # Delivery inference: a sender with at least one live out-edge
        # put its payload in the network this round (the relay reading —
        # module docstring); zero live out-edges means straggle, death,
        # quarantine, scrub, or total link isolation, all of which the
        # preceding folds expressed as a zeroed column.
        deliver = (_sender_out_degree(adj) > 0).astype(jnp.float32)
        age_new = jnp.where(
            deliver > 0, 0.0, jnp.minimum(age + 1.0, age_cap)
        )
        # Usable = stale (not delivering) AND within the bound AND not
        # caught by a sentinel this round.  The scrub gate is the replay
        # hole's plug: a quarantined/scrubbed row's CACHED copy is
        # withheld for the round exactly like its fresh one (MUR1103
        # taint-kills the path).
        usable = (
            (1.0 - deliver)
            * scrub_ok
            * (age_new <= max_staleness).astype(jnp.float32)
        )
        if discount < 1.0:
            w_sender = usable * jnp.exp(age_new * log_discount)
        else:
            w_sender = usable

        # Re-added edges: base-graph in-edges of stale senders, gated by
        # receiver liveness.  Columns of delivering senders carry
        # w_sender = 0, so the sum never double-counts a live edge and a
        # link-dropped edge of a delivering sender stays dropped.
        if sparse:
            readd = base_c * recv_ok[None, :] * _sender_view(w_sender)
        else:
            readd = base_c * recv_ok[:, None] * w_sender[None, :]
        adj_eff = adj + readd

        # One payload version per sender: fresh rows pass through, stale
        # rows substitute the cached copy.  The cache then advances to
        # exactly what receivers could aggregate this round, so the
        # served representation and the stored one never diverge — the
        # invariant the pipelined rounds' buffer reuse relies on (module
        # docstring; core/pipeline.py reads this cache as pipe_bcast).
        fresh = deliver[:, None] > 0
        bcast_eff = jnp.where(fresh, bcast, cache.astype(bcast.dtype))
        updates = {
            CACHE_KEY: bcast_eff.astype(cache.dtype),
            AGE_KEY: age_new,
        }

        used = (readd > 0).astype(jnp.float32)
        # "Expired" counts AGE expiry only: the cached payload is older
        # than the bound (a round-0 cold cache reads as infinitely old,
        # which is the same operator fact).  Scrub-withheld senders are
        # NOT expired — their cache is fresh enough, just quarantined
        # for the round — and counting them here would over-report
        # cache expiry under attack (agg_stale_expired is read as the
        # age signal).
        expired = (
            (1.0 - deliver)
            * scrub_ok
            * (age_new > max_staleness).astype(jnp.float32)
        )
        if sparse:
            used_in = used.sum(axis=0)  # per-receiver stale in-edges
            expired_edges = (
                base_c * recv_ok[None, :] * _sender_view(expired)
            )
        else:
            used_in = used.sum(axis=1)
            expired_edges = base_c * recv_ok[:, None] * expired[None, :]
        stats = {
            "stale_used": used.sum(),
            "stale_expired": (expired_edges > 0).astype(jnp.float32).sum(),
        }
        if audit:
            # Per-node taps (telemetry.audit_taps): WHICH receivers
            # aggregated stale rows and HOW old each served sender's
            # payload was — elementwise over node-local rows plus the
            # same column-sum/roll shapes as the delivery inference, so
            # no collectives are added (MUR400/MUR1102).  The age tap is
            # gated on the sender actually having a re-added edge: a
            # usable cache nobody was eligible to receive (every
            # base-graph receiver dead/quarantined) was NOT served, and
            # the report's histogram documents 0 = fresh or unserved.
            served = (_sender_out_degree(used) > 0).astype(jnp.float32)
            stats["tap_stale_used"] = used_in
            stats["tap_stale_age"] = age_new * usable * served
        return bcast_eff, adj_eff, updates, stats

    return fold


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="staleness",
    module="murmura_tpu.core.stale",
    state_keys_group="STALE_STATE_KEYS",
    stage="murmura.stale",
    verdicts={
        "adaptive": composes(),
        "compression": composes(),
        "dmtt": refuses(
            "bounded staleness does not compose with dmtt (the "
            "exchange graph is trust-gated per round; a cached row "
            "would bypass the round's claim verification)"
        ),
        # Staleness is DEFINED over the fault model: without it the
        # cache is dead state, so the dependency is a constraint tag.
        "faults": composes(
            requires_faults=(
                "exchange.max_staleness requires faults.enabled: true "
                "— without the fault model nothing ever misses a "
                "round, so the stale cache would be dead state in "
                "every program"
            ),
        ),
        "mobility": refuses(
            "bounded staleness does not compose with mobility: an "
            "edge leaving G^t is topology change, not a fault, and "
            "the re-add layer needs a static base graph baked at "
            "trace time"
        ),
        "pipeline": composes(),
        "population": refuses(
            "bounded staleness does not compose with population "
            "(the payload cache is per-slot [N, P] carried state; "
            "cohort swaps reassign node slots, so a cached row would "
            "be served into the wrong user's stream — the "
            "compression carried-state rationale)"
        ),
        "sharding": composes(),
        "sparse": composes(
            one_peer=(
                "bounded staleness does not compose with the one_peer "
                "topology (its active offset varies per round as mask "
                "values, so there is no static base edge mask to "
                "re-add from); use the exponential sparse family or a "
                "dense topology"
            ),
        ),
    },
)
