"""Bounded-staleness contracts (MUR1100-1103) — part of the default
package check (docs/ROBUSTNESS.md "Bounded staleness").

The stale exchange layer (core/stale.py) threads a payload cache through
the compiled round program: folded adjacency -> delivery inference ->
cache/age update -> re-added discounted edges -> rule math.  Each link
carries an invariant that must stay machine-checked or the robustness
story silently rots:

- **MUR1100 — stale-state registry bijection.**  ``STALE_STATE_KEYS``
  must be registered in the MUR900 snapshot registry under its defining
  module, its keys distinct and ``stale_``-prefixed, and
  ``init_stale_state`` must emit exactly those keys with the [N, P]
  cache / [N] float32 age shapes the scan carry, gang vmap and
  durability snapshot rely on.
- **MUR1101 — recompile-free staleness.**  The cache, ages and the
  per-round stale/fresh split are carried state and input values; a
  stale-enabled round program compiles once and every staleness
  variation — churn filling and draining the cache round to round — is
  value-only (:class:`~murmura_tpu.analysis.sanitizers.CompileTracker`).
  The probe also requires the cache to actually serve edges, so a
  silently-dead stale layer cannot pass vacuously.
- **MUR1102 — collective-inventory parity.**  The stale fold is
  elementwise math plus adjacency column sums (dense) or rolls of [N]
  rows (sparse); the stale round program's traced collective inventory
  must equal the drop-sync faulted program's, per rule x dense/sparse —
  tolerating staleness must not add communication.
- **MUR1103 — staleness influence bounds + the replay hole.**  Run the
  taint interpreter (analysis/flow.py) over the composed stale-fold +
  aggregation step with broadcast AND cache rows label-seeded: bounded
  rules (krum/median/trimmed/ubar) must keep their declared MUR800
  per-coordinate influence cardinality when stale rows enter rule math
  (a cached row is still ONE neighbor), a scrubbed sender's current
  broadcast must never reach the cache, and a scrubbed/expired sender's
  CACHED copy must never reach the aggregated output — the replay hole
  an adaptive attacker (alternating loud rounds with quiet cache
  replays) would otherwise exploit.

Like ``check_adaptive``, MUR1101 compiles and runs tiny programs, so the
family is memoized per process and runs by default only for the package
check; tests gate representative cells per tier-1 run
(tests/test_staleness.py) and negatives prove each probe can fire.
"""

from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/durability.py/adaptive.py twin pattern).
STALE_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    STALE_CHECK_FAMILIES[fn.__name__] = fn
    return fn


_PKG = Path(__file__).resolve().parent.parent
_STALE_PATH = str(_PKG / "core" / "stale.py")

# The trace-level collective vocabulary — IMPORTED from the MUR1002
# check so the two parity checks cannot drift on what counts as
# communication.
from murmura_tpu.analysis.adaptive import _COLLECTIVE_PRIMS  # noqa: E402

# The exchange layouts the staleness grids sweep: the dense [N, N]
# adjacency fold and the sparse [k, N] edge-mask fold (one_peer has no
# static base mask and mobility no static graph — both are rejected at
# schema validation, so there is nothing to sweep there).
STALE_MODES: Tuple[str, ...] = ("dense", "sparse")


def _rule_anchor(rule: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor as anchor

    return anchor(rule)


# --------------------------------------------------------------------------
# MUR1100 — stale-state registry bijection
# --------------------------------------------------------------------------


@_family
def check_stale_state_registry() -> List[Finding]:
    """MUR1100: STALE_STATE_KEYS <-> init_stale_state <-> MUR900 snapshot
    registry, all bijective and shape-sound."""
    findings: List[Finding] = []
    try:
        from murmura_tpu.core.stale import (
            STALE_STATE_KEYS,
            StalenessSpec,
            init_stale_state,
        )
        from murmura_tpu.durability.snapshot import (
            RESERVED_AGG_STATE_KEY_GROUPS,
        )
    except Exception as e:  # noqa: BLE001 — the import failure IS the finding
        return [Finding(
            "MUR1100", _STALE_PATH, 1,
            f"the staleness module failed to import "
            f"({type(e).__name__}: {e}) — the MUR1100 bijection cannot "
            "be checked",
        )]

    keys = tuple(STALE_STATE_KEYS)
    if len(set(keys)) != len(keys) or any(
        not k.startswith("stale_") for k in keys
    ):
        findings.append(Finding(
            "MUR1100", _STALE_PATH, 1,
            f"STALE_STATE_KEYS must be distinct 'stale_'-prefixed "
            f"agg_state keys, got {keys} — the prefix is how telemetry "
            "and report consumers recognize staleness state",
        ))
    reg = RESERVED_AGG_STATE_KEY_GROUPS.get("STALE_STATE_KEYS")
    if reg != "murmura_tpu.core.stale":
        findings.append(Finding(
            "MUR1100", _STALE_PATH, 1,
            "STALE_STATE_KEYS is not registered in durability.snapshot."
            f"RESERVED_AGG_STATE_KEY_GROUPS under its defining module "
            f"(got {reg!r}) — the payload cache would be invisible to "
            "the MUR900 snapshot-completeness contract and a SIGKILL "
            "mid-round would silently resume with a cold cache",
        ))
    try:
        spec = StalenessSpec(max_staleness=2, discount=0.5)
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR1100", _STALE_PATH, 1,
            f"StalenessSpec(2, 0.5) crashed: {type(e).__name__}: {e}",
        ))
        return findings
    for n, p in ((4, 7), (9, 3)):
        init = init_stale_state(spec, n, p, np.float32)
        if set(init) != set(keys):
            findings.append(Finding(
                "MUR1100", _STALE_PATH, 1,
                f"init_stale_state keys {sorted(init)} != "
                f"STALE_STATE_KEYS {sorted(keys)} — the round program "
                "seeds agg_state from the reservation",
            ))
            continue
        cache = np.asarray(init["stale_cache"])
        age = np.asarray(init["stale_age"])
        if cache.shape != (n, p):
            findings.append(Finding(
                "MUR1100", _STALE_PATH, 1,
                f"init stale_cache is shape {cache.shape}, not "
                f"({n}, {p}) — the cache must mirror the exchanged "
                "[N, P] tensor so donation aliases and gang vmap hold",
            ))
        if age.shape != (n,) or age.dtype != np.float32:
            findings.append(Finding(
                "MUR1100", _STALE_PATH, 1,
                f"init stale_age is {age.dtype}{age.shape}, not float32 "
                f"({n},) — ages are per-sender [N] float32 rows",
            ))
        elif not (age > spec.max_staleness).all():
            findings.append(Finding(
                "MUR1100", _STALE_PATH, 1,
                "init stale_age starts within the staleness bound — a "
                "round-0 disruption would serve the all-zeros cache as "
                "a real payload instead of degrading to drop-the-edge",
            ))
    for bad in ({"max_staleness": 0}, {"max_staleness": 2, "discount": 0.0}):
        try:
            StalenessSpec(**bad)
        except ValueError:
            pass
        else:
            findings.append(Finding(
                "MUR1100", _STALE_PATH, 1,
                f"StalenessSpec accepted invalid parameters {bad} — the "
                "spec must refuse configurations the schema layer "
                "already rejects, so direct library use cannot build a "
                "silently-dead stale layer",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1101 — recompile-free staleness (executable)
# --------------------------------------------------------------------------


def _cell_config(rule: str, mode: str, max_staleness: int = 2):
    """One (rule, mode) staleness cell's tiny-but-real config — the
    durability grid's cell (analysis/durability.py) plus the fault
    schedule and the exchange block, so the executable grids stay one
    inventory."""
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.config import Config

    raw: Dict[str, Any] = {
        "experiment": {"name": f"stale-{rule}-{mode}", "seed": 7,
                       "rounds": 5},
        "topology": {"type": "ring", "num_nodes": 5},
        "aggregation": {"algorithm": rule,
                        "params": dict(AGG_CASES.get(rule, {}))},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 40, "input_shape": [6],
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 6, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "simulation",
        "faults": {"enabled": True, "straggler_prob": 0.4,
                   "link_drop_prob": 0.2, "seed": 11},
        "exchange": {"max_staleness": max_staleness,
                     "staleness_discount": 0.5},
    }
    if mode == "sparse":
        raw["topology"] = {"type": "exponential", "num_nodes": 8}
    elif mode != "dense":
        raise ValueError(f"unknown staleness mode {mode!r}")
    return Config.model_validate(raw)


def recompile_cell_findings(rule: str, mode: str = "dense") -> List[Finding]:
    """Run ONE (rule, mode) MUR1101 cell: 2 warmup rounds (the compile),
    then 3 more under CompileTracker — churn fills and drains the cache,
    ages walk their whole range, and none of it may recompile.  The cell
    must also actually serve stale edges (``agg_stale_used`` > 0), so a
    dead stale layer cannot pass vacuously.  Exposed per-cell so tests
    gate a subset (tests/test_staleness.py)."""
    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.utils.factories import build_network_from_config

    path, line = _rule_anchor(rule)
    net = build_network_from_config(_cell_config(rule, mode))
    net.train(rounds=2, verbose=False)
    with track_compiles() as tracker:
        net.train(rounds=3, verbose=False)
    findings: List[Finding] = []
    if tracker.total:
        findings.append(Finding(
            "MUR1101", path, line,
            f"[{rule}/{mode}] 3 stale-enabled rounds after warmup "
            f"compiled {tracker.total} program(s) — the cache and ages "
            "are carried state and the fault masks input values, so "
            "staleness variation must be value-only over one compiled "
            "round program",
        ))
    used = net.history.get("agg_stale_used") or []
    if not any(u > 0 for u in used):
        findings.append(Finding(
            "MUR1101", path, line,
            f"[{rule}/{mode}] a 40% straggler / 20% link-drop schedule "
            "served zero stale edges across 5 rounds — the recompile "
            "check is vacuous (the stale fold is not actually wired "
            "into this rule's round program; check core/rounds.py)",
        ))
    return findings


@_family
def check_stale_recompile() -> List[Finding]:
    """MUR1101 over ``AGGREGATORS x STALE_MODES`` (compiles and runs tiny
    programs — the check_durability cost profile)."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for mode in STALE_MODES:
            try:
                findings.extend(recompile_cell_findings(rule, mode))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1101", path, line,
                    f"[{rule}/{mode}] stale recompile probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1102 — collective-inventory parity (trace-level, per rule x mode)
# --------------------------------------------------------------------------


def _build_stale_programs(rule: str, mode: str):
    """(drop-sync program, stale program) for one (rule, mode) cell —
    identical in every respect except the staleness spec."""
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES, canonical_offsets
    from murmura_tpu.attacks.gaussian import make_gaussian_attack
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.core.stale import StalenessSpec
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.faults.schedule import FaultSpec
    from murmura_tpu.models import make_mlp

    n, s = 8, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n, s, 6)).astype(np.float32),
        y=rng.integers(0, 3, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=3,
    )
    model = make_mlp(
        input_dim=6, hidden_dims=(8,), num_classes=3,
        evidential=(rule == "evidential_trust"),
    )
    flat0, _ = ravel_pytree(model.init(jax.random.PRNGKey(0)))
    case = dict(AGG_CASES.get(rule, {}))
    offsets = tuple(canonical_offsets(n))
    if mode == "sparse":
        case["exchange_offsets"] = list(offsets)
        case["sparse_exchange"] = True
        sparse_offsets: Optional[Tuple[int, ...]] = offsets
        base = np.ones((len(offsets), n), np.float32)
    else:
        from murmura_tpu.analysis.ir import _canonical_adj

        sparse_offsets = None
        base = np.asarray(_canonical_adj(n, circulant=True), np.float32)
    agg = build_aggregator(
        rule, case, model_dim=int(flat0.size), total_rounds=4
    )
    attack = make_gaussian_attack(
        n, attack_percentage=0.3, noise_std=5.0, seed=7
    )
    common = dict(
        local_epochs=1, batch_size=8, lr=0.05, total_rounds=4, seed=7,
        attack=attack, faults=FaultSpec(), sparse_offsets=sparse_offsets,
    )
    plain = build_round_program(model, agg, data, **common)
    stale = build_round_program(
        model, agg, data,
        staleness=StalenessSpec(
            max_staleness=2, discount=0.5, base_mask=base
        ),
        **common,
    )
    return plain, stale


def _trace_collectives(prog) -> frozenset:
    """Collective primitive names in a FAULTED round program's traced
    jaxpr (the program takes the extra [N] alive input)."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.ir import iter_eqns

    n = prog.num_nodes
    if prog.sparse:
        adj = jnp.ones((len(prog.sparse_offsets), n), jnp.float32)
    else:
        adj = jnp.asarray(
            np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
        )
    closed = jax.make_jaxpr(prog.train_step)(
        prog.init_params,
        {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
        jax.random.PRNGKey(0),
        adj,
        jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32),
        jnp.asarray(0.0, jnp.float32),
        {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
    )
    return frozenset(
        e.primitive.name for e in iter_eqns(closed)
        if e.primitive.name in _COLLECTIVE_PRIMS
    )


def collective_cell_findings(rule: str, mode: str) -> List[Finding]:
    """One (rule, mode) MUR1102 cell: the stale round program's traced
    collective inventory vs the drop-sync faulted program's — tolerating
    staleness must not add communication."""
    path, line = _rule_anchor(rule)
    plain, stale = _build_stale_programs(rule, mode)
    stray = _trace_collectives(stale) - _trace_collectives(plain)
    if stray:
        return [Finding(
            "MUR1102", path, line,
            f"[{rule}/{mode}] the stale round program traces "
            f"collective(s) {sorted(stray)} absent from the drop-sync "
            "faulted program — the stale fold must stay elementwise "
            "math, adjacency column sums, and rolls of [N] rows",
        )]
    return []


@_family
def check_stale_collectives() -> List[Finding]:
    """MUR1102 over ``AGGREGATORS x STALE_MODES`` (trace-only: nothing
    compiles)."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for mode in STALE_MODES:
            try:
                findings.extend(collective_cell_findings(rule, mode))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1102", path, line,
                    f"[{rule}/{mode}] stale collective-inventory probe "
                    f"crashed: {type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1103 — staleness influence bounds + the replay hole (trace-only)
# --------------------------------------------------------------------------

# The probe's cast of senders over the canonical flow cell's graph:
# one usable stale sender (column down, age within bound, clean), one
# scrubbed sender (column down, age within bound, sentinel-caught this
# round), one expired sender (column down, age past the bound).
_STALE_SENDER = 1
_SCRUBBED_SENDER = 2
_EXPIRED_SENDER = 3

# Rules exempt from the probe-C replay-hole taint check, with the reason.
# geometric_median's dense path computes its Weiszfeld distances through
# ``pairwise_l2_distances``, which centers every row on the mean of the
# WHOLE broadcast tensor before the Gram identity — the centering cancels
# exactly in every distance (the dark rows mathematically cannot move the
# result, and their cached values are finite by construction, so no
# 0*inf hazard either), but a value-dataflow taint cannot see the
# cancellation, so every label reaches every weight.  This is the same
# documented analysis limitation that exempts unbounded rules from the
# MUR802 cross-mode parity (analysis/flow.py).  The probe-B cache-write
# contract still applies to these rules in full.
_REPLAY_TAINT_EXEMPT: Dict[str, str] = {
    "geometric_median": "Weiszfeld distances run through the dense "
    "Gram centering mean, which couples all rows in value dataflow "
    "while cancelling exactly in every distance",
}


def _stale_cell(rule: str, fold_factory=None):
    """The composed stale-fold + aggregation step over the canonical
    dense flow cell, plus the concrete seed values the probes share.
    ``fold_factory`` overrides :func:`murmura_tpu.core.stale.
    make_stale_fold` so negative tests can drive the probes with a
    broken fold (tests/test_staleness.py)."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.flow import _quiet_tracing, build_flow_cell
    from murmura_tpu.core.stale import (
        AGE_KEY,
        CACHE_KEY,
        StalenessSpec,
        make_stale_fold,
    )

    cell = build_flow_cell(rule, "dense")
    n = cell.n
    own, bcast, adj0 = cell.args[0], cell.args[1], cell.args[2]
    base = np.asarray(adj0, np.float32)
    spec = StalenessSpec(max_staleness=2, discount=0.5, base_mask=base)
    fold = (fold_factory or make_stale_fold)(spec)

    # Fault the adjacency: the three probe senders' columns go dark.
    adj_f = base.copy()
    for s in (_STALE_SENDER, _SCRUBBED_SENDER, _EXPIRED_SENDER):
        adj_f[:, s] = 0.0
    scrub_np = np.ones((n,), np.float32)
    scrub_np[_SCRUBBED_SENDER] = 0.0
    age_np = np.zeros((n,), np.float32)
    age_np[_EXPIRED_SENDER] = spec.age_cap  # saturated: long-dark sender
    rng = np.random.default_rng(1)
    cache_np = np.asarray(rng.normal(size=bcast.shape) * 0.1, np.float32)
    alive = jnp.ones((n,), jnp.float32)
    scrub_ok = jnp.asarray(scrub_np)

    cell_fn = cell.fn
    rest = tuple(cell.args[3:])

    def fn(own_a, bcast_a, adj_a, cache_a, age_a, *rest_a):  # murmura: traced
        bcast_eff, adj_eff, updates, _stats = fold(
            bcast_a, adj_a,
            {CACHE_KEY: cache_a, AGE_KEY: age_a},
            alive, scrub_ok,
        )
        new_flat, _state, _stats2 = cell_fn(
            own_a, bcast_eff, adj_eff, *rest_a
        )
        return new_flat, updates[CACHE_KEY]

    args = (
        own, bcast, jnp.asarray(adj_f),
        jnp.asarray(cache_np), jnp.asarray(age_np),
    ) + rest
    with _quiet_tracing():
        closed = jax.make_jaxpr(fn)(*args)
    return cell, closed, args, adj_f, base


def _taint_run(closed, args, n, seed_bcast: bool, seed_cache: bool):
    """Evaluate the composed step with row labels on the broadcast and/or
    cache leaves; returns (out_taint [L, N, P], cache_taint [L, N, P])."""
    import jax

    from murmura_tpu.analysis.flow import TaintEval, _quiet_tracing, _tz

    flat_args, _ = jax.tree_util.tree_flatten(args)
    arg_leaf_pos: List[int] = []
    for i, a in enumerate(args):
        arg_leaf_pos.extend([i] * len(jax.tree_util.tree_leaves(a)))
    pairs = []
    for leaf, pos in zip(flat_args, arg_leaf_pos):
        v = np.asarray(leaf)
        t = _tz(n, v.shape)
        if (pos == 1 and seed_bcast) or (pos == 3 and seed_cache):
            for lbl in range(n):
                t[lbl, lbl] = True
        pairs.append((v, t))
    ev = TaintEval(n)
    with _quiet_tracing():
        outs = ev.eval_closed(closed, pairs)
    return outs[0][1], outs[1][1]


def stale_influence_findings(rule: str, fold_factory=None) -> List[Finding]:
    """One rule's MUR1103 probes over the composed stale+aggregate step.

    Probe A (bcast + cache seeded): bounded rules keep their declared
    per-coordinate influence cardinality with a stale row in rule math.
    Probe B (bcast seeded): the scrubbed sender's current broadcast never
    reaches the cache; every delivering sender's does.
    Probe C (cache seeded): the scrubbed and expired senders' cached
    copies never reach the aggregated output — the replay hole.
    """
    path, line = _rule_anchor(rule)
    cell, closed, args, adj_f, base = _stale_cell(rule, fold_factory)
    n = cell.n
    findings: List[Finding] = []

    # -- Probe A: influence cardinality with stale rows in rule math ----
    out_t, _cache_t = _taint_run(
        closed, args, n, seed_bcast=True, seed_cache=True
    )
    influence = cell.agg.influence
    if influence is not None and influence.kind == "bounded":
        # Per-RECEIVER comparison: the effective graph is ragged (live
        # edges plus the one usable re-added stale edge; the scrubbed
        # and expired senders stay dark), and bounds like the median's
        # depend on stack parity — bound(k) is not monotone in k, so a
        # single worst-case degree would miss (or fabricate) violations.
        eff = adj_f > 0
        eff[:, _STALE_SENDER] |= base[:, _STALE_SENDER] > 0
        per_coord = out_t.sum(axis=0)  # [N, P] distinct-label counts
        self_t = out_t[np.arange(n), np.arange(n)]  # [N, P]
        card_i = (per_coord - self_t).max(axis=1)  # [N]
        for i in range(n):
            bound = influence.bound(int(eff[i].sum()))
            if int(card_i[i]) > bound:
                findings.append(Finding(
                    "MUR1103", path, line,
                    f"[{rule}] the composed stale+aggregate step mixes "
                    f"{int(card_i[i])} neighbors into receiver {i}'s "
                    f"output coordinate but the rule declares a bound "
                    f"of {bound} at its effective degree "
                    f"{int(eff[i].sum())} — stale rows entering rule "
                    "math widened the rule's per-coordinate influence",
                ))

    # -- Probe B: a scrubbed row must never enter the cache -------------
    _out_b, cache_t = _taint_run(
        closed, args, n, seed_bcast=True, seed_cache=False
    )
    s = _SCRUBBED_SENDER
    if cache_t[s].any():
        findings.append(Finding(
            "MUR1103", path, line,
            f"[{rule}] the scrubbed sender {s}'s current broadcast "
            "taints the updated stale cache — a sentinel-caught row "
            "must never be stored for replay",
        ))
    fresh = [
        j for j in range(n)
        if j not in (_STALE_SENDER, _SCRUBBED_SENDER, _EXPIRED_SENDER)
    ]
    if fresh and not cache_t[fresh[0], fresh[0]].any():
        findings.append(Finding(
            "MUR1103", path, line,
            f"[{rule}] delivering sender {fresh[0]}'s broadcast does "
            "not reach its own cache row — the cache update is not "
            "wired and the replay-hole probes are vacuous",
        ))

    # -- Probe C: scrubbed/expired CACHED copies must not be served -----
    if rule in _REPLAY_TAINT_EXEMPT:
        return findings
    out_c, _ = _taint_run(closed, args, n, seed_bcast=False, seed_cache=True)
    for bad, why in (
        (_SCRUBBED_SENDER, "was scrubbed/quarantined this round"),
        (_EXPIRED_SENDER, "aged past max_staleness"),
    ):
        if out_c[bad].any():
            findings.append(Finding(
                "MUR1103", path, line,
                f"[{rule}] sender {bad}'s CACHED payload taints the "
                f"aggregated output although it {why} — the replay "
                "hole: a caught or expired row survives via its cached "
                "copy",
            ))
    return findings


@_family
def check_stale_influence() -> List[Finding]:
    """MUR1103 over every registered rule (trace-only), plus the
    non-vacuity guard: on fedavg — declared-unbounded, every neighbor
    admitted — the usable stale sender's cached row MUST reach some
    honest receiver's output, proving the probes exercise a live stale
    path rather than an edgeless one."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        try:
            findings.extend(stale_influence_findings(rule))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1103", path, line,
                f"[{rule}] stale influence probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    try:
        cell, closed, args, adj_f, base = _stale_cell("fedavg")
        out_c, _ = _taint_run(
            closed, args, cell.n, seed_bcast=False, seed_cache=True
        )
        receivers = np.nonzero(base[:, _STALE_SENDER] > 0)[0]
        served = any(
            out_c[_STALE_SENDER, r].any() for r in receivers
        )
        if not served:
            path, line = _rule_anchor("fedavg")
            findings.append(Finding(
                "MUR1103", path, line,
                "[fedavg] the usable stale sender's cached payload "
                "reaches NO base-graph receiver — the stale path is "
                "dead and every MUR1103 containment verdict above is "
                "vacuous",
            ))
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR1103", _STALE_PATH, 1,
            f"the MUR1103 non-vacuity guard crashed: "
            f"{type(e).__name__}: {e}",
        ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_STALE_MEMO: Optional[List[Finding]] = None


def check_staleness(force: bool = False) -> List[Finding]:
    """Run MUR1100-1103; returns findings (empty = every bounded-
    staleness contract holds).  Memoized per process — the CLI and the
    slow test gate share one sweep.  MUR1101
    compiles and runs tiny programs (the check_durability cost profile),
    which is why the family runs only for the package-level check."""
    global _STALE_MEMO
    if _STALE_MEMO is not None and not force:
        return list(_STALE_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in STALE_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1100", str(Path(__file__).resolve()), 1,
                f"staleness check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _STALE_MEMO = list(findings)
    return findings
