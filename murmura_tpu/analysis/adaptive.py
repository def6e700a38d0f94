"""Adaptive-adversary contracts (MUR1000-1003) — part of the default
package check (docs/ROBUSTNESS.md "Adaptive adversaries & the frontier").

The closed-loop attacks (attacks/adaptive.py) thread a feedback path
through the compiled round program: acceptance taps -> adaptation state
(``ATTACK_STATE_KEYS`` in ``agg_state``) -> next round's broadcast.  Each
link carries an invariant that must stay machine-checked or the frontier's
claims (docs/ROBUSTNESS.md) silently rot:

- **MUR1000 — attack-state registry bijection.**  Every adaptive attack's
  carried state keys must be drawn from — and jointly cover —
  :data:`~murmura_tpu.attacks.adaptive.ATTACK_STATE_KEYS`, every factory
  must populate the full adaptation interface with ``[N] float32`` rows,
  and the tuple itself must be registered in the MUR900 snapshot registry
  (durability/snapshot.py) so SIGKILL/``--resume`` carries a
  mid-bisection attacker byte-identically.
- **MUR1001 — recompile-free adaptation.**  Strength lives in carried
  state and the round index is a traced input, so an adaptive round
  program compiles once and every strength/round variation is value-only
  (:class:`~murmura_tpu.analysis.sanitizers.CompileTracker`); the gang's
  ``reset_run`` re-aim between frontier stages must be equally free.
- **MUR1002 — collective-inventory parity.**  The feedback path is
  elementwise over node-local rows; the adaptive round program's traced
  collective inventory must equal the static-attack *tapped* program's,
  per rule (observing-and-reacting must not add communication, the
  MUR400 promise extended through the loop).
- **MUR1003 — feedback taint containment.**  Run the taint interpreter
  (analysis/flow.py) over the feedback path and the composed
  aggregate+feedback step: acceptance-signal taint may reach the
  *attacker's* broadcast/state rows only, and the composed step must
  still satisfy each bounded rule's declared MUR800 influence bound.
  (The interpreter deliberately excludes selection influence — a
  predicate's taint is dropped, the MUR800 semantics — so what this
  proves is that the acceptance signal never flows *as values* into
  honest rows or the aggregated output.)

Like ``check_durability``, the full grid compiles and runs tiny programs,
so it is memoized per process and runs by default only for the package
check; tests gate representative cells per tier-1 run
(tests/test_adaptive.py) and the full grid under ``-m slow``.
"""

from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding

# The adaptive-attack kinds the grids sweep: adaptive ALIE (the
# variance-quantile z walk), the generic scale bisection wrapped around
# the gaussian attack (the pair `murmura frontier` charts), and adaptive
# IPM (the epsilon walk on the paper's own mean-negation axis — the
# ISSUE-13 follow-up).
ADAPTIVE_ATTACK_KINDS: Tuple[str, ...] = ("alie", "gaussian", "ipm")

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/durability.py twin pattern).
ADAPTIVE_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    ADAPTIVE_CHECK_FAMILIES[fn.__name__] = fn
    return fn


_PKG = Path(__file__).resolve().parent.parent
_ATK_PATH = str(_PKG / "attacks" / "adaptive.py")
_ROUNDS_PATH = str(_PKG / "core" / "rounds.py")

# Collective jaxpr primitives (the MUR1002 inventory subject) — the traced
# names, not HLO op names (analysis/ir.py's _HLO_COLLECTIVES covers the
# lowered side for the canonical cells under MUR400).
_COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pbroadcast", "psum", "psum_scatter", "pmax", "pmin",
    "all_gather", "all_to_all", "reduce_scatter", "pgather", "axis_index",
})


def _build_adaptive(kind: str, n: int, pct: float = 0.3, seed: int = 7):
    """One adaptive attack of ``kind`` at size ``n`` (the grid cells')."""
    from murmura_tpu.attacks.adaptive import (
        make_adaptive_alie_attack,
        make_adaptive_ipm_attack,
        make_bisection_attack,
    )
    from murmura_tpu.attacks.gaussian import make_gaussian_attack

    if kind == "alie":
        return make_adaptive_alie_attack(n, attack_percentage=pct, seed=seed)
    if kind == "ipm":
        return make_adaptive_ipm_attack(n, attack_percentage=pct, seed=seed)
    if kind == "gaussian":
        return make_bisection_attack(
            make_gaussian_attack(
                n, attack_percentage=pct, noise_std=5.0, seed=seed
            )
        )
    raise ValueError(f"unknown adaptive attack kind {kind!r}")


# --------------------------------------------------------------------------
# MUR1000 — attack-state registry bijection
# --------------------------------------------------------------------------


@_family
def check_attack_state_registry() -> List[Finding]:
    """MUR1000: ATTACK_STATE_KEYS <-> adaptive-attack factories <-> MUR900
    snapshot registry, all bijective and shape-sound."""
    findings: List[Finding] = []
    try:
        from murmura_tpu.attacks.adaptive import (
            ADAPTIVE_ATTACKS,
            ATTACK_STATE_KEYS,
            AdaptiveAttack,
        )
        from murmura_tpu.durability.snapshot import (
            RESERVED_AGG_STATE_KEY_GROUPS,
        )
    except Exception as e:  # noqa: BLE001 — the import failure IS the finding
        return [Finding(
            "MUR1000", _ATK_PATH, 1,
            f"the adaptive-attack registries failed to import "
            f"({type(e).__name__}: {e}) — the MUR1000 bijection cannot "
            "be checked",
        )]

    keys = tuple(ATTACK_STATE_KEYS)
    if len(set(keys)) != len(keys) or any(
        not k.startswith("atk_") for k in keys
    ):
        findings.append(Finding(
            "MUR1000", _ATK_PATH, 1,
            f"ATTACK_STATE_KEYS must be distinct 'atk_'-prefixed agg_state "
            f"keys, got {keys} — the prefix is how telemetry/frontier "
            "consumers recognize adaptation state",
        ))
    reg = RESERVED_AGG_STATE_KEY_GROUPS.get("ATTACK_STATE_KEYS")
    if reg != "murmura_tpu.attacks.adaptive":
        findings.append(Finding(
            "MUR1000", _ATK_PATH, 1,
            "ATTACK_STATE_KEYS is not registered in durability.snapshot."
            f"RESERVED_AGG_STATE_KEY_GROUPS under its defining module "
            f"(got {reg!r}) — the attacker's bracket/EMA state would be "
            "invisible to the MUR900 snapshot-completeness contract and "
            "a resumed attacker would silently restart cold",
        ))

    covered: set = set()
    for name, factory in sorted(ADAPTIVE_ATTACKS.items()):
        try:
            atk = factory()
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1000", _ATK_PATH, 1,
                f"adaptive attack factory '{name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
            continue
        if not isinstance(atk, AdaptiveAttack):
            findings.append(Finding(
                "MUR1000", _ATK_PATH, 1,
                f"ADAPTIVE_ATTACKS['{name}'] built a "
                f"{type(atk).__name__}, not an AdaptiveAttack",
            ))
            continue
        for hook in ("init_attack_state", "apply_adaptive",
                     "update_attack_state", "strength_stats"):
            if getattr(atk, hook) is None:
                findings.append(Finding(
                    "MUR1000", _ATK_PATH, 1,
                    f"adaptive attack '{name}' does not populate "
                    f"'{hook}' — the round program (core/rounds.py) "
                    "calls every adaptation hook unconditionally",
                ))
        stray = set(atk.state_keys) - set(keys)
        if stray:
            findings.append(Finding(
                "MUR1000", _ATK_PATH, 1,
                f"adaptive attack '{name}' carries state keys "
                f"{sorted(stray)} not reserved in ATTACK_STATE_KEYS — "
                "unreserved carried state collides with rule state and "
                "escapes the MUR900 snapshot bijection",
            ))
        covered |= set(atk.state_keys)
        if atk.init_attack_state is None:
            continue
        for n in (4, 9):
            try:
                init = atk.init_attack_state(n)
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                findings.append(Finding(
                    "MUR1000", _ATK_PATH, 1,
                    f"adaptive attack '{name}' init_attack_state({n}) "
                    f"crashed: {type(e).__name__}: {e}",
                ))
                continue
            if set(init) != set(atk.state_keys):
                findings.append(Finding(
                    "MUR1000", _ATK_PATH, 1,
                    f"adaptive attack '{name}' init_attack_state keys "
                    f"{sorted(init)} != declared state_keys "
                    f"{sorted(atk.state_keys)} — the round program seeds "
                    "agg_state from the declaration",
                ))
                continue
            for k, v in init.items():
                arr = np.asarray(v)
                if arr.shape != (n,) or arr.dtype != np.float32:
                    findings.append(Finding(
                        "MUR1000", _ATK_PATH, 1,
                        f"adaptive attack '{name}' state key '{k}' is "
                        f"{arr.dtype}{arr.shape}, not float32 ({n},) — "
                        "adaptation state must be per-node [N] float32 "
                        "rows so gang vmap and the durability snapshot "
                        "treat it like any node-indexed carried state",
                    ))
    orphans = set(keys) - covered
    if orphans:
        findings.append(Finding(
            "MUR1000", _ATK_PATH, 1,
            f"ATTACK_STATE_KEYS entries {sorted(orphans)} are carried by "
            "no registered adaptive attack — remove the stale "
            "reservation or register the attack in ADAPTIVE_ATTACKS",
        ))
    return findings


# --------------------------------------------------------------------------
# MUR1001 — recompile-free adaptation (executable, per rule x attack)
# --------------------------------------------------------------------------


def _cell_config(rule: str, kind: str):
    """One (rule, adaptive attack) cell's tiny-but-real config — the
    durability grid's cell (analysis/durability.py) plus the adaptive
    attack block, so the two executable grids stay one inventory."""
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.config import Config

    raw: Dict[str, Any] = {
        "experiment": {"name": f"adaptive-{rule}-{kind}", "seed": 7,
                       "rounds": 4},
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
        "attack": {"enabled": True, "type": kind, "percentage": 0.3,
                   "params": ({"noise_std": 5.0} if kind == "gaussian"
                              else {}),
                   "adaptive": {"enabled": True}},
    }
    return Config.model_validate(raw)


def recompile_cell_findings(rule: str, kind: str) -> List[Finding]:
    """Run ONE (rule, adaptive attack) MUR1001 cell: 2 warmup rounds (the
    compile), then 2 more under CompileTracker — the adaptation state
    evolves (the bisection moves its probe, the ALIE z walks) and the
    round index advances, and none of it may recompile.  Exposed per-cell
    so tests gate a subset (tests/test_adaptive.py)."""
    from murmura_tpu.analysis.ir import _rule_anchor
    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.utils.factories import build_network_from_config

    path, line = _rule_anchor(rule)
    net = build_network_from_config(_cell_config(rule, kind))
    net.train(rounds=2, verbose=False)
    state_before = {
        k: np.asarray(v) for k, v in net.agg_state.items()
        if k.startswith("atk_")
    }
    with track_compiles() as tracker:
        net.train(rounds=2, verbose=False)
    findings: List[Finding] = []
    if tracker.total:
        findings.append(Finding(
            "MUR1001", path, line,
            f"[{rule}/{kind}] 2 adaptive rounds after warmup compiled "
            f"{tracker.total} program(s) — attack strength is carried "
            "state and the round index a traced input, so adaptation "
            "must be value-only over one compiled round program",
        ))
    comp = np.asarray(net.compromised) > 0
    moved = any(
        not np.array_equal(
            state_before[k][comp], np.asarray(net.agg_state[k])[comp]
        )
        for k in state_before
    )
    if state_before and comp.any() and not moved:
        findings.append(Finding(
            "MUR1001", path, line,
            f"[{rule}/{kind}] the adaptation state did not move across 2 "
            "rounds — the recompile check is vacuous (the feedback loop "
            "is not actually running; check the acceptance wiring in "
            "core/rounds.py)",
        ))
    return findings


@_family
def check_adaptive_recompile() -> List[Finding]:
    """MUR1001 over ``AGGREGATORS x ADAPTIVE_ATTACK_KINDS``, plus the
    frontier's gang re-aim: ``reset_run`` to a new strength grid over the
    warm bucket must cost zero compiles (the `murmura frontier` stage
    loop's contract)."""
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.analysis.ir import _rule_anchor

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for kind in ADAPTIVE_ATTACK_KINDS:
            try:
                findings.extend(recompile_cell_findings(rule, kind))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1001", path, line,
                    f"[{rule}/{kind}] adaptive recompile probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    try:
        findings.extend(gang_reset_findings())
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR1001", str(_PKG / "core" / "gang.py"), 1,
            f"the gang reset_run recompile probe crashed: "
            f"{type(e).__name__}: {e}",
        ))
    return findings


def gang_reset_findings() -> List[Finding]:
    """The frontier stage loop's contract: a strength-grid re-aim via
    ``GangNetwork.reset_run`` over the warm bucket costs zero compiles."""
    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.config import Config
    from murmura_tpu.core.gang import GangMember
    from murmura_tpu.utils.factories import build_gang_from_config

    raw = _cell_config("krum", "gaussian").model_dump()
    raw["sweep"] = {"members": [
        {"seed": 7, "attack_scale": 0.0},
        {"seed": 7, "attack_scale": 1.0},
    ]}
    gang = build_gang_from_config(
        Config.model_validate(raw), retain_init=True
    )
    gang.train(rounds=2, eval_every=2)
    with track_compiles() as tracker:
        gang.reset_run([
            GangMember(seed=7, attack_scale=0.0),
            GangMember(seed=7, attack_scale=2.5),
        ])
        gang.train(rounds=2, eval_every=2)
    if tracker.total:
        return [Finding(
            "MUR1001", str(_PKG / "core" / "gang.py"), 1,
            f"reset_run + retrain over the warm gang bucket compiled "
            f"{tracker.total} program(s) — the frontier's successive-"
            "halving stages must be value-only resets (strengths are "
            "traced hp inputs; the bucket shape is unchanged)",
        )]
    return []


# --------------------------------------------------------------------------
# MUR1002 — collective-inventory parity (trace-level, per rule x attack)
# --------------------------------------------------------------------------


def _trace_collectives(prog) -> frozenset:
    """Collective primitive names in the round program's traced jaxpr."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.ir import iter_eqns

    n = prog.num_nodes
    adj = jnp.asarray(
        np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
    )
    closed = jax.make_jaxpr(prog.train_step)(
        prog.init_params,
        {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
        jax.random.PRNGKey(0),
        adj,
        jnp.zeros((n,), jnp.float32),
        jnp.asarray(0.0, jnp.float32),
        {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
    )
    return frozenset(
        e.primitive.name for e in iter_eqns(closed)
        if e.primitive.name in _COLLECTIVE_PRIMS
    )


def collective_cell_findings(rule: str, kind: str) -> List[Finding]:
    """One (rule, adaptive attack) MUR1002 cell: the adaptive round
    program's traced collective inventory vs the static-attack *tapped*
    program's — the feedback path must not add communication."""
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES, _rule_anchor
    from murmura_tpu.attacks.alie import make_alie_attack
    from murmura_tpu.attacks.gaussian import make_gaussian_attack
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp

    path, line = _rule_anchor(rule)
    n, s = 5, 16
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
    agg = build_aggregator(
        rule, dict(AGG_CASES.get(rule, {})), model_dim=int(flat0.size),
        total_rounds=4,
    )
    if kind == "alie":
        static = make_alie_attack(n, attack_percentage=0.3, seed=7)
    elif kind == "ipm":
        from murmura_tpu.attacks.ipm import make_ipm_attack

        static = make_ipm_attack(n, attack_percentage=0.3, seed=7)
    else:
        static = make_gaussian_attack(
            n, attack_percentage=0.3, noise_std=5.0, seed=7
        )
    adaptive = _build_adaptive(kind, n)
    common = dict(
        local_epochs=1, batch_size=8, lr=0.05, total_rounds=4, seed=7
    )
    inv_static = _trace_collectives(build_round_program(
        model, agg, data, attack=static, audit_taps=True, **common
    ))
    inv_adaptive = _trace_collectives(build_round_program(
        model, agg, data, attack=adaptive, **common
    ))
    stray = inv_adaptive - inv_static
    if stray:
        return [Finding(
            "MUR1002", path, line,
            f"[{rule}/{kind}] the adaptive round program traces "
            f"collective(s) {sorted(stray)} absent from the static-attack "
            "tapped program — the acceptance feedback must stay "
            "elementwise over node-local rows (closing the loop must not "
            "add communication)",
        )]
    return []


@_family
def check_adaptive_collectives() -> List[Finding]:
    """MUR1002 over ``AGGREGATORS x ADAPTIVE_ATTACK_KINDS`` (trace-only:
    nothing compiles)."""
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.analysis.ir import _rule_anchor

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for kind in ADAPTIVE_ATTACK_KINDS:
            try:
                findings.extend(collective_cell_findings(rule, kind))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1002", path, line,
                    f"[{rule}/{kind}] adaptive collective-inventory probe "
                    f"crashed: {type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1003 — feedback taint containment (trace-only)
# --------------------------------------------------------------------------


def containment_findings(name: str, attack) -> List[Finding]:
    """Taint the acceptance signal, run the feedback update + the next
    apply, and require every tainted broadcast/state row to be the
    attacker's own: accept-label j may reach row i only when ``i == j``
    and i is compromised.  Factored out so tests can drive it with a
    leaky fake attack (tests/test_adaptive.py)."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.flow import TaintEval, _quiet_tracing, _tz

    n, dim = 8, 6
    comp = jnp.asarray(attack.compromised.astype(np.float32))
    comp_np = np.asarray(attack.compromised) > 0
    keys = tuple(sorted(attack.state_keys))
    state0 = attack.init_attack_state(n)
    rng_np = np.random.default_rng(0)
    flat0 = jnp.asarray(rng_np.normal(size=(n, dim)) * 0.1, jnp.float32)
    prng = jax.random.PRNGKey(0)

    def fn(flat, accept, *state_vals):  # murmura: traced
        state = dict(zip(keys, state_vals))
        new_state = attack.update_attack_state(
            state, accept, jnp.ones(n, jnp.float32), comp
        )
        out = attack.apply_adaptive(
            flat, comp, prng, jnp.asarray(0.0, jnp.float32), new_state
        )
        return (out,) + tuple(new_state[k] for k in keys)

    args = (flat0, jnp.full((n,), 0.5, jnp.float32)) + tuple(
        jnp.asarray(state0[k]) for k in keys
    )
    with _quiet_tracing():
        closed = jax.make_jaxpr(fn)(*args)
    ev = TaintEval(n)
    pairs = []
    for i, a in enumerate(args):
        v = np.asarray(a)
        t = _tz(n, v.shape)
        if i == 1:  # the acceptance signal: row labels
            for lbl in range(n):
                t[lbl, lbl] = True
        pairs.append((v, t))
    with _quiet_tracing():
        outs = ev.eval_closed(closed, pairs)

    findings: List[Finding] = []
    subjects = [("broadcast", outs[0][1])] + [
        (f"state '{k}'", outs[1 + i][1]) for i, k in enumerate(keys)
    ]
    for label, t in subjects:
        # t is [L, N, ...]: label j present anywhere in row i.
        rows = t.reshape(n, n, -1).any(axis=2)  # [label, row]
        for j in range(n):
            for i in range(n):
                if not rows[j, i]:
                    continue
                if i != j or not comp_np[i]:
                    who = (
                        "an honest row" if not comp_np[i]
                        else "another compromised node's row"
                    )
                    findings.append(Finding(
                        "MUR1003", _ATK_PATH, 1,
                        f"adaptive attack '{name}': acceptance-signal "
                        f"taint about node {j} reaches {label} row {i} "
                        f"({who}) — the feedback loop may only tune the "
                        "attacker's own rows",
                    ))
    return findings


def adaptive_influence_findings(rule: str, kind: str) -> List[Finding]:
    """One (rule, adaptive attack) composed-step cell: aggregate with
    taps on, feed the acceptance signal into the attack-state update, and
    analyze the whole step with broadcast rows taint-seeded.  The
    aggregated output must still satisfy the rule's declared MUR800
    bound, and the updated attack state may be tainted at compromised
    rows only."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.flow import (
        TaintEval,
        _quiet_tracing,
        _rule_anchor,
        _tz,
        build_flow_cell,
    )
    from murmura_tpu.attacks.adaptive import acceptance_feedback

    path, line = _rule_anchor(rule)
    cell = build_flow_cell(rule, "dense", audit=True)
    n = cell.n
    attack = _build_adaptive(kind, n)
    comp = jnp.asarray(attack.compromised.astype(np.float32))
    comp_np = np.asarray(attack.compromised) > 0
    keys = tuple(sorted(attack.state_keys))
    atk0 = attack.init_attack_state(n)
    cell_fn, bcast_args = cell.fn, cell.bcast_args

    def fn(*all_args):  # murmura: traced
        cell_args = all_args[: len(cell.args)]
        state_vals = all_args[len(cell.args):]
        new_flat, _rule_state, agg_stats = cell_fn(*cell_args)
        adj = cell_args[2]  # dense cells: (own, bcast, adj, ridx, ...)
        accept, observed = acceptance_feedback(
            agg_stats, {}, adj.sum(axis=1), None
        )
        atk_state = dict(zip(keys, state_vals))
        new_atk = attack.update_attack_state(
            atk_state, accept, observed, comp
        )
        return (new_flat,) + tuple(new_atk[k] for k in keys)

    args = tuple(cell.args) + tuple(jnp.asarray(atk0[k]) for k in keys)
    with _quiet_tracing():
        closed = jax.make_jaxpr(fn)(*args)
    ev = TaintEval(n)
    flat_args, _ = jax.tree_util.tree_flatten(args)
    arg_leaf_pos: List[int] = []
    for i, a in enumerate(args):
        arg_leaf_pos.extend([i] * len(jax.tree_util.tree_leaves(a)))
    pairs = []
    for leaf, pos in zip(flat_args, arg_leaf_pos):
        v = np.asarray(leaf)
        t = _tz(n, v.shape)
        if pos in bcast_args:  # the exchanged payload: row labels
            for lbl in range(n):
                t[lbl, lbl] = True
        pairs.append((v, t))
    with _quiet_tracing():
        outs = ev.eval_closed(closed, pairs)

    findings: List[Finding] = []
    out_t = outs[0][1]  # [L, N, P]
    self_t = out_t[np.arange(n), np.arange(n)]
    card = int((out_t.sum(axis=0) - self_t).max())
    influence = cell.agg.influence
    if influence is not None and influence.kind == "bounded":
        k_deg = int(np.asarray(cell.args[2]).sum(axis=1).max())
        bound = influence.bound(k_deg)
        if card > bound:
            findings.append(Finding(
                "MUR1003", path, line,
                f"[{rule}/{kind}] the composed aggregate+feedback step "
                f"mixes {card} neighbors into an output coordinate but "
                f"the rule declares a bound of {bound} — the adaptive "
                "feedback loop widened the rule's per-coordinate "
                "influence",
            ))
    for i, key in enumerate(keys):
        t = outs[1 + i][1]  # [L, N]
        tainted_rows = np.nonzero(t.any(axis=0))[0]
        bad = [int(r) for r in tainted_rows if not comp_np[r]]
        if bad:
            findings.append(Finding(
                "MUR1003", path, line,
                f"[{rule}/{kind}] updated attack state '{key}' carries "
                f"exchange taint at honest row(s) {bad} — the feedback "
                "update must be gated to the attacker's own rows",
            ))
    return findings


@_family
def check_adaptive_influence() -> List[Finding]:
    """MUR1003: feedback containment per adaptive attack, plus the
    composed aggregate+feedback influence sweep over
    ``AGGREGATORS x ADAPTIVE_ATTACK_KINDS`` (trace-only)."""
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.attacks.adaptive import ADAPTIVE_ATTACKS

    findings: List[Finding] = []
    kind_of = {
        "adaptive_alie": "alie",
        "adaptive_ipm": "ipm",
        "bisection": "gaussian",
    }
    for name in sorted(ADAPTIVE_ATTACKS):
        try:
            atk = _build_adaptive(kind_of.get(name, "gaussian"), 8)
            findings.extend(containment_findings(name, atk))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1003", _ATK_PATH, 1,
                f"adaptive attack '{name}' crashed the containment "
                f"probe: {type(e).__name__}: {e}",
            ))
    for rule in sorted(AGGREGATORS):
        for kind in ADAPTIVE_ATTACK_KINDS:
            try:
                findings.extend(adaptive_influence_findings(rule, kind))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                from murmura_tpu.analysis.flow import _rule_anchor

                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1003", path, line,
                    f"[{rule}/{kind}] adaptive influence probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_ADAPTIVE_MEMO: Optional[List[Finding]] = None


def check_adaptive(force: bool = False) -> List[Finding]:
    """Run MUR1000-1003; returns findings (empty = every adaptive-attack
    contract holds).  Memoized per process — the CLI and the slow test
    gate share one sweep.  MUR1001 compiles
    and runs tiny programs (the check_durability cost profile), which is
    why the family runs only for the package-level check."""
    global _ADAPTIVE_MEMO
    if _ADAPTIVE_MEMO is not None and not force:
        return list(_ADAPTIVE_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in ADAPTIVE_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1000", str(Path(__file__).resolve()), 1,
                f"adaptive check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _ADAPTIVE_MEMO = list(findings)
    return findings
