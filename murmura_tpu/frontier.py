"""`murmura frontier <yaml>`: the robustness frontier at gang speed
(ISSUE 11; docs/ROBUSTNESS.md "The robustness frontier").

For every (rule x adaptive attack x topology) cell of the configured grid
this driver charts honest accuracy against attack strength and locates
the rule's empirical **breaking point** — the strength where the honest-
accuracy cliff happens — then writes one committed ``frontier.json``
artifact placing that number next to the rule's MUR800 *declared*
influence bound (``AggregatorDef.influence``, verified statically by
`murmura check --flow`).  The artifact is the static-vs-dynamic
comparison ROADMAP item 4 calls for: what the dataflow analyzer proves a
rule CAN admit, against what an adversary that fights back actually
achieves.

Execution model — compile-compatible buckets, stages without recompiles:

- One cell's strength x seed grid becomes ONE gang (core/gang.py): every
  strength is a per-member ``attack_scale`` traced input (the ``sweep:``
  plumbing), the member count pads to the next power of two, and the
  whole stage runs in one vmapped compiled program.  A 0-strength member
  rides every stage as the benign reference.
- The outer successive-halving loop re-aims the strength grid at the
  cliff between stages via :meth:`GangNetwork.reset_run` — a value-only
  reset of params/RNG/state over the SAME warm executables, so a whole
  multi-stage cell costs the bucket's initial compiles and nothing more
  (<= 2: the fused train program and nothing else, or train + eval on
  the per-round path; tests/test_adaptive.py counts them under
  ``tpu.recompile_guard``).
- The attacks are ADAPTIVE (attacks/adaptive.py): each member's attacker
  bisects/walks its own strength multiplier against the acceptance taps
  *within* the member's base strength, so a strength-grid point reports
  the best closed-loop attack at that budget, not a fixed perturbation.
"""

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from murmura_tpu.config.schema import Config, FrontierConfig

FRONTIER_SCHEMA_VERSION = 1

# Attack-strength grid floor: successive halving must not chase the cliff
# into denormal territory (a strength this small is "the rule filters the
# attack outright", which the artifact records as such).
_MIN_STRENGTH = 1e-3


@dataclass
class FrontierCell:
    """One (rule, attack, topology[, percentage]) cell's accumulated
    results."""

    rule: str
    attack: str
    topology: str
    degree: int
    percentage: Optional[float] = None
    # strength -> list of per-seed records
    curve: Dict[float, Dict[str, Any]] = field(default_factory=dict)
    benign_accuracy: float = float("nan")
    compiles: int = 0
    stages_run: int = 0


def _geom_grid(lo: float, hi: float, points: int) -> List[float]:
    lo = max(float(lo), _MIN_STRENGTH)
    hi = max(float(hi), lo * (1.0 + 1e-6))
    return [float(g) for g in np.geomspace(lo, hi, points)]


def _cell_config(
    config: Config,
    f: FrontierConfig,
    rule: str,
    attack: str,
    topology: str,
    members: Optional[List[Dict[str, Any]]] = None,
    percentage: Optional[float] = None,
) -> Config:
    """Derive one cell's runnable config from the base experiment.

    The cell keeps the base data/model/training setup; rule params come
    from the user's config when the cell runs the configured rule, else
    the canonical AGG_CASES defaults (the same inventory every analysis
    grid uses).  Telemetry/durability are stripped — the frontier's
    artifact IS its output, and per-member writer trees for hundreds of
    stage-members would be noise.
    """
    from murmura_tpu.analysis.ir import AGG_CASES

    raw = config.model_dump()
    raw["aggregation"] = {
        "algorithm": rule,
        "params": (
            dict(config.aggregation.params)
            if rule == config.aggregation.algorithm
            else dict(AGG_CASES.get(rule, {}))
        ),
    }
    base_attack = config.attack
    if percentage is not None:
        # The breakdown-point axis (frontier.percentages): this cell runs
        # with an explicit compromised fraction.  Each percentage is its
        # own gang bucket — the compromised set is a static attack
        # closure, so it cannot vary inside one compiled bucket the way
        # the strength grid does.
        pct = float(percentage)
    else:
        pct = base_attack.percentage if base_attack.enabled else 0.25
    params: Dict[str, Any] = {}
    if attack == "gaussian":
        params["noise_std"] = float(
            base_attack.params.get("noise_std", 10.0)
        ) if base_attack.type == "gaussian" else 10.0
    elif base_attack.type == "alie" and "z" in base_attack.params:
        params["z"] = base_attack.params["z"]
    # Pin the compromised placement to the base experiment seed so every
    # member of every stage shares the attack's static closures (the gang
    # contract, core/gang.py).
    params["seed"] = int(
        base_attack.params.get("seed", config.experiment.seed)
    )
    raw["attack"] = {
        "enabled": True,
        "type": attack,
        "percentage": pct,
        "params": params,
        "adaptive": {"enabled": True},
    }
    n = config.topology.num_nodes
    if topology == "sparse":
        raw["topology"] = {"type": "exponential", "num_nodes": n}
    elif config.topology.type in ("exponential", "one_peer"):
        # The base config is itself sparse; the dense cell needs a dense
        # stand-in — the canonical k-regular(4) graph at the same size.
        raw["topology"] = {
            "type": "k-regular", "num_nodes": n, "k": min(4, n - 1),
        }
    else:
        raw["topology"] = config.topology.model_dump()
    if f.rounds is not None:
        raw["experiment"] = {
            **raw["experiment"], "rounds": int(f.rounds),
        }
    raw["experiment"]["verbose"] = False
    raw.pop("telemetry", None)
    raw.pop("durability", None)
    raw.pop("sweep", None)
    raw.pop("frontier", None)
    if members is not None:
        raw["sweep"] = {"members": members}
    try:
        return Config.model_validate(raw)
    except Exception as e:  # noqa: BLE001 — surface as the CLI's error kind
        from murmura_tpu.utils.factories import ConfigError

        raise ConfigError(
            f"frontier cell {rule} x {attack} x {topology} does not "
            f"validate against the base config: {e}"
        ) from e


def _members_for(
    strengths: Sequence[float], seeds: Sequence[int]
) -> List[Dict[str, Any]]:
    return [
        {"seed": int(s), "attack_scale": float(g)}
        for g in strengths
        for s in seeds
    ]


def _honest_final(history: Dict[str, List[float]]) -> float:
    rows = history.get("honest_accuracy") or history.get("mean_accuracy")
    return float(rows[-1]) if rows else float("nan")


def _adaptive_summary(gang, member: int) -> Dict[str, float]:
    """Mean adaptation state over the member's compromised rows — the
    attacker's own account of where it converged (bisection bracket /
    ALIE z / acceptance EMA)."""
    comp = np.asarray(gang.compromised) > 0
    out: Dict[str, float] = {}
    for key, arr in gang.agg_state.items():
        if not key.startswith("atk_"):
            continue
        rows = np.asarray(arr)[member]
        out[key] = float(rows[comp].mean()) if comp.any() else float("nan")
    return out


def _locate_break(
    curve: Dict[float, Dict[str, Any]], benign: float, break_fraction: float
):
    """(last_held, first_broken) from the accumulated curve: the largest
    strength whose mean honest accuracy still clears the threshold and
    the smallest that falls below it."""
    thr = break_fraction * benign
    held = [g for g, rec in curve.items() if g > 0 and rec["mean"] >= thr]
    broken = [g for g, rec in curve.items() if g > 0 and rec["mean"] < thr]
    last_held = max(held) if held else None
    first_broken = min(broken) if broken else None
    return last_held, first_broken, thr


def run_cell(
    config: Config,
    f: FrontierConfig,
    rule: str,
    attack: str,
    topology: str,
    seeds: Sequence[int],
    progress: Optional[Callable[[str], None]] = None,
    percentage: Optional[float] = None,
) -> FrontierCell:
    """Run one (rule, attack, topology[, percentage]) cell: stage-0
    grid, then successive-halving refinement around the cliff, all on
    one gang bucket with value-only resets between stages."""
    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.core.gang import GangMember
    from murmura_tpu.utils.factories import build_gang_from_config

    say = progress or (lambda s: None)
    grid = _geom_grid(f.strength_lo, f.strength_hi, f.points)
    strengths = [0.0] + grid
    cfg = _cell_config(
        config, f, rule, attack, topology,
        members=_members_for(strengths, seeds),
        percentage=percentage,
    )
    rounds = cfg.experiment.rounds
    gang = build_gang_from_config(cfg, retain_init=True)
    if topology == "sparse":
        degree = len(gang.topology.offsets)
    else:
        degree = int(np.asarray(gang.topology.mask()).sum(axis=1).max())

    cell = FrontierCell(
        rule=rule, attack=attack, topology=topology, degree=degree,
        percentage=(
            float(percentage) if percentage is not None
            else float(cfg.attack.percentage)
        ),
    )

    def run_stage(stage: int, stage_strengths: Sequence[float]) -> None:
        members = [
            GangMember(seed=int(s), attack_scale=float(g))
            for g in stage_strengths
            for s in seeds
        ]
        if stage > 0:
            gang.reset_run(members)
        histories = gang.train(
            rounds=rounds, eval_every=rounds,
            rounds_per_dispatch=rounds,
        )
        comp = np.asarray(gang.compromised) > 0
        for i, m in enumerate(members):
            acc = _honest_final(histories[i])
            g = float(m.attack_scale)
            rec = cell.curve.setdefault(
                g, {"per_seed": {}, "adaptive": {}, "stage": stage}
            )
            rec["per_seed"][str(m.seed)] = acc
            if comp.any():
                rec["adaptive"][str(m.seed)] = _adaptive_summary(gang, i)
        for rec in cell.curve.values():
            vals = list(rec["per_seed"].values())
            rec["mean"] = float(np.mean(vals))
            rec["std"] = float(np.std(vals))
        cell.stages_run = stage + 1

    with track_compiles() as tracker:
        say(f"  stage 0: strengths {['%.3g' % g for g in strengths]}")
        run_stage(0, strengths)
        cell.benign_accuracy = cell.curve[0.0]["mean"]
        for stage in range(1, f.stages):
            last_held, first_broken, _thr = _locate_break(
                cell.curve, cell.benign_accuracy, f.break_fraction
            )
            if last_held is None and first_broken is None:
                break
            if first_broken is None:
                # Nothing broke: push the grid upward.
                nxt = _geom_grid(last_held, last_held * 4.0, f.points)
            elif last_held is None:
                # Everything broke: pull the grid downward.
                nxt = _geom_grid(first_broken / 8.0, first_broken, f.points)
            else:
                if first_broken <= last_held * (1.0 + 1e-6):
                    break  # non-monotone overlap — the bracket is as
                    # tight as this grid can make it
                inner = _geom_grid(last_held, first_broken, f.points + 2)
                nxt = inner[1:-1]
            fresh = [
                g for g in nxt
                if all(abs(g - g0) > 1e-9 for g0 in cell.curve)
            ]
            if not fresh:
                break
            while len(fresh) < f.points:
                fresh.append(grid[len(fresh) % len(grid)])
            say(
                f"  stage {stage}: refining "
                f"{['%.3g' % g for g in fresh[: f.points]]}"
            )
            run_stage(stage, [0.0] + fresh[: f.points])
    cell.compiles = tracker.total
    return cell


def declared_influence(rule: str, degree: int) -> Optional[Dict[str, Any]]:
    """The rule's MUR800 declared influence contract at this cell's
    degree — the static half of the static-vs-dynamic comparison."""
    try:
        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.analysis.ir import AGG_CASES

        agg = build_aggregator(
            rule, dict(AGG_CASES.get(rule, {})), model_dim=8,
            total_rounds=1,
        )
    except Exception:  # noqa: BLE001 — the artifact stays writable
        return None
    decl = agg.influence
    if decl is None:
        return None
    return {
        "kind": decl.kind,
        "bound": decl.bound(degree) if decl.kind == "bounded" else None,
        "describe": decl.describe(degree),
    }


def run_frontier(
    config: Config,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict[str, Any]:
    """Run the full configured grid; returns the frontier artifact dict
    (the ``frontier.json`` payload)."""
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.utils.factories import ConfigError

    say = progress or (lambda s: None)
    f = config.frontier or FrontierConfig()
    unknown = sorted(set(f.rules) - set(AGGREGATORS))
    if unknown:
        raise ConfigError(
            f"frontier.rules names unregistered aggregation rule(s) "
            f"{unknown}; known: {sorted(AGGREGATORS)}"
        )
    # Fail loud BEFORE any cell trains: every cell runs a closed-loop
    # adaptive attack, whose schema-level composition limits the base
    # config must already satisfy (config/schema.py
    # _adaptive_attack_is_wirable gives the full rationale).
    if config.dmtt is not None:
        raise ConfigError(
            "frontier cells run adaptive attacks, which do not compose "
            "with dmtt — remove the dmtt block from the frontier config"
        )
    if config.backend == "distributed":
        raise ConfigError(
            "frontier cells close the attack feedback loop inside the "
            "jitted round program; use backend: simulation or tpu"
        )
    seeds = list(f.seeds) if f.seeds is not None else [config.experiment.seed]

    # The breakdown-point axis (frontier.percentages): each compromised
    # fraction runs the full strength x seed successive-halving search as
    # its own compile-compatible bucket.  None = the base attack fraction
    # only (the pre-axis behavior; the artifact still records which).
    percentages: List[Optional[float]] = (
        [float(p) for p in f.percentages]
        if f.percentages is not None else [None]
    )

    cells: List[Dict[str, Any]] = []
    for rule in f.rules:
        for attack in f.attacks:
            for topology in f.topologies:
                for pct in percentages:
                    pct_label = "" if pct is None else f" x pct={pct:g}"
                    say(f"cell {rule} x {attack} x {topology}{pct_label}")
                    cell = run_cell(
                        config, f, rule, attack, topology, seeds,
                        progress=progress, percentage=pct,
                    )
                    last_held, first_broken, thr = _locate_break(
                        cell.curve, cell.benign_accuracy, f.break_fraction
                    )
                    curve_rows = [
                        {"strength": g, **rec}
                        for g, rec in sorted(cell.curve.items())
                    ]
                    cells.append({
                        "rule": rule,
                        "attack": attack,
                        "topology": topology,
                        "percentage": cell.percentage,
                        "degree": cell.degree,
                        "benign_accuracy": cell.benign_accuracy,
                        "curve": curve_rows,
                        "breaking_point": {
                            "last_held": last_held,
                            "first_broken": first_broken,
                            "threshold_accuracy": thr,
                            "criterion": (
                                f"mean honest accuracy < "
                                f"{f.break_fraction} x benign "
                                "(0-strength) accuracy"
                            ),
                        },
                        "declared_influence": declared_influence(
                            rule, cell.degree
                        ),
                        "stages": cell.stages_run,
                        "compiles": cell.compiles,
                    })

    return {
        "schema_version": FRONTIER_SCHEMA_VERSION,
        "generated_by": "murmura frontier",
        "experiment": config.experiment.name,
        "grid": {
            "rules": list(f.rules),
            "attacks": list(f.attacks),
            "topologies": list(f.topologies),
            "percentages": (
                list(f.percentages) if f.percentages is not None else None
            ),
            "seeds": seeds,
            "points": f.points,
            "stages": f.stages,
            "rounds": f.rounds or config.experiment.rounds,
            "strength_lo": f.strength_lo,
            "strength_hi": f.strength_hi,
            "break_fraction": f.break_fraction,
            "num_nodes": config.topology.num_nodes,
        },
        "cells": cells,
    }


def write_frontier(artifact: Dict[str, Any], path) -> Path:
    """Durably write the artifact (the checkpoint fsync discipline — a
    frontier run is minutes of compute the write must not tear)."""
    from murmura_tpu.utils.checkpoint import durable_replace

    path = Path(path).resolve()
    path.parent.mkdir(parents=True, exist_ok=True)
    durable_replace(
        path.parent, path.name,
        (json.dumps(artifact, indent=2) + "\n").encode("utf-8"),
    )
    return path


def load_frontier(path) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as fh:
        artifact = json.load(fh)
    if "cells" not in artifact:
        raise ValueError(
            f"{path} is not a frontier artifact (no 'cells' section)"
        )
    return artifact


def frontier_break_summary(artifact: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Flat per-cell summary rows for `murmura report --frontier`:
    empirical breaking point next to the declared MUR800 bound."""
    rows = []
    for c in artifact.get("cells", []):
        decl = c.get("declared_influence") or {}
        bp = c.get("breaking_point") or {}
        rows.append({
            "rule": c.get("rule"),
            "attack": c.get("attack"),
            "topology": c.get("topology"),
            # Pre-percentage-axis artifacts (schema v1 before ISSUE 13)
            # have no percentage field; render as unknown, not 0.
            "percentage": c.get("percentage"),
            "degree": c.get("degree"),
            "benign_accuracy": c.get("benign_accuracy"),
            "last_held": bp.get("last_held"),
            "first_broken": bp.get("first_broken"),
            "declared": decl.get("describe"),
            "declared_kind": decl.get("kind"),
            "declared_bound": decl.get("bound"),
            "compiles": c.get("compiles"),
        })
    return rows
