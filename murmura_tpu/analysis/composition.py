"""Cross-feature composition contracts (MUR1400-1403) — part of the
default package check (docs/ANALYSIS.md "Composition grid").

The framework's orthogonal levers (murmura_tpu/levers.py) historically
interacted through hand-written ``ConfigError`` refusals scattered over
``config/schema.py`` and ``utils/factories.py``: nothing checked that a
refusal was still justified, that a declared-compatible pair still
composed, or that a new lever declared anything at all.  Each
:class:`~murmura_tpu.levers.LeverManifest` now declares its lever's
composition surface exactly once, and this module closes the loop both
ways:

- **MUR1400 — manifest <-> guard bijection.**  The ``LEVER_MODULES``
  registry, an AST scan for module-level ``LEVER_MANIFEST`` assignments
  (the MUR900 ``*_STATE_KEYS`` discovery pattern), the reserved
  state-key-group registry and the ``STAGE_ORDER`` labels must agree;
  every ``refusal_reason(...)`` guard site in schema/factories must
  resolve to a declared verdict; every declared refusal must have a
  live guard that FIRES (the executable census arms each refused
  combination and requires the declared reason verbatim in the raised
  error); and no refusal-shaped literal may bypass the manifest — a
  guard string containing "does not compose" outside ``refusal_reason``
  is an undeclared refusal.  The committed census
  (analysis/COMPOSITION.json) pins the refusal count so lifting a pair
  (or quietly refusing a new one) is a reviewed diff, not drift.
- **MUR1401 — the generated pairwise grid.**  Every declared-compatible
  pair's composed round program must actually build from config, train
  recompile-free after warmup
  (:class:`~murmura_tpu.analysis.sanitizers.CompileTracker`), produce
  finite metrics, and keep collective-inventory parity: the composed
  trace's collectives stay within the union of the two
  individually-armed programs' (a composed build that grows a new
  collective is a new distributed algorithm, not a composition).  The
  lifted ``sharding x sweep`` cell additionally pins the
  ("seed", "nodes", "param") gang mesh and rebuild determinism.
- **MUR1402 — composed carried state + stage order.**  The reserved
  ``*_STATE_KEYS`` groups are pairwise disjoint; a composed program's
  ``agg_state`` carries the union of the two single-lever programs'
  keys; and the composed trace's ``murmura.*`` named_scope stage labels
  first-occur in ``STAGE_ORDER`` order, with each armed lever's
  declared stage hook actually present (core/rounds.py is the single
  ordering authority the manifests must match).
- **MUR1403 — flow-taint preservation on composed cells.**  Bounded
  rules keep their MUR800-declared per-coordinate influence when two
  levers touch the same exchange: the compressed+stale cell (int8
  round-trip feeding the stale fold) and the sparse+stale cell ([k, N]
  edge masks through the re-add layer) re-run the staleness Probe-A
  taint run (analysis/staleness.py) over the composed step.

MUR1401 compiles and runs one tiny program per compatible pair (the
check_durability cost profile at grid scale), so the family is memoized
per process and runs by default only for the package check; tests gate
representative cells per tier-1 run (tests/test_composition.py) and
negatives prove each probe can fire.
"""

import ast
import copy
import json
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding
from murmura_tpu.levers import (
    LEVER_MODULES,
    STAGE_ORDER,
    compatible_pairs,
    declared_refusals,
    discover_lever_manifests,
    lever_manifests,
    pair_verdict,
    refusal_reason,
)

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/sharded.py twin pattern).
COMPOSE_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    COMPOSE_CHECK_FAMILIES[fn.__name__] = fn
    return fn


_PKG = Path(__file__).resolve().parent.parent
_LEVERS_PATH = str(_PKG / "levers.py")
_SCHEMA_PATH = str(_PKG / "config" / "schema.py")
_FACTORIES_PATH = str(_PKG / "utils" / "factories.py")

# The committed refusal census: lifting a pair (or adding a refusal)
# must move this file in the same diff (the BUDGETS.json convention).
COMPOSITION_JSON = Path(__file__).resolve().parent / "COMPOSITION.json"

# Levers whose arming changes the traced round program (the others —
# mobility, population, sweep — act at the orchestrator layer and leave
# the per-round trace alone, so collective parity is not their contract).
_PROGRAM_LEVERS = frozenset((
    "adaptive", "compression", "dmtt", "faults", "pipeline", "sharding",
    "sparse", "staleness",
))

# Stage labels a lever's arming reliably emits into the composed trace
# (core/rounds.py wraps exactly these code paths in jax.named_scope).
# dmtt/sparse declare the exchange stage they ride but do not open their
# own bracket, so presence is only required for this subset.
_SCOPED_STAGES: Dict[str, str] = {
    "adaptive": "murmura.exchange",
    "compression": "murmura.compress",
    "staleness": "murmura.stale",
    "pipeline": "murmura.pipeline",
}


def _manifest_anchor(lever: str) -> Tuple[str, int]:
    """(path, line) of a lever's LEVER_MANIFEST declaration."""
    import importlib

    mod = importlib.import_module(LEVER_MODULES[lever])
    path = str(Path(mod.__file__).resolve())
    try:
        for i, text in enumerate(Path(path).read_text().splitlines(), 1):
            if text.startswith("LEVER_MANIFEST"):
                return path, i
    except OSError:
        pass
    return path, 1


def _pair_anchor(a: str, b: str) -> Tuple[str, int]:
    """Findings about a pair anchor at the later lever's manifest — the
    declaration that owns the verdict."""
    return _manifest_anchor(max(a, b))


def _deep_merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


# --------------------------------------------------------------------------
# The canonical grid cell: one tiny experiment + one armer per lever
# --------------------------------------------------------------------------

# Ring of 8 nodes, tiny MLP (flat dim 99 -> padded 100 over 2 shards),
# synthetic data, 4 trained rounds per cell (2 warmup + 2 tracked).
_BASE_RAW: Dict[str, Any] = {
    "experiment": {"name": "compose-cell", "seed": 7, "rounds": 6},
    "topology": {"type": "ring", "num_nodes": 8},
    "aggregation": {"algorithm": "balance", "params": {}},
    "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
    "data": {"adapter": "synthetic",
             "params": {"num_samples": 40, "input_shape": [6],
                        "num_classes": 3}},
    "model": {"factory": "mlp",
              "params": {"input_dim": 6, "hidden_dims": [8],
                         "num_classes": 3}},
}

# One canonical arming per lever — the raw-config override that turns
# the lever ON in a grid cell.  Constrained pairs arm OUTSIDE their
# refused sub-configuration (see _PAIR_OVERRIDES): int8 block 10
# divides the 50-wide shard-local flat width, the sparse armer has 3
# offsets (not one_peer), the staleness armer carries the fault model it
# requires, and the dmtt armer sets allow_static so cells without
# mobility stay wirable.
LEVER_ARMERS: Dict[str, Dict[str, Any]] = {
    "adaptive": {"attack": {"enabled": True, "type": "gaussian",
                            "percentage": 0.25,
                            "adaptive": {"enabled": True},
                            "params": {"noise_std": 5.0, "seed": 7}}},
    "compression": {"compression": {"algorithm": "int8",
                                    "error_feedback": True, "block": 10}},
    "dmtt": {"dmtt": {"budget_B": 3, "rho": 0.1, "lambda_forget": 0.9,
                      "w_a": 0.7, "tau_U": 0.3, "eta": 5.0,
                      "allow_static": True}},
    "faults": {"faults": {"enabled": True, "seed": 777,
                          "straggler_prob": 0.3, "link_drop_prob": 0.2}},
    "mobility": {"mobility": {"area_size": 100.0, "comm_range": 60.0,
                              "max_speed": 5.0, "seed": 42,
                              "ensure_connected": True}},
    "pipeline": {"exchange": {"pipeline": True}},
    "population": {"population": {"enabled": True, "virtual_size": 32,
                                  "sampler": "stratified", "seed": 3,
                                  "rounds_per_cohort": 1}},
    "sharding": {"backend": "tpu", "tpu": {"param_shards": 2}},
    "sparse": {"topology": {"type": "exponential", "num_nodes": 8}},
    "staleness": {"exchange": {"max_staleness": 2,
                               "staleness_discount": 0.7},
                  "faults": {"enabled": True, "seed": 777,
                             "straggler_prob": 0.3}},
    "sweep": {"sweep": {"num_seeds": 2}},
}

# Pair-specific adjustments that keep a CONSTRAINED pair outside its
# refused sub-configuration when the plain armer union would hit it.
_PAIR_OVERRIDES: Dict[Tuple[str, str], Dict[str, Any]] = {
    # carried_state: error feedback is per-slot carried state; the
    # population cell arms the stateless int8 codec.
    ("compression", "population"): {"compression": {"error_feedback": False}},
}


def pair_raw(a: str, b: str) -> Dict[str, Any]:
    """The raw config of the (a, b) grid cell: base + both armers."""
    raw = copy.deepcopy(_BASE_RAW)
    earlier, later = sorted((a, b))
    raw = _deep_merge(raw, LEVER_ARMERS[earlier])
    raw = _deep_merge(raw, LEVER_ARMERS[later])
    raw = _deep_merge(raw, _PAIR_OVERRIDES.get((earlier, later), {}))
    return raw


def _validate(raw: Dict[str, Any]):
    from murmura_tpu.config import Config

    return Config.model_validate(raw)


def _build_cell(cfg):
    """(driver, is_gang) for one validated cell config."""
    from murmura_tpu.utils.factories import (
        build_gang_from_config,
        build_network_from_config,
    )

    if cfg.sweep is not None:
        return build_gang_from_config(cfg), True
    return build_network_from_config(cfg), False


def _histories(driver, is_gang) -> List[Dict[str, List[Any]]]:
    return list(driver.histories) if is_gang else [driver.history]


# --------------------------------------------------------------------------
# Trace helpers (shared by MUR1401 parity and MUR1402 stage order)
# --------------------------------------------------------------------------


def _trace_program(prog):
    """Closed jaxpr of one round program's ``train_step`` over canonical
    inputs (dense or [k, N] sparse adjacency; the faulted signature
    carries the extra alive mask)."""
    import jax
    import jax.numpy as jnp

    n = prog.num_nodes
    if prog.sparse:
        adj = jnp.ones((len(prog.sparse_offsets), n), jnp.float32)
    else:
        adj = jnp.asarray(
            np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
        )
    args = [
        prog.init_params,
        {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
        jax.random.PRNGKey(0),
        adj,
        jnp.zeros((n,), jnp.float32),
    ]
    if prog.faulted:
        args.append(jnp.ones((n,), jnp.float32))
    args.append(jnp.asarray(0.0, jnp.float32))
    args.append({k: jnp.asarray(v) for k, v in prog.data_arrays.items()})
    return jax.make_jaxpr(prog.train_step)(*args)


def _trace_collectives(closed) -> frozenset:
    from murmura_tpu.analysis.adaptive import _COLLECTIVE_PRIMS
    from murmura_tpu.analysis.ir import iter_eqns

    return frozenset(
        e.primitive.name for e in iter_eqns(closed)
        if e.primitive.name in _COLLECTIVE_PRIMS
    )


def _trace_stages(closed) -> List[str]:
    """First-occurrence order of ``murmura.*`` named_scope labels in one
    traced program (core/rounds.py stage brackets)."""
    from murmura_tpu.analysis.ir import iter_eqns

    seen: List[str] = []
    for e in iter_eqns(closed):
        stack = getattr(e.source_info, "name_stack", None)
        if stack is None:
            continue
        for part in str(stack).split("/"):
            if part.startswith("murmura.") and part not in seen:
                seen.append(part)
    return seen


_SINGLE_MEMO: Dict[str, Any] = {}


def _single_program(lever: str, override: Optional[Dict[str, Any]] = None):
    """The single-lever round program (memoized), or None for levers
    whose arming never reaches the traced program.  ``override`` is the
    pair's constrained-arming patch (_PAIR_OVERRIDES) so the baseline
    matches the composed cell's sub-configuration."""
    if lever not in _PROGRAM_LEVERS:
        return None
    key = lever if not override else (
        lever + "|" + json.dumps(override, sort_keys=True)
    )
    if key not in _SINGLE_MEMO:
        raw = _deep_merge(copy.deepcopy(_BASE_RAW), LEVER_ARMERS[lever])
        if override:
            raw = _deep_merge(raw, override)
        net, _ = _build_cell(_validate(raw))
        _SINGLE_MEMO[key] = net.program
    return _SINGLE_MEMO[key]


_BASE_MEMO: Dict[str, Any] = {}


def _base_program():
    if "base" not in _BASE_MEMO:
        net, _ = _build_cell(_validate(copy.deepcopy(_BASE_RAW)))
        _BASE_MEMO["base"] = net.program
    return _BASE_MEMO["base"]


# --------------------------------------------------------------------------
# MUR1400 — manifest <-> schema/guard bijection
# --------------------------------------------------------------------------


@_family
def check_manifest_bijection() -> List[Finding]:
    """MUR1400 (structural): the LEVER_MODULES registry, the AST-scan
    discovery, the reserved state-key-group registry, the stage labels
    and the mesh-axis names must agree with the loaded manifests."""
    from murmura_tpu.durability.snapshot import (
        RESERVED_AGG_STATE_KEY_GROUPS,
        resolve_reserved_agg_state_keys,
    )

    findings: List[Finding] = []
    manifests = lever_manifests()

    found = discover_lever_manifests(_PKG)
    declared_mods = set(LEVER_MODULES.values())
    for mod in sorted(declared_mods - set(found)):
        findings.append(Finding(
            "MUR1400", _LEVERS_PATH, 1,
            f"LEVER_MODULES names {mod} but no module-level "
            "LEVER_MANIFEST assignment was discovered there — the "
            "registry row is stale",
        ))
    for mod in sorted(set(found) - declared_mods):
        findings.append(Finding(
            "MUR1400", found[mod], 1,
            f"module {mod} declares a LEVER_MANIFEST that is not in the "
            "levers.LEVER_MODULES registry — register the lever so the "
            "composition grid covers it",
        ))

    reserved = resolve_reserved_agg_state_keys()
    claimed = {
        m.state_keys_group: name for name, m in manifests.items()
        if m.state_keys_group is not None
    }
    for group in sorted(set(claimed) - set(reserved)):
        path, line = _manifest_anchor(claimed[group])
        findings.append(Finding(
            "MUR1400", path, line,
            f"lever '{claimed[group]}' claims state-key group "
            f"'{group}' which RESERVED_AGG_STATE_KEY_GROUPS does not "
            "register (durability/snapshot.py)",
        ))
    for group in sorted(set(reserved) - set(claimed)):
        findings.append(Finding(
            "MUR1400", _LEVERS_PATH, 1,
            f"reserved state-key group '{group}' "
            f"({RESERVED_AGG_STATE_KEY_GROUPS[group]}) is claimed by no "
            "lever manifest — carried state with no composition owner",
        ))

    for name, m in sorted(manifests.items()):
        path, line = _manifest_anchor(name)
        if m.stage is not None and m.stage not in STAGE_ORDER:
            findings.append(Finding(
                "MUR1400", path, line,
                f"lever '{name}' declares stage {m.stage!r} which is "
                "not a STAGE_ORDER label (levers.py)",
            ))
        bad_axes = [ax for ax in m.mesh_axes
                    if ax not in ("seed", "nodes", "param")]
        if bad_axes:
            findings.append(Finding(
                "MUR1400", path, line,
                f"lever '{name}' declares mesh axes {bad_axes} outside "
                "the (seed, nodes, param) mesh vocabulary "
                "(parallel/mesh.py)",
            ))
    return findings


# Phrases that mark a hand-written refusal message.  A guard literal
# containing one of these OUTSIDE a refusal_reason(...) citation is an
# undeclared refusal — the bypass MUR1400 exists to catch.
_REFUSAL_PHRASES: Tuple[str, ...] = (
    "does not compose", "do not compose", "not gang-batchable",
)


def _cited_refusals(src: str, path: str):
    """(citations, findings) from one guard module's source: every
    ``refusal_reason(...)`` call with literal arguments resolved to its
    (earlier, later, constraint|None) key, plus findings for dynamic
    citations and for refusal-phrase literals outside any citation."""
    findings: List[Finding] = []
    cited: List[Tuple[str, str, Optional[str]]] = []
    tree = ast.parse(src, filename=path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        fname = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if fname != "refusal_reason":
            continue
        lits = [
            a.value for a in node.args
            if isinstance(a, ast.Constant) and isinstance(a.value, str)
        ]
        if len(lits) != len(node.args) or len(lits) not in (2, 3):
            findings.append(Finding(
                "MUR1400", path, node.lineno,
                "refusal_reason(...) cited with non-literal arguments — "
                "the manifest bijection cannot be verified statically; "
                "cite lever names as string literals",
            ))
            continue
        a, b = sorted(lits[:2])
        key = (a, b, lits[2] if len(lits) == 3 else None)
        cited.append(key)
        if key not in set(declared_refusals()):
            findings.append(Finding(
                "MUR1400", path, node.lineno,
                f"guard cites refusal_reason{tuple(lits)} but the "
                "manifests declare no such refusal — an undeclared "
                "refusal (or a stale citation after a lift)",
            ))
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            low = node.value.lower()
            if any(p in low for p in _REFUSAL_PHRASES):
                findings.append(Finding(
                    "MUR1400", path, node.lineno,
                    "refusal-shaped literal (contains "
                    f"{[p for p in _REFUSAL_PHRASES if p in low]!r}) is "
                    "not routed through refusal_reason(...) — an "
                    "undeclared cross-feature refusal bypassing the "
                    "manifest grid",
                ))
    return cited, findings


def refusal_guard_findings(
    schema_src: Optional[str] = None,
    factories_src: Optional[str] = None,
) -> List[Finding]:
    """MUR1400 (guard sites): every citation resolves to a declared
    verdict; every declared refusal is cited by at least one guard.
    ``schema_src``/``factories_src`` are injectable so negative tests
    drive the probes with doctored sources (tests/test_composition.py)."""
    if schema_src is None:
        schema_src = Path(_SCHEMA_PATH).read_text()
    if factories_src is None:
        factories_src = Path(_FACTORIES_PATH).read_text()
    findings: List[Finding] = []
    cited: List[Tuple[str, str, Optional[str]]] = []
    for src, path in (
        (schema_src, _SCHEMA_PATH), (factories_src, _FACTORIES_PATH),
    ):
        c, f = _cited_refusals(src, path)
        cited.extend(c)
        findings.extend(f)
    for key in sorted(set(declared_refusals()) - set(cited),
                      key=lambda k: (k[0], k[1], k[2] or "")):
        a, b, tag = key
        path, line = _pair_anchor(a, b)
        findings.append(Finding(
            "MUR1400", path, line,
            f"manifest declares refusal ({a}, {b}"
            + (f", {tag!r})" if tag else ")")
            + " but no guard site in config/schema.py or "
            "utils/factories.py cites it — a stale declaration (lift "
            "the verdict) or a missing guard (users hit the refused "
            "combination at runtime instead of validation)",
        ))
    return findings


# The executable refusal census: for every declared refusal, a raw
# config that arms exactly the refused combination ("arm" pulls lever
# armers, "extra" patches on top) and the layer whose guard must fire.
# MUR1400 runs each and requires the declared reason verbatim in the
# raised error — the message a user sees IS the manifest's verdict.
REFUSAL_CONFIGS: Dict[Tuple[str, str, Optional[str]], Dict[str, Any]] = {
    ("adaptive", "dmtt", None): {"via": "schema",
                                 "arm": ("adaptive", "dmtt")},
    ("adaptive", "pipeline", None): {"via": "schema",
                                     "arm": ("adaptive", "pipeline")},
    ("compression", "dmtt", None): {"via": "schema",
                                    "arm": ("compression", "dmtt")},
    ("compression", "population", "carried_state"): {
        "via": "schema", "arm": ("compression", "population"),
        "extra": {"compression": {"error_feedback": True}},
    },
    ("compression", "sharding", "topk"): {
        "via": "schema", "arm": ("compression", "sharding"),
        "extra": {"compression": {"algorithm": "topk",
                                  "topk_ratio": 0.1}},
    },
    ("compression", "sharding", "int8_block"): {
        # Block 48 does not divide the 50-wide shard-local flat width —
        # the guard lives where the model dim is known
        # (utils/factories.py).
        "via": "network", "arm": ("compression", "sharding"),
        "extra": {"compression": {"block": 48}},
    },
    ("dmtt", "mobility", "requires_mobility"): {
        "via": "schema", "arm": ("dmtt",),
        "extra": {"dmtt": {"allow_static": False}},
    },
    ("dmtt", "pipeline", None): {"via": "schema",
                                 "arm": ("dmtt", "pipeline")},
    ("dmtt", "population", None): {"via": "schema",
                                   "arm": ("dmtt", "population")},
    ("dmtt", "sharding", None): {"via": "schema",
                                 "arm": ("dmtt", "sharding")},
    ("dmtt", "sparse", None): {"via": "schema",
                               "arm": ("dmtt", "sparse")},
    ("dmtt", "staleness", None): {"via": "schema",
                                  "arm": ("dmtt", "staleness")},
    ("faults", "staleness", "requires_faults"): {
        "via": "schema", "arm": (),
        "extra": {"exchange": {"max_staleness": 2,
                               "staleness_discount": 0.7}},
    },
    ("mobility", "sparse", None): {"via": "schema",
                                   "arm": ("mobility", "sparse")},
    ("mobility", "staleness", None): {"via": "schema",
                                      "arm": ("mobility", "staleness")},
    ("pipeline", "population", None): {"via": "schema",
                                       "arm": ("pipeline", "population")},
    ("population", "sharding", None): {"via": "schema",
                                       "arm": ("population", "sharding")},
    ("population", "staleness", None): {"via": "schema",
                                        "arm": ("population", "staleness")},
    ("population", "sweep", None): {"via": "schema",
                                    "arm": ("population", "sweep")},
    ("sparse", "staleness", "one_peer"): {
        "via": "schema", "arm": ("staleness",),
        "extra": {"topology": {"type": "one_peer", "num_nodes": 8}},
    },
    ("sparse", "sweep", "tpu_backend"): {
        "via": "gang", "arm": ("sparse", "sweep"),
        "extra": {"backend": "tpu"},
    },
}


def _census_raw(entry: Dict[str, Any]) -> Dict[str, Any]:
    raw = copy.deepcopy(_BASE_RAW)
    for lever in entry.get("arm", ()):
        raw = _deep_merge(raw, LEVER_ARMERS[lever])
    return _deep_merge(raw, entry.get("extra", {}))


def census_cell_findings(
    key: Tuple[str, str, Optional[str]], entry: Dict[str, Any],
) -> List[Finding]:
    """Arm ONE declared refusal's combination and require its guard to
    fire with the manifest's reason verbatim."""
    a, b, tag = key
    path, line = _pair_anchor(a, b)
    reason = refusal_reason(a, b, tag)
    raw = _census_raw(entry)
    try:
        cfg = _validate(raw)
        if entry["via"] == "network":
            from murmura_tpu.utils.factories import build_network_from_config

            build_network_from_config(cfg)
        elif entry["via"] == "gang":
            from murmura_tpu.utils.factories import build_gang_from_config

            build_gang_from_config(cfg)
        elif entry["via"] != "schema":
            raise ValueError(f"unknown census layer {entry['via']!r}")
    except Exception as e:  # noqa: BLE001 — the raise IS the contract
        if reason not in str(e):
            return [Finding(
                "MUR1400", path, line,
                f"census ({a}, {b}" + (f", {tag!r})" if tag else ")")
                + f" raised via {entry['via']} but the error does not "
                "carry the manifest's declared reason verbatim — the "
                "guard message and the declaration have diverged: "
                f"{type(e).__name__}: {str(e)[:300]}",
            )]
        return []
    return [Finding(
        "MUR1400", path, line,
        f"census ({a}, {b}" + (f", {tag!r})" if tag else ")")
        + f" armed the refused combination via {entry['via']} and no "
        "guard fired — a stale refusal declaration (lift it) or a "
        "fail-loud guard that silently degraded",
    )]


@_family
def check_refusal_census() -> List[Finding]:
    """MUR1400 (executable): the census covers every declared refusal,
    every entry's guard fires with the declared reason, and the
    committed COMPOSITION.json matches the live grid."""
    findings: List[Finding] = list(refusal_guard_findings())
    declared = set(declared_refusals())
    census = set(REFUSAL_CONFIGS)
    for a, b, tag in sorted(
        declared - census, key=lambda k: (k[0], k[1], k[2] or "")
    ):
        path, line = _pair_anchor(a, b)
        findings.append(Finding(
            "MUR1400", path, line,
            f"declared refusal ({a}, {b}"
            + (f", {tag!r})" if tag else ")")
            + " has no REFUSAL_CONFIGS census entry — add the arming "
            "raw config so the guard is executed, not just grepped",
        ))
    for a, b, tag in sorted(
        census - declared, key=lambda k: (k[0], k[1], k[2] or "")
    ):
        findings.append(Finding(
            "MUR1400", str(Path(__file__).resolve()), 1,
            f"census entry ({a}, {b}" + (f", {tag!r})" if tag else ")")
            + " matches no declared refusal — remove it (the pair was "
            "lifted) or declare the verdict",
        ))
    for key in sorted(census & declared,
                      key=lambda k: (k[0], k[1], k[2] or "")):
        try:
            findings.extend(census_cell_findings(key, REFUSAL_CONFIGS[key]))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            a, b, tag = key
            path, line = _pair_anchor(a, b)
            findings.append(Finding(
                "MUR1400", path, line,
                f"census ({a}, {b}" + (f", {tag!r})" if tag else ")")
                + f" probe crashed: {type(e).__name__}: {e}",
            ))
    findings.extend(_census_drift_findings())
    return findings


def census_snapshot() -> Dict[str, Any]:
    """The live census in COMPOSITION.json's committed shape."""
    refusals = [
        [a, b] for a, b, tag in declared_refusals() if tag is None
    ]
    constrained = [
        [a, b, tag] for a, b, tag in declared_refusals() if tag is not None
    ]
    return {
        "refusals": refusals,
        "constrained": constrained,
        "compatible_pairs": [[a, b] for a, b in compatible_pairs()],
    }


def _census_drift_findings() -> List[Finding]:
    path = str(COMPOSITION_JSON)
    if not COMPOSITION_JSON.exists():
        return [Finding(
            "MUR1400", path, 1,
            "analysis/COMPOSITION.json is missing — commit the refusal "
            "census (murmura check --compose regenerates the snapshot)",
        )]
    committed = json.loads(COMPOSITION_JSON.read_text())
    live = census_snapshot()
    findings: List[Finding] = []
    for field in ("refusals", "constrained", "compatible_pairs"):
        if committed.get(field) != live[field]:
            findings.append(Finding(
                "MUR1400", path, 1,
                f"COMPOSITION.json '{field}' "
                f"({len(committed.get(field, []))} entries) diverges "
                f"from the live manifests ({len(live[field])}) — "
                "lifting or refusing a pair must move the committed "
                "census in the same diff",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1401 + MUR1402 — the generated pairwise grid
# --------------------------------------------------------------------------

# The lifted pair whose cell pins the 3-axis gang mesh (ISSUE 16).
LIFTED_PAIRS: Tuple[Tuple[str, str], ...] = (("sharding", "sweep"),)

_COMPOSE_SUMMARIES: List[Dict[str, Any]] = []


def compose_summaries() -> List[Dict[str, Any]]:
    """Machine-readable grid rows for ``check --json`` (one
    ``{"kind": "compose_summary", ...}`` per pair, refusals included) —
    the flow_summaries() twin.  Populated by check_composition_grid."""
    return list(_COMPOSE_SUMMARIES)


def _lifted_cell_findings(gang, raw) -> List[Finding]:
    """Extra probes for the sharding x sweep cell: the gang mesh carries
    all three axes with a real param extent, and the cell is
    rebuild-deterministic (the sharded lowering's RNG placement makes
    cross-mesh bit-parity meaningless; determinism of the SAME composed
    build is the parity contract that remains)."""
    from murmura_tpu.utils.factories import build_gang_from_config

    path, line = _pair_anchor("sharding", "sweep")
    findings: List[Finding] = []
    mesh = gang.mesh
    if tuple(mesh.axis_names) != ("seed", "nodes", "param"):
        findings.append(Finding(
            "MUR1401", path, line,
            f"[sharding x sweep] the lifted gang mesh carries axes "
            f"{tuple(mesh.axis_names)} instead of "
            "('seed', 'nodes', 'param') — the composed cell did not "
            "take the 3-axis layout",
        ))
        return findings
    if dict(mesh.shape).get("param", 1) <= 1:
        findings.append(Finding(
            "MUR1401", path, line,
            "[sharding x sweep] the lifted gang mesh has a degenerate "
            "param axis — the cell must actually shard the flat width "
            "(needs >= 8 host devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
        ))
        return findings
    losses = []
    for _ in range(2):
        g = build_gang_from_config(_validate(copy.deepcopy(raw)))
        g.train(rounds=2, verbose=False)
        losses.append(np.asarray(
            [h["mean_loss"][-1] for h in g.histories], np.float64
        ))
    if not np.array_equal(losses[0], losses[1]):
        findings.append(Finding(
            "MUR1401", path, line,
            "[sharding x sweep] two identical builds of the lifted "
            "cell diverge after 2 rounds "
            f"({losses[0].tolist()} vs {losses[1].tolist()}) — the "
            "composed sharded sweep is not rebuild-deterministic",
        ))
    return findings


def grid_cell_findings(a: str, b: str) -> List[Finding]:
    """One declared-compatible pair's composed cell: builds from config,
    trains recompile-free with finite metrics (MUR1401), keeps
    collective-inventory parity with the single-armed programs
    (MUR1401), and carries the union of their state keys with stage
    labels in STAGE_ORDER order (MUR1402).  Exposed per-cell so tests
    gate a subset (tests/test_composition.py)."""
    from murmura_tpu.analysis.sanitizers import track_compiles

    a, b = sorted((a, b))
    path, line = _pair_anchor(a, b)
    findings: List[Finding] = []
    raw = pair_raw(a, b)
    try:
        cfg = _validate(raw)
    except Exception as e:  # noqa: BLE001 — the refusal IS the finding
        return [Finding(
            "MUR1401", path, line,
            f"[{a} x {b}] declared composes() but the composed config "
            f"refuses at validation — a stale composes() declaration: "
            f"{type(e).__name__}: {str(e)[:300]}",
        )]
    try:
        driver, is_gang = _build_cell(cfg)
    except Exception as e:  # noqa: BLE001
        return [Finding(
            "MUR1401", path, line,
            f"[{a} x {b}] declared composes() but the composed build "
            f"crashed: {type(e).__name__}: {str(e)[:300]}",
        )]

    driver.train(rounds=2, verbose=False)
    with track_compiles() as tracker:
        driver.train(rounds=2, verbose=False)
    if tracker.total:
        findings.append(Finding(
            "MUR1401", path, line,
            f"[{a} x {b}] 2 composed rounds after warmup compiled "
            f"{tracker.total} program(s) — arming two levers together "
            "must stay value-only over one compiled program",
        ))
    for h in _histories(driver, is_gang):
        tail = h.get("mean_loss", [])
        if not tail or not np.isfinite(np.asarray(tail, np.float64)).all():
            findings.append(Finding(
                "MUR1401", path, line,
                f"[{a} x {b}] the composed cell's mean_loss history is "
                f"missing or non-finite ({tail[-3:] if tail else []}) — "
                "the pair composes structurally but not numerically",
            ))
            break

    prog = getattr(driver, "program", None)
    if prog is not None and not is_gang:
        closed = _trace_program(prog)
        override = _PAIR_OVERRIDES.get((a, b))
        # -- MUR1401: collective-inventory parity --------------------
        allowed = _trace_collectives(_trace_program(_base_program()))
        for lever in (a, b):
            single = _single_program(lever, override)
            if single is not None:
                allowed = allowed | _trace_collectives(
                    _trace_program(single)
                )
        stray = _trace_collectives(closed) - allowed
        if stray:
            findings.append(Finding(
                "MUR1401", path, line,
                f"[{a} x {b}] the composed trace contains "
                f"collective(s) {sorted(stray)} that neither "
                "single-armed program lowers — composition grew a new "
                "distributed algorithm",
            ))
        # -- MUR1402: composed state is the union of the singles -----
        composed_keys = set(prog.init_agg_state)
        # Declared buffer reuse (core/pipeline.pipeline_state_keys):
        # with bounded staleness armed the pipeline's broadcast buffer
        # IS the stale fold's payload cache, so pipe_bcast is dropped
        # by contract rather than silently disarmed.
        reused: set = set()
        if getattr(prog, "pipelined", False) and prog.stale:
            from murmura_tpu.core.pipeline import (
                PIPELINE_STATE_KEYS,
                pipeline_state_keys,
            )

            reused = set(PIPELINE_STATE_KEYS) - set(
                pipeline_state_keys(stale=True)
            )
        for lever in (a, b):
            single = _single_program(lever, override)
            if single is None:
                continue
            missing = set(single.init_agg_state) - composed_keys - reused
            if missing:
                findings.append(Finding(
                    "MUR1402", path, line,
                    f"[{a} x {b}] the composed agg_state drops "
                    f"{sorted(missing)} that the '{lever}'-only "
                    "program carries — arming a second lever silently "
                    "disarmed the first",
                ))
        # -- MUR1402: stage hooks present and in STAGE_ORDER order ----
        stages = _trace_stages(closed)
        order = {s: i for i, s in enumerate(STAGE_ORDER)}
        checked = stages
        if getattr(prog, "pipelined", False) \
                and checked[:1] == ["murmura.aggregate"]:
            # A pipelined program drains round r-1's delayed aggregation
            # at the top of round r — the double-buffer rotation IS the
            # lever's contract (core/pipeline.py); the rest of the round
            # must still follow STAGE_ORDER.
            checked = checked[1:]
        idx = [order[s] for s in checked if s in order]
        if idx != sorted(idx):
            findings.append(Finding(
                "MUR1402", path, line,
                f"[{a} x {b}] the composed trace's stage labels "
                f"first-occur as {stages} — out of the declared "
                f"STAGE_ORDER; core/rounds.py and levers.py disagree "
                "about hook ordering",
            ))
        for lever in (a, b):
            want = _SCOPED_STAGES.get(lever)
            if want is not None and want not in stages:
                findings.append(Finding(
                    "MUR1402", path, line,
                    f"[{a} x {b}] lever '{lever}' declares stage "
                    f"{want!r} but the composed trace opens no such "
                    "bracket — the hook is disarmed or the manifest "
                    "stage is stale",
                ))

    _COMPOSE_SUMMARIES.append({
        "kind": "compose_summary",
        "pair": [a, b],
        "verdict": "composes",
        "constraints": [t for t, _ in pair_verdict(a, b).constraints],
        "cell": "gang" if is_gang else "network",
        "recompiles": int(tracker.total),
        "clean": not findings,
    })
    return findings


@_family
def check_composition_grid() -> List[Finding]:
    """MUR1401/MUR1402 over every declared-compatible pair (compiles and
    runs one tiny composed program per pair — the check_durability cost
    profile at grid scale)."""
    from murmura_tpu.analysis.ir import _ensure_host_devices

    _ensure_host_devices(8)
    _COMPOSE_SUMMARIES.clear()
    for a, b, tag in declared_refusals():
        if tag is None:
            _COMPOSE_SUMMARIES.append({
                "kind": "compose_summary", "pair": [a, b],
                "verdict": "refuses", "reason": refusal_reason(a, b),
            })
    findings: List[Finding] = []
    for a, b in compatible_pairs():
        try:
            findings.extend(grid_cell_findings(a, b))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _pair_anchor(a, b)
            findings.append(Finding(
                "MUR1401", path, line,
                f"[{a} x {b}] composed grid cell crashed: "
                f"{type(e).__name__}: {e}",
            ))
    for a, b in LIFTED_PAIRS:
        try:
            raw = pair_raw(a, b)
            gang, _ = _build_cell(_validate(raw))
            findings.extend(_lifted_cell_findings(gang, raw))
        except Exception as e:  # noqa: BLE001
            path, line = _pair_anchor(a, b)
            findings.append(Finding(
                "MUR1401", path, line,
                f"[{a} x {b}] lifted-cell probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return findings


@_family
def check_composed_state() -> List[Finding]:
    """MUR1402 (global): every pair of reserved state-key groups is
    disjoint — two levers riding the same agg_state key cannot compose
    under any verdict."""
    from murmura_tpu.durability.snapshot import (
        resolve_reserved_agg_state_keys,
    )

    resolved = resolve_reserved_agg_state_keys()
    findings: List[Finding] = []
    groups = sorted(resolved)
    for i, g1 in enumerate(groups):
        for g2 in groups[i + 1:]:
            clash = set(resolved[g1]) & set(resolved[g2])
            if clash:
                findings.append(Finding(
                    "MUR1402", _LEVERS_PATH, 1,
                    f"reserved state-key groups {g1} and {g2} both "
                    f"claim {sorted(clash)} — composed programs would "
                    "overwrite one lever's carried state with the "
                    "other's",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1403 — flow-taint preservation on composed cells
# --------------------------------------------------------------------------

# (mode, rule) composed taint cells.  The compressed+stale cell runs
# both bounded archetypes; the sparse+stale cell runs krum, whose
# declared bound is degree-invariant — the [k, N] fault surgery changes
# per-receiver degrees in a direction-dependent way the probe does not
# reconstruct, and a constant bound makes that reconstruction moot.
COMPOSED_TAINT_CELLS: Tuple[Tuple[str, str], ...] = (
    ("compressed_stale", "krum"),
    ("compressed_stale", "median"),
    ("sparse_stale", "krum"),
)


def _composed_stale_cell(rule: str, mode: str, fold_factory=None):
    """The staleness Probe cell with a second lever in the loop:
    ``compressed_stale`` round-trips the broadcast through the int8
    codec before the stale fold (the core/rounds.py compress->stale
    ordering); ``sparse_stale`` runs the [k, N] sparse cell through the
    sparse-mode fold."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.flow import (
        FLOW_BLOCK,
        _flow_offsets,
        _quiet_tracing,
        build_flow_cell,
    )
    from murmura_tpu.analysis.staleness import (
        _EXPIRED_SENDER,
        _SCRUBBED_SENDER,
        _STALE_SENDER,
    )
    from murmura_tpu.core.stale import (
        AGE_KEY,
        CACHE_KEY,
        StalenessSpec,
        make_stale_fold,
    )
    from murmura_tpu.ops.compress import quantize_int8

    cell = build_flow_cell(rule, "sparse" if mode == "sparse_stale"
                           else "dense")
    n = cell.n
    own, bcast, adj0 = cell.args[0], cell.args[1], cell.args[2]
    base = np.asarray(adj0, np.float32)
    spec = StalenessSpec(max_staleness=2, discount=0.5, base_mask=base)
    offsets = _flow_offsets(n) if mode == "sparse_stale" else ()
    fold = (fold_factory or make_stale_fold)(spec, sparse_offsets=offsets)

    adj_f = base.copy()
    for s in (_STALE_SENDER, _SCRUBBED_SENDER, _EXPIRED_SENDER):
        adj_f[:, s] = 0.0  # dense rows or [k, N] offsets: same surgery
    scrub_np = np.ones((n,), np.float32)
    scrub_np[_SCRUBBED_SENDER] = 0.0
    age_np = np.zeros((n,), np.float32)
    age_np[_EXPIRED_SENDER] = spec.age_cap
    rng = np.random.default_rng(1)
    cache_np = np.asarray(rng.normal(size=bcast.shape) * 0.1, np.float32)
    alive = jnp.ones((n,), jnp.float32)
    scrub_ok = jnp.asarray(scrub_np)

    cell_fn = cell.fn
    rest = tuple(cell.args[3:])
    compressed = mode == "compressed_stale"

    def fn(own_a, bcast_a, adj_a, cache_a, age_a, *rest_a):  # murmura: traced
        if compressed:
            bcast_a = quantize_int8(bcast_a, FLOW_BLOCK).dequantize()
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


def composed_taint_findings(
    mode: str, rule: str, fold_factory=None,
) -> List[Finding]:
    """Probe A over one composed cell: with the broadcast AND cache
    seeded, bounded rules keep their MUR800-declared per-coordinate
    influence cardinality although a second lever (codec or [k, N]
    masks) stands between exchange and rule math."""
    from murmura_tpu.analysis.ir import _rule_anchor
    from murmura_tpu.analysis.staleness import _STALE_SENDER, _taint_run

    path, line = _rule_anchor(rule)
    cell, closed, args, adj_f, base = _composed_stale_cell(
        rule, mode, fold_factory
    )
    n = cell.n
    out_t, _cache_t = _taint_run(
        closed, args, n, seed_bcast=True, seed_cache=True
    )
    influence = cell.agg.influence
    if influence is None or influence.kind != "bounded":
        return [Finding(
            "MUR1403", path, line,
            f"[{rule}/{mode}] composed taint cell ran on a rule "
            "without a bounded influence declaration — the probe is "
            "vacuous; pick a bounded rule for COMPOSED_TAINT_CELLS",
        )]
    findings: List[Finding] = []
    per_coord = out_t.sum(axis=0)  # [N, P] distinct-label counts
    self_t = out_t[np.arange(n), np.arange(n)]
    card_i = (per_coord - self_t).max(axis=1)  # [N]
    if mode == "sparse_stale":
        # [k, N] masks: per-receiver degree is offset-direction
        # dependent; restrict to degree-invariant bounds (see
        # COMPOSED_TAINT_CELLS) and use the full-degree bound.
        bounds = {influence.bound(d) for d in range(1, n)}
        if len(bounds) != 1:
            return [Finding(
                "MUR1403", path, line,
                f"[{rule}/{mode}] the sparse composed cell needs a "
                "degree-invariant influence bound but "
                f"'{rule}' declares {sorted(bounds)} over degrees "
                "1..n-1 — move the rule to the compressed cell",
            )]
        bound = bounds.pop()
        for i in range(n):
            if int(card_i[i]) > bound:
                findings.append(Finding(
                    "MUR1403", path, line,
                    f"[{rule}/{mode}] the composed sparse+stale step "
                    f"mixes {int(card_i[i])} neighbors into receiver "
                    f"{i}'s output coordinate but the rule declares a "
                    f"degree-invariant bound of {bound} — the second "
                    "lever widened the rule's per-coordinate influence",
                ))
        return findings
    eff = adj_f > 0
    eff[:, _STALE_SENDER] |= base[:, _STALE_SENDER] > 0
    for i in range(n):
        bound = influence.bound(int(eff[i].sum()))
        if int(card_i[i]) > bound:
            findings.append(Finding(
                "MUR1403", path, line,
                f"[{rule}/{mode}] the composed compress+stale step "
                f"mixes {int(card_i[i])} neighbors into receiver "
                f"{i}'s output coordinate but the rule declares a "
                f"bound of {bound} at its effective degree "
                f"{int(eff[i].sum())} — the codec round-trip widened "
                "the rule's per-coordinate influence",
            ))
    return findings


@_family
def check_composed_taint() -> List[Finding]:
    """MUR1403 over the composed taint cells (trace-only)."""
    findings: List[Finding] = []
    for mode, rule in COMPOSED_TAINT_CELLS:
        try:
            findings.extend(composed_taint_findings(mode, rule))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            from murmura_tpu.analysis.ir import _rule_anchor

            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1403", path, line,
                f"[{rule}/{mode}] composed taint probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_COMPOSITION_MEMO: Optional[List[Finding]] = None


def check_composition(force: bool = False) -> List[Finding]:
    """Run MUR1400-1403; returns findings (empty = the declared grid and
    the shipped code agree everywhere).  Memoized per process — the CLI
    and the test gate share one sweep."""
    global _COMPOSITION_MEMO
    if _COMPOSITION_MEMO is not None and not force:
        return list(_COMPOSITION_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in COMPOSE_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1400", str(Path(__file__).resolve()), 1,
                f"composition check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _COMPOSITION_MEMO = list(findings)
    return findings
