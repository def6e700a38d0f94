"""Static memory contracts (MUR1500-1503) — part of the default package
check (docs/ANALYSIS.md "Memory contracts", docs/PERFORMANCE.md "Memory
footprint").

ROADMAP items 4 and 5 stand on claims the repo could not verify off-chip:
that a big sharded model *fits* (peak HBM scales as P/shards) and that
pipelined rounds *overlap* (aggregation is dependence-independent of the
round's training).  This family makes both compile-time contract
evidence, the way MUR206 made FLOPs/bytes reviewable perf history:

- **MUR1500 — peak-HBM accounting.**  Every (rule x dense/circulant/
  sparse x plain/int8+EF/stale/pipeline) round-program cell is
  AOT-lowered and ``compile().memory_analysis()`` (temp/argument/output/
  generated, flattened by
  :func:`normalize_memory_analysis` — the memory twin of
  ``normalize_cost_analysis``) is gated against the committed
  ``analysis/MEMORY.json`` within tolerance.  A change that silently
  doubles a round program's live footprint is a finding, not a surprise
  on the chip; ``murmura check --update-memory`` rewrites the file so the
  diff itself is reviewable residency history (the BUDGETS.json
  etiquette).
- **MUR1501 — sharded scaling law.**  For param-sharded cells, the
  per-device peak must shrink ~P/shards across shards in {1, 2, 4}: with
  d12 = peak(1) - peak(2) and d24 = peak(2) - peak(4), the sharded
  [N, P]-class bytes satisfy d12 ~ 2 x d24 (fixed overhead cancels in
  the differences) and the 4-shard peak drops below a declared fraction
  of the unsharded peak.  This statically verifies the PR 15 residency
  claim.
- **MUR1502 — donation completeness by leaf.**  Walk the
  ``input_output_alias`` header of each compiled cell: every carried
  leaf — params plus every ``*_STATE_KEYS`` group in the MUR900
  registry (EF residual, top-k reference, stale cache + ages, pipeline
  buffers, attack/trust state) — must be aliased, and a finding names
  the unaliased leaf and its key group (an undonated [N, P] carry
  doubles peak; MUR204's alias *count* cannot say which).  A leaf jax
  prunes as unused before XLA (a dead carry with no executable buffer)
  is exempt by construction — :func:`entry_param_numbers` maps the
  surviving leaves onto XLA's post-pruning parameter order.  Extra
  donation-only cells (top-k, adaptive attack, DMTT) cover the key
  groups the MUR1500 feature grid does not arm.
- **MUR1503 — overlap-dependence.**  Build the def-use graph of the
  optimized HLO (call-site-qualified across fusions/calls/while bodies,
  collectives included) and prove the pipelined program's buffered-
  aggregation subgraph (``murmura.aggregate`` scope metadata) has no
  dependence path from the round's training subgraph
  (``murmura.train``).  The serialized program is the positive control —
  its train->aggregate path must exist, so a metadata or parser
  regression cannot silently make the contract vacuous — and the prover
  itself is negative-tested each run against a doctored combine whose
  aggregation reads a training output.

Every contract shares ONE memoized AOT compile per grid cell
(:func:`cell_artifacts`): MUR1500 reads its memory stats, MUR1502 its
alias header, MUR1503 its optimized HLO — the new family costs one
compile sweep, not three (the flow-memoization precedent from PR 8, and
the same sharing `budgets.compiled_cell` / `Network.step_memory_analysis`
apply on their grids).  The sweep uses the persistent compilation cache
(``factories.apply_compilation_cache``).
"""

import contextlib
import json
import math
import re
from collections import deque
from pathlib import Path
from typing import (
    Any, Callable, Dict, List, Optional, Sequence, Tuple,
)

import numpy as np

from murmura_tpu.analysis.lint import Finding

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/sharded.py twin pattern).
MEMORY_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    MEMORY_CHECK_FAMILIES[fn.__name__] = fn
    return fn


MEMORY_PATH = Path(__file__).resolve().parent / "MEMORY.json"

_PKG = Path(__file__).resolve().parent.parent
_ROUNDS_PATH = str(_PKG / "core" / "rounds.py")

# The memory grid: every registry rule x exchange topology x feature.
# Topology is program structure at the round level too — "circulant" arms
# the rules' exchange_offsets roll path, "sparse" the [k, N] edge-mask
# engine — and each feature arms one carried-state subsystem, so the grid
# covers every *_STATE_KEYS layout the MUR1502 walk must see.
MEMORY_TOPOS: Tuple[str, ...] = ("dense", "circulant", "sparse")
MEMORY_FEATURES: Tuple[str, ...] = ("plain", "int8_ef", "stale", "pipeline")

# Donation-only extra cells (one rule suffices — the carried-state layout
# is feature structure, not rule structure): cover the *_STATE_KEYS
# groups the MEMORY_FEATURES grid does not arm (top-k reference,
# adaptive-attack state, DMTT trust state).
DONATION_EXTRA_CELLS: Tuple[Tuple[str, str, str], ...] = (
    ("fedavg", "dense", "topk_ef"),
    ("fedavg", "dense", "adaptive"),
    ("fedavg", "dense", "dmtt"),
)

TOLERANCE = 0.10
_N, _S = 8, 16

# MUR1501: the big-dim param-sharded scaling cells and the law's bounds
# (declared in the finding text).  d12 ~ 2 x d24 within _RATIO_TOL and
# peak(4) <= _MAX_RESIDUAL_FRACTION x peak(1) — the [N, P] class must
# dominate the cell for the scaling claim to be non-vacuous.
MUR1501_CELLS: Tuple[Tuple[str, str], ...] = (
    ("fedavg", "circulant"),
    ("median", "sparse"),
)
SCALING_SHARDS: Tuple[int, ...] = (1, 2, 4)
_SCALING_DIM = 8192
_RATIO_TOL = 0.35
_MAX_RESIDUAL_FRACTION = 0.45

# MUR1503: the dependence cells — one per adjacency storage layout; the
# "pipeline"/"plain" feature compiles are shared with MUR1500/MUR1502.
MUR1503_CELLS: Tuple[Tuple[str, str], ...] = (
    ("fedavg", "dense"),
    ("median", "sparse"),
)
_TRAIN_SCOPE = "murmura.train"
_AGG_SCOPE = "murmura.aggregate"


# --------------------------------------------------------------------------
# Cross-version memory_analysis normalization (the cost_analysis twin)
# --------------------------------------------------------------------------

_MEMORY_FIELDS: Tuple[Tuple[str, str], ...] = (
    ("temp_bytes", "temp_size_in_bytes"),
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_bytes", "generated_code_size_in_bytes"),
)


def normalize_memory_analysis(mem) -> Dict[str, float]:
    """Flatten ``Compiled.memory_analysis()`` (a ``CompiledMemoryStats``
    object, or None where the backend reports nothing) into one flat dict.
    Shared with ``Network.step_memory_analysis``.

    ``peak_bytes`` is the derived live-footprint bound XLA does not
    expose directly: arguments + outputs - aliased (donated buffers are
    counted once) + temporaries + generated code.
    """
    out: Dict[str, float] = {
        key: float(getattr(mem, attr, 0.0) or 0.0)
        for key, attr in _MEMORY_FIELDS
    }
    out["peak_bytes"] = (
        out["argument_bytes"] + out["output_bytes"] - out["alias_bytes"]
        + out["temp_bytes"] + out["generated_bytes"]
    )
    return out


def memory_key(rule: str, topo: str, feature: str) -> str:
    return f"{rule}/{topo}/{feature}"


def _rule_anchor(rule: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor as anchor

    return anchor(rule)


def _cpu_device():
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


# --------------------------------------------------------------------------
# The shared grid-cell builder + one memoized AOT compile per cell
# --------------------------------------------------------------------------


def build_memory_cell(rule: str, topo: str, feature: str):
    """(round program, concrete args) for one grid cell — the canonical
    tiny round shape (n=8, s=16, MLP 6->(8,)->3) every executable family
    uses, with the cell's topology and feature armed."""
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import (
        AGG_CASES, _canonical_adj, canonical_offsets,
    )
    from murmura_tpu.attacks.gaussian import make_gaussian_attack
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.core.stale import StalenessSpec
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.faults.schedule import FaultSpec
    from murmura_tpu.models import make_mlp
    from murmura_tpu.ops.compress import CompressionSpec

    n, s = _N, _S
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
    sparse_offsets: Optional[Tuple[int, ...]] = None
    if topo == "sparse":
        offsets = tuple(canonical_offsets(n))
        case["exchange_offsets"] = list(offsets)
        case["sparse_exchange"] = True
        sparse_offsets = offsets
    elif topo == "circulant":
        case["exchange_offsets"] = list(canonical_offsets(n))
    elif topo != "dense":
        raise ValueError(f"unknown memory topo {topo!r}")
    agg = build_aggregator(
        rule, case, model_dim=int(flat0.size), total_rounds=4
    )
    kw: Dict[str, Any] = dict(
        local_epochs=1, batch_size=8, lr=0.05, total_rounds=4, seed=7,
        attack=make_gaussian_attack(
            n, attack_percentage=0.3, noise_std=5.0, seed=7
        ),
        sparse_offsets=sparse_offsets,
    )
    if feature == "int8_ef":
        kw["compression"] = CompressionSpec(
            "int8", block=32, error_feedback=True
        )
    elif feature == "topk_ef":
        kw["compression"] = CompressionSpec(
            "topk", block=32, topk_ratio=0.1, error_feedback=True
        )
    elif feature == "stale":
        if topo == "sparse":
            base = np.ones((len(sparse_offsets), n), np.float32)
        else:
            base = np.asarray(
                _canonical_adj(n, circulant=(topo == "circulant")),
                np.float32,
            )
        kw["staleness"] = StalenessSpec(
            max_staleness=2, discount=0.5, base_mask=base
        )
        kw["faults"] = FaultSpec()
    elif feature == "pipeline":
        kw["pipeline"] = True
    elif feature == "adaptive":
        from murmura_tpu.attacks.adaptive import make_adaptive_alie_attack

        kw["attack"] = make_adaptive_alie_attack(
            n, attack_percentage=0.3, seed=7
        )
    elif feature == "dmtt":
        from murmura_tpu.dmtt.protocol import DMTTParams

        kw["dmtt"] = DMTTParams()
        kw.pop("attack")
    elif feature != "plain":
        raise ValueError(f"unknown memory feature {feature!r}")
    prog = build_round_program(model, agg, data, **kw)

    if prog.sparse:
        adj = jnp.ones((len(prog.sparse_offsets), n), jnp.float32)
    else:
        adj = jnp.asarray(
            _canonical_adj(n, circulant=(topo == "circulant")), jnp.float32
        )
    args: List[Any] = [
        prog.init_params,
        {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
        jax.random.PRNGKey(0),
        adj,
        jnp.zeros((n,), jnp.float32),
        jnp.asarray(0.0, jnp.float32),
        {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
    ]
    if prog.faulted:
        args.insert(5, jnp.ones((n,), jnp.float32))
    return prog, args


_CELL_MEMO: Dict[Tuple[str, str, str], Tuple[Any, Any, Any]] = {}
_HLO_MEMO: Dict[Tuple[str, str, str], str] = {}


def cell_artifacts(rule: str, topo: str, feature: str):
    """(program, args, compiled executable) for one grid cell — the ONE
    AOT compile (donation armed, exactly as the tpu backend jits the
    step) every MUR1500/1502/1503 consumer shares.  Memoized per process;
    honors the persistent compilation cache."""
    import jax

    from murmura_tpu.utils.factories import apply_compilation_cache

    key = (rule, topo, feature)
    if key in _CELL_MEMO:
        return _CELL_MEMO[key]
    apply_compilation_cache()
    prog, args = build_memory_cell(rule, topo, feature)
    dev = _cpu_device()
    cm = (
        jax.default_device(dev) if dev is not None
        else contextlib.nullcontext()
    )
    with cm:
        compiled = (
            jax.jit(prog.train_step, donate_argnums=(0, 1))
            .lower(*args)
            .compile()
        )
    _CELL_MEMO[key] = (prog, args, compiled)
    return _CELL_MEMO[key]


def cell_hlo(rule: str, topo: str, feature: str) -> str:
    """Optimized HLO text of one grid cell (cached; the compile is the
    memoized one)."""
    key = (rule, topo, feature)
    if key not in _HLO_MEMO:
        _HLO_MEMO[key] = cell_artifacts(rule, topo, feature)[2].as_text()
    return _HLO_MEMO[key]


def measure_cell(rule: str, topo: str, feature: str) -> Dict[str, float]:
    """Normalized memory stats of one grid cell's compiled executable."""
    return normalize_memory_analysis(
        cell_artifacts(rule, topo, feature)[2].memory_analysis()
    )


_MEASURE_MEMO: Optional[Dict[str, Dict[str, float]]] = None


def measure_all(force: bool = False) -> Dict[str, Dict[str, float]]:
    """Measured memory cells for every registry rule over the full
    (topo x feature) grid.  Memoized per process (shared by the CLI and
    the test gate)."""
    global _MEASURE_MEMO
    if _MEASURE_MEMO is not None and not force:
        return dict(_MEASURE_MEMO)
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.analysis import ir

    out: Dict[str, Dict[str, float]] = {}
    for rule in sorted(AGGREGATORS):
        if rule not in ir.AGG_CASES:
            continue  # MUR205 already covers the missing case
        for topo in MEMORY_TOPOS:
            for feature in MEMORY_FEATURES:
                try:
                    out[memory_key(rule, topo, feature)] = measure_cell(
                        rule, topo, feature
                    )
                except Exception as e:  # noqa: BLE001 — cell error
                    out[memory_key(rule, topo, feature)] = {
                        "error": f"{type(e).__name__}: {e}"
                    }
    _MEASURE_MEMO = dict(out)
    return out


# --------------------------------------------------------------------------
# MUR1500 — committed per-cell memory budgets (the BUDGETS.json etiquette)
# --------------------------------------------------------------------------

# The metrics gated against the committed file.  alias_bytes is implied
# by the others through peak_bytes and would double-report every drift.
_GATED_METRICS: Tuple[str, ...] = (
    "temp_bytes", "argument_bytes", "output_bytes", "generated_bytes",
    "peak_bytes",
)


def _load_doc(path: Optional[Path] = None) -> Dict[str, Any]:
    p = Path(path) if path is not None else MEMORY_PATH
    if not p.exists():
        return {}
    return json.loads(p.read_text())


def load_memory(path: Optional[Path] = None) -> Dict[str, Any]:
    return _load_doc(path).get("budgets", {})


def update_memory(path: Optional[Path] = None) -> Path:
    """Measure the full grid and rewrite MEMORY.json (sorted keys, stable
    formatting — the diff is the review artifact).  Refuses to write when
    any cell failed to compile, the update_budgets contract."""
    p = Path(path) if path is not None else MEMORY_PATH
    measured = measure_all(force=True)
    broken = {k: v["error"] for k, v in measured.items() if "error" in v}
    if broken:
        raise RuntimeError(
            "refusing to rewrite memory budgets: "
            f"{len(broken)} grid cell(s) failed to compile — fix the "
            f"rules first: {json.dumps(broken, indent=2)}"
        )
    doc = {
        "_comment": (
            "Committed XLA memory_analysis budgets per round-program "
            "grid cell (murmura check --memory, MUR1500; see "
            "docs/ANALYSIS.md).  Regenerate with `python -m murmura_tpu "
            "check --update-memory` and review the diff as residency "
            "history."
        ),
        "tolerance": TOLERANCE,
        "budgets": {
            k: {m: measured[k][m] for m in _GATED_METRICS}
            for k in sorted(measured)
        },
    }
    p.write_text(json.dumps(doc, indent=2, sort_keys=False) + "\n")
    return p


def _rel_delta(measured: float, budget: float) -> float:
    if budget == 0.0:
        return math.inf if measured else 0.0
    return (measured - budget) / budget


def memory_budget_findings(
    path: Optional[Path] = None,
) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """Compare the measured grid against the committed budgets.

    Returns ``(findings, summaries)``: findings are MUR1500
    drift/missing/stale entries; ``summaries`` carries one
    ``{"kind": "memory_summary", ...}`` record per cell (including
    in-tolerance ones) for ``check --json``.
    """
    memory_path = Path(path) if path is not None else MEMORY_PATH
    anchor = str(memory_path)
    doc = _load_doc(memory_path)
    budgets = doc.get("budgets", {})
    # The committed file's tolerance governs (the reviewable knob the
    # file advertises); the module constant is only the written default.
    tolerance = float(doc.get("tolerance", TOLERANCE))
    measured = measure_all()

    findings: List[Finding] = []
    summaries: List[Dict[str, Any]] = []
    for key in sorted(measured):
        cell = measured[key]
        rule = key.split("/", 1)[0]
        rule_path, rule_line = _rule_anchor(rule)
        if "error" in cell:
            findings.append(Finding(
                "MUR1500", rule_path, rule_line,
                f"memory sweep for {key} failed to compile: "
                f"{cell['error']}",
            ))
            continue
        committed = budgets.get(key)
        if committed is None:
            findings.append(Finding(
                "MUR1500", anchor, 1,
                f"no committed memory budget for {key} — run `python -m "
                "murmura_tpu check --update-memory` and commit the diff",
            ))
            continue
        record: Dict[str, Any] = {"kind": "memory_summary", "key": key}
        within = True
        for metric in _GATED_METRICS:
            record[metric] = cell[metric]
            record[f"budget_{metric}"] = committed.get(metric, 0.0)
            d = _rel_delta(record[metric], record[f"budget_{metric}"])
            record[f"{metric}_delta"] = d
            if abs(d) > tolerance:
                within = False
                findings.append(Finding(
                    "MUR1500", rule_path, rule_line,
                    f"{key}: {metric} drifted {d:+.1%} from the "
                    f"committed memory budget ({record[metric]:.3g} vs "
                    f"{record[f'budget_{metric}']:.3g}, tolerance "
                    f"±{tolerance:.0%}) — if intended, run "
                    "--update-memory and commit the diff as residency "
                    "history",
                    data={"key": key, "metric": metric, "delta": d},
                ))
        record["within_tolerance"] = within
        summaries.append(record)
    for key in sorted(set(budgets) - set(measured)):
        findings.append(Finding(
            "MUR1500", anchor, 1,
            f"stale memory budget entry {key} matches no measured grid "
            "cell — remove it (or run --update-memory)",
        ))
    return findings, summaries


@_family
def check_memory_budgets() -> List[Finding]:
    """MUR1500 over the committed MEMORY.json (the full grid compile
    sweep — every other family in this module reuses its executables)."""
    return memory_budget_findings()[0]


def memory_summaries() -> List[Dict[str, Any]]:
    """The per-cell ``memory_summary`` records for ``check --json``
    (measurement is the memoized sweep — no extra compiles)."""
    return memory_budget_findings()[1]


# --------------------------------------------------------------------------
# MUR1501 — per-device peak shrinks ~P/shards on the param mesh
# --------------------------------------------------------------------------


def sharded_cell_peak(rule: str, mode: str, shards: int) -> float:
    """Per-device normalized peak of one big-dim canonical cell compiled
    on a ("seed", "nodes", "param") = (1, 2, shards) mesh with the
    [N, P]-class operands column-sharded."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from murmura_tpu.analysis.ir import _ensure_host_devices, build_canonical
    from murmura_tpu.parallel.mesh import param_axis_scope

    _ensure_host_devices(8)
    devices = jax.devices()
    if len(devices) < 2 * shards:
        raise RuntimeError(
            f"needs {2 * shards} devices, have {len(devices)} (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    mesh = Mesh(
        np.array(devices[: 2 * shards]).reshape(1, 2, shards),
        ("seed", "nodes", "param"),
    )
    prog = build_canonical(
        rule, _N, circulant=(mode == "circulant"), node_axis_sharded=True,
        sparse=(mode == "sparse"), dim=_SCALING_DIM,
    )
    node_s = NamedSharding(mesh, P("nodes"))
    repl = NamedSharding(mesh, P())
    edge_s = NamedSharding(mesh, P(None, "nodes"))
    flat_s = NamedSharding(mesh, P("nodes", "param"))
    base = prog.arg_shardings(node_s, repl, edge_s)

    def flatten_spec(arg, spec):
        def leaf_spec(a, s):
            if (
                hasattr(a, "ndim") and a.ndim == 2
                and a.shape[-1] == prog.dim
            ):
                return flat_s
            return s
        if isinstance(arg, dict):
            return {
                k: leaf_spec(arg[k], spec[k] if isinstance(spec, dict) else spec)
                for k in arg
            }
        return leaf_spec(arg, spec)

    in_s = tuple(
        flatten_spec(arg, spec) for arg, spec in zip(prog.args, base)
    )

    def scoped(*args):  # murmura: traced
        with param_axis_scope(mesh, prog.dim):
            return prog.fn(*args)

    compiled = jax.jit(scoped, in_shardings=in_s).lower(*prog.args).compile()
    return normalize_memory_analysis(compiled.memory_analysis())["peak_bytes"]


def scaling_cell_findings(rule: str, mode: str) -> List[Finding]:
    """One (rule, mode) MUR1501 cell: peaks at shards {1, 2, 4} must obey
    the P/shards law (exposed per-cell so tests gate one cell per tier-1
    run)."""
    path, line = _rule_anchor(rule)
    peaks = {s: sharded_cell_peak(rule, mode, s) for s in SCALING_SHARDS}
    d12 = peaks[1] - peaks[2]
    d24 = peaks[2] - peaks[4]
    findings: List[Finding] = []
    detail = (
        f"peaks/device {{1: {peaks[1]:.0f}, 2: {peaks[2]:.0f}, "
        f"4: {peaks[4]:.0f}}} bytes"
    )
    if d12 <= 0 or d24 <= 0:
        findings.append(Finding(
            "MUR1501", path, line,
            f"[{rule}/{mode}] per-device peak does not decrease with "
            f"shards ({detail}) — the [N, P]-class buffers are not "
            "actually sharded",
            data={"peaks": peaks},
        ))
        return findings
    # The shards->2x-shards deltas isolate the sharded class (the fixed
    # overhead cancels): d12 = var/2, d24 = var/4, so d12 ~ 2 x d24.
    ratio = d12 / d24
    if abs(ratio - 2.0) > 2.0 * _RATIO_TOL:
        findings.append(Finding(
            "MUR1501", path, line,
            f"[{rule}/{mode}] sharded-class bytes violate the P/shards "
            f"law: (peak1-peak2)/(peak2-peak4) = {ratio:.2f}, expected "
            f"~2 within ±{_RATIO_TOL:.0%} ({detail}) — some [N, P] "
            "buffer stopped scaling with the shard count",
            data={"peaks": peaks, "ratio": ratio},
        ))
    if peaks[4] > _MAX_RESIDUAL_FRACTION * peaks[1]:
        findings.append(Finding(
            "MUR1501", path, line,
            f"[{rule}/{mode}] 4-shard per-device peak retains "
            f"{peaks[4] / peaks[1]:.0%} of the unsharded peak (bound "
            f"{_MAX_RESIDUAL_FRACTION:.0%}; {detail}) — the fixed "
            "overhead dominates, so the cell no longer evidences the "
            "P/shards residency claim",
            data={"peaks": peaks},
        ))
    return findings


@_family
def check_sharded_memory_scaling() -> List[Finding]:
    """MUR1501 over the big-dim scaling cells (3 compiles per cell;
    degrades with a warning when the platform cannot give 8 devices,
    the MUR202 convention)."""
    import warnings

    import jax

    from murmura_tpu.analysis.ir import _ensure_host_devices

    _ensure_host_devices(8)
    if len(jax.devices()) < 8:
        warnings.warn(
            "MUR1501 sharded memory scaling is unobservable on this "
            "platform (needs >= 8 devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
        return []
    findings: List[Finding] = []
    for rule, mode in MUR1501_CELLS:
        try:
            findings.extend(scaling_cell_findings(rule, mode))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1501", path, line,
                f"[{rule}/{mode}] sharded memory-scaling probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1502 — donation completeness by carried leaf
# --------------------------------------------------------------------------

# `{output_index}: (param_number, {param_index}, may/must-alias)` pairs in
# the HloModule input_output_alias header.
_ALIAS_PAIR_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)"
)


def aliased_param_numbers(hlo_text: str) -> frozenset:
    """Entry-parameter numbers aliased to an output in the compiled
    module's ``input_output_alias`` header (XLA's post-pruning
    parameter order)."""
    header = hlo_text.splitlines()[0] if hlo_text else ""
    return frozenset(
        int(m.group(1)) for m in _ALIAS_PAIR_RE.finditer(header)
    )


def entry_param_numbers(compiled, num_flat_args: int) -> Dict[int, int]:
    """Map flat argument index -> XLA entry parameter number.

    jax prunes arguments the traced program never reads before XLA sees
    them (e.g. the buffered adjacency of a circulant pipelined cell,
    whose exchange mask is offset structure, not values), shifting the
    parameter numbering the alias header uses.  A donated leaf absent
    from the map is such a dead carry: it has no executable buffer, so
    there is nothing to alias — exempt from MUR1502 by construction.
    Falls back to the identity map when the private ``_kept_var_idx`` is
    unavailable on a future jax."""
    kept = getattr(
        getattr(compiled, "_executable", None), "_kept_var_idx", None
    )
    if kept is None:
        kept = range(num_flat_args)
    return {flat: rank for rank, flat in enumerate(sorted(kept))}


def _leaf_key_group(
    path_root: int, leaf_path: str,
    groups: Dict[str, Tuple[str, ...]],
) -> str:
    """Classify one donated leaf into its MUR900 key group: ``params``,
    a registered ``*_STATE_KEYS`` group, or the rule's own carried
    state."""
    if path_root == 0:
        return "params"
    for group, keys in groups.items():
        if any(f"'{k}'" in leaf_path for k in keys):
            return group
    return "aggregator-state"


def donation_gap_findings(
    hlo_text: str,
    donated_leaves: Sequence[Tuple[Optional[int], str]],
    rule: str, topo: str, feature: str,
) -> List[Finding]:
    """The pure half of MUR1502 (unit-testable without a compile): given
    the optimized HLO and the ``(entry_param_number, leaf_path)`` list
    of donated carried leaves, a finding per live leaf missing from the
    alias header, naming the leaf and its MUR900 key group.  A leaf with
    param number None was pruned as unused before XLA (a dead carry —
    no buffer exists to alias) and is exempt."""
    from murmura_tpu.durability.snapshot import (
        resolve_reserved_agg_state_keys,
    )

    groups = resolve_reserved_agg_state_keys()
    aliased = aliased_param_numbers(hlo_text)
    path, line = _rule_anchor(rule)
    findings: List[Finding] = []
    for idx, leaf_path in donated_leaves:
        if idx is None or idx in aliased:
            continue
        root = 0 if leaf_path.startswith("[0]") else 1
        group = _leaf_key_group(root, leaf_path, groups)
        findings.append(Finding(
            "MUR1502", path, line,
            f"[{rule}/{topo}/{feature}] donated carried leaf "
            f"{leaf_path} (key group: {group}) is not aliased in the "
            "compiled executable — the undonated carry keeps two copies "
            "of the buffer live and silently raises peak memory",
            data={
                "leaf": leaf_path, "group": group, "param_number": idx,
            },
        ))
    return findings


def donation_cell_findings(
    rule: str, topo: str, feature: str
) -> List[Finding]:
    """One grid cell's MUR1502 walk (the compile is the shared memoized
    one — this reads only its alias header)."""
    import jax.tree_util as jtu

    _, args, compiled = cell_artifacts(rule, topo, feature)
    hlo = cell_hlo(rule, topo, feature)
    num_flat = len(jtu.tree_leaves(tuple(args)))
    param_of = entry_param_numbers(compiled, num_flat)
    flat, _ = jtu.tree_flatten_with_path((args[0], args[1]))
    donated = [
        (param_of.get(i), jtu.keystr(p)) for i, (p, _) in enumerate(flat)
    ]
    return donation_gap_findings(hlo, donated, rule, topo, feature)


@_family
def check_donation_completeness() -> List[Finding]:
    """MUR1502 over the full MUR1500 grid (shared compiles — no extra
    cost) plus the donation-only cells covering the remaining
    ``*_STATE_KEYS`` groups."""
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.analysis import ir

    cells: List[Tuple[str, str, str]] = [
        (rule, topo, feature)
        for rule in sorted(AGGREGATORS) if rule in ir.AGG_CASES
        for topo in MEMORY_TOPOS
        for feature in MEMORY_FEATURES
    ]
    cells.extend(DONATION_EXTRA_CELLS)
    findings: List[Finding] = []
    for rule, topo, feature in cells:
        try:
            findings.extend(donation_cell_findings(rule, topo, feature))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1502", path, line,
                f"[{rule}/{topo}/{feature}] donation-completeness probe "
                f"crashed: {type(e).__name__}: {e}",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1503 — overlap-dependence: no train -> buffered-aggregation path
# --------------------------------------------------------------------------

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_TOKEN_RE = re.compile(r"%?([\w.\-]+)")
_CALLEE_RE = re.compile(r"(?:calls|to_apply|body|condition)=\(?([%\w.\-, ]+)\)?")
_PARAM_OP_RE = re.compile(r"(?:^|\s)parameter\((\d+)\)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

# Backstop against pathological expansion of shared computations (each
# call site expands its callee); real round programs sit around 10^3
# instructions.
_MAX_GRAPH_NODES = 2_000_000


def parse_hlo_computations(hlo_text: str):
    """``{computation: [(instr, rhs, is_root), ...]}`` plus the ENTRY
    computation name, from optimized HLO text."""
    comps: Dict[str, List[Tuple[str, str, bool]]] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            comps[cur].append(
                (m.group(1), m.group(2), line.lstrip().startswith("ROOT"))
            )
    if entry is None:
        raise ValueError("no ENTRY computation in HLO text")
    return comps, entry


def build_def_use_graph(hlo_text: str):
    """Call-site-qualified def-use graph of the optimized HLO.

    Returns ``(successors, op_names)``: nodes are
    ``<call path>/<instr>`` strings (each call site expands its callee,
    so a computation shared by two callers cannot conflate their
    dataflow), edges follow def -> use including call/fusion operand ->
    callee parameter (order-matched), callee root -> call site, and the
    while-loop carry.  ``op_names`` maps metadata-bearing nodes to their
    ``op_name`` scope string — the `jax.named_scope` phase brackets the
    round program plants (murmura.train / murmura.aggregate / ...).
    """
    comps, entry = parse_hlo_computations(hlo_text)
    succ: Dict[str, set] = {}
    op_names: Dict[str, str] = {}
    count = [0]

    def add_edge(a: str, b: str):
        succ.setdefault(a, set()).add(b)

    def expand(comp: str, site: str):
        instrs = comps[comp]
        count[0] += len(instrs)
        if count[0] > _MAX_GRAPH_NODES:
            raise RuntimeError(
                f"HLO def-use graph exceeded {_MAX_GRAPH_NODES} nodes"
            )
        defined = {n for n, _, _ in instrs}
        params: Dict[int, str] = {}
        root: Optional[str] = None
        for name, rhs, is_root in instrs:
            node = f"{site}/{name}"
            rhs_core = rhs.split(", metadata=")[0]
            mo = _OPNAME_RE.search(rhs)
            if mo:
                op_names[node] = mo.group(1)
            pm = _PARAM_OP_RE.search(rhs_core)
            if pm:
                params[int(pm.group(1))] = node
            if is_root:
                root = node
            callee_names: List[str] = []
            for c in _CALLEE_RE.findall(rhs_core):
                callee_names.extend(
                    part.strip().lstrip("%") for part in c.split(",")
                )
            operands = []
            for t in _TOKEN_RE.finditer(rhs_core):
                tok = t.group(1)
                if tok in defined and tok != name:
                    operands.append(tok)
            for op in operands:
                add_edge(f"{site}/{op}", node)
            for cn in callee_names:
                if cn not in comps:
                    continue
                sub = f"{site}/{name}>{cn}"
                sub_params, sub_root = expand(cn, sub)
                if len(operands) == len(sub_params):
                    # Call operands map to callee parameters in order.
                    for i, op in enumerate(operands):
                        if i in sub_params:
                            add_edge(f"{site}/{op}", sub_params[i])
                else:
                    # Conservative fallback (e.g. while bodies sharing
                    # one tuple operand): every operand may reach every
                    # parameter.
                    for op in operands:
                        for p in sub_params.values():
                            add_edge(f"{site}/{op}", p)
                if sub_root is not None:
                    add_edge(sub_root, node)
                    if "body=" in rhs_core:
                        # While carry: the body root feeds the next
                        # iteration's parameters.
                        for p in sub_params.values():
                            add_edge(sub_root, p)
        return params, root

    expand(entry, "")
    return succ, op_names


def scope_dependence_path(
    hlo_text: str, src_scope: str, dst_scope: str
) -> Optional[Tuple[int, int, bool]]:
    """(#src nodes, #dst nodes, path exists) for dataflow from any
    instruction whose ``op_name`` metadata contains ``src_scope`` to any
    containing ``dst_scope``.  None when either scope set is empty (the
    metadata did not survive — callers treat that as its own failure)."""
    succ, op_names = build_def_use_graph(hlo_text)
    srcs = [n for n, l in op_names.items() if src_scope in l]
    dsts = {n for n, l in op_names.items() if dst_scope in l}
    if not srcs or not dsts:
        return None
    seen = set(srcs)
    queue = deque(srcs)
    found = False
    while queue:
        n = queue.popleft()
        if n in dsts:
            found = True
            break
        for m in succ.get(n, ()):
            if m not in seen:
                seen.add(m)
                queue.append(m)
    return len(srcs), len(dsts), found


def doctored_combine_hlo() -> str:
    """Optimized HLO of a deliberately broken combine: the aggregation
    scope reads this round's training output.  The MUR1503 prover must
    find its train -> aggregate path — the per-run negative control that
    keeps the def-use machinery honest (and the shape tests reuse)."""
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(np.random.default_rng(0).normal(size=(6, 6)), jnp.float32)

    def doctored(x, buf):  # murmura: traced
        with jax.named_scope(_TRAIN_SCOPE):
            t = jnp.tanh(x @ w)
        with jax.named_scope(_AGG_SCOPE):
            # The bug under test: aggregation consumes the fresh training
            # output t instead of only the buffered carry.
            a = jnp.sum(buf + t, axis=0)
        return t, a

    x = jnp.ones((4, 6), jnp.float32)
    buf = jnp.ones((4, 6), jnp.float32)
    return jax.jit(doctored).lower(x, buf).compile().as_text()


def overlap_cell_findings(rule: str, topo: str) -> List[Finding]:
    """One (rule, topo) MUR1503 cell: the pipelined program's buffered
    aggregation must have NO dependence path from this round's training;
    the serialized program is the positive control (its path MUST
    exist).  Both compiles are the shared MUR1500 grid executables."""
    path, line = _rule_anchor(rule)
    findings: List[Finding] = []

    piped = scope_dependence_path(
        cell_hlo(rule, topo, "pipeline"), _TRAIN_SCOPE, _AGG_SCOPE
    )
    plain = scope_dependence_path(
        cell_hlo(rule, topo, "plain"), _TRAIN_SCOPE, _AGG_SCOPE
    )
    if piped is None or plain is None:
        findings.append(Finding(
            "MUR1503", _ROUNDS_PATH, 1,
            f"[{rule}/{topo}] the murmura.train/murmura.aggregate "
            "named_scope metadata did not survive into the optimized "
            "HLO — the overlap-dependence contract is unobservable and "
            "the phase brackets in core/rounds.py need restoring",
        ))
        return findings
    if not plain[2]:
        findings.append(Finding(
            "MUR1503", _ROUNDS_PATH, 1,
            f"[{rule}/{topo}] positive control failed: the SERIALIZED "
            "program shows no train -> aggregate dependence path "
            f"({plain[0]} train / {plain[1]} aggregate nodes) — the "
            "prover or the scope metadata regressed, so the pipelined "
            "no-path result cannot be trusted",
        ))
    if piped[2]:
        findings.append(Finding(
            "MUR1503", _ROUNDS_PATH, 1,
            f"[{rule}/{topo}] the pipelined program's buffered "
            "aggregation depends on this round's training subgraph "
            f"({piped[0]} train / {piped[1]} aggregate nodes) — XLA "
            "cannot overlap the exchange/aggregation with local "
            "training, which is the entire point of the pipeline flag",
        ))
    return findings


@_family
def check_overlap_dependence() -> List[Finding]:
    """MUR1503 over the dependence cells, plus the doctored-combine
    negative control proving the prover still detects a real
    train -> aggregate path each run."""
    findings: List[Finding] = []
    for rule, topo in MUR1503_CELLS:
        try:
            findings.extend(overlap_cell_findings(rule, topo))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1503", path, line,
                f"[{rule}/{topo}] overlap-dependence probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    try:
        doctored = scope_dependence_path(
            doctored_combine_hlo(), _TRAIN_SCOPE, _AGG_SCOPE
        )
        if doctored is None or not doctored[2]:
            findings.append(Finding(
                "MUR1503", str(Path(__file__).resolve()), 1,
                "negative control failed: the dependence prover did not "
                "flag the doctored combine that reads a training output "
                "— MUR1503's clean results are vacuous until the "
                "def-use machinery is fixed",
            ))
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR1503", str(Path(__file__).resolve()), 1,
            f"doctored-combine negative control crashed: "
            f"{type(e).__name__}: {e}",
        ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_MEMORY_MEMO: Optional[List[Finding]] = None


def check_memory(force: bool = False) -> List[Finding]:
    """Run MUR1500-1503; returns findings (empty = every memory contract
    holds).  Memoized per process — the CLI and the test gate share one
    sweep, and the families themselves share one AOT compile per grid
    cell."""
    global _MEMORY_MEMO
    if _MEMORY_MEMO is not None and not force:
        return list(_MEMORY_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in MEMORY_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1500", str(Path(__file__).resolve()), 1,
                f"memory check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _MEMORY_MEMO = list(findings)
    return findings
