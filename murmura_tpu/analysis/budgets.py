"""AOT cost budgets (MUR206) — committed FLOPs/bytes per aggregator cell.

Generalizes ``Network.step_cost_analysis`` (core/network.py) from a one-run
diagnostic into a compile-time perf gate: every registry aggregator is
AOT-compiled (``.lower().compile().cost_analysis()`` — nothing executes) on
CPU over the canonical (n x dim x mode) grid from :mod:`analysis.ir`, and
the measured flops/bytes are compared against the committed
``analysis/BUDGETS.json`` with a ±10% tolerance.  A +20% FLOPs change to
any rule therefore fails ``murmura check --ir`` before a run ever reaches
a chip, and ``murmura check --update-budgets`` rewrites the file so the
diff itself becomes reviewable perf history — a budget bump nobody can
explain in review is the regression, caught at the cheapest possible
moment.

Budget keys are ``<rule>/n<N>/d<DIM>/<dtype>/<mode>``; cells carry
``{"flops": f, "bytes": b}`` from XLA's own cost model.  The numbers are
deterministic for a fixed jax/XLA build; after a toolchain upgrade the
workflow is: run ``--update-budgets``, review the diff, commit.
"""

import contextlib
import json
import math
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from murmura_tpu.analysis.lint import Finding

BUDGETS_PATH = Path(__file__).resolve().parent / "BUDGETS.json"

# Canonical sweep: two network sizes x two model dims x both exchange
# modes, float32 (the budget tracks program *shape*, not precision; bf16
# discipline is MUR201's job and CPU bf16 costs would measure emulation
# artifacts).  Probe-based rules are pinned to the canonical probe model's
# own dimension, so they contribute one dim each.
BUDGET_NODE_COUNTS: Tuple[int, ...] = (8, 16)
BUDGET_MODEL_DIMS: Tuple[int, ...] = (256, 1024)
BUDGET_DTYPE = "float32"
TOLERANCE = 0.10

# Fused Pallas aggregation kernels (ops/pallas_agg.py): the circulant
# cells of these rules are additionally measured with the kernels armed
# (mode "pallas"), so the fused formulation's FLOP/bytes delta vs the lax
# circulant cells is committed, reviewable perf history.  On CPU the
# kernels run interpreted — the numbers track the interpreter's lowering,
# which is stable for a fixed jax build (same contract as every other
# cell).
PALLAS_BUDGET_RULES: Tuple[str, ...] = ("krum", "median", "trimmed_mean")


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict (it may be None or
    empty).  Shared with ``Network.step_cost_analysis``."""
    return dict(cost or {})


def budget_key(name: str, n: int, dim: int, mode: str) -> str:
    return f"{name}/n{n}/d{dim}/{BUDGET_DTYPE}/{mode}"


def _cpu_device():
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


_COMPILED_MEMO: Dict[Tuple[str, int, bool, Optional[int], bool], Any] = {}


def compiled_cell(
    name: str, n: int, circulant: bool, dim: Optional[int] = None,
    pallas: bool = False,
):
    """The ONE memoized AOT compile of a canonical cell on CPU, shared by
    every consumer of the grid — the MUR206 cost gate reads its
    ``cost_analysis()``, memory consumers its ``memory_analysis()`` and
    HLO text — so adding a metric family never adds a compile sweep
    (the flow-memoization precedent; ``Network.step_cost_analysis`` /
    ``step_memory_analysis`` share their compile the same way)."""
    import jax

    from murmura_tpu.analysis import ir

    key = (name, n, circulant, dim, pallas)
    if key in _COMPILED_MEMO:
        return _COMPILED_MEMO[key]
    params = (
        dict(ir.AGG_CASES.get(name, {}), pallas=True) if pallas else None
    )
    prog = ir.build_canonical(
        name, n, BUDGET_DTYPE, circulant, dim=dim, params=params
    )
    dev = _cpu_device()
    cm = jax.default_device(dev) if dev is not None else contextlib.nullcontext()
    with cm:
        compiled = jax.jit(prog.fn).lower(*prog.args).compile()
    _COMPILED_MEMO[key] = compiled
    return compiled


def measure_cell(
    name: str, n: int, circulant: bool, dim: Optional[int] = None,
    pallas: bool = False,
) -> Dict[str, float]:
    """Read XLA's cost model off the shared compiled cell."""
    cost = normalize_cost_analysis(
        compiled_cell(name, n, circulant, dim=dim, pallas=pallas)
        .cost_analysis()
    )
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
    }


_MEASURE_MEMO: Optional[Dict[str, Dict[str, float]]] = None


def measure_all(force: bool = False) -> Dict[str, Dict[str, float]]:
    """Measured cost cells for every registry aggregator over the grid.
    Memoized per process (shared by the tier-1 gate and the CLI test)."""
    global _MEASURE_MEMO
    if _MEASURE_MEMO is not None and not force:
        return dict(_MEASURE_MEMO)
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.analysis import ir

    ir._ensure_host_devices()
    from murmura_tpu.utils.factories import apply_compilation_cache

    apply_compilation_cache()
    out: Dict[str, Dict[str, float]] = {}
    for name in sorted(AGGREGATORS):
        if name not in ir.AGG_CASES:
            continue  # MUR205 already covers the missing case
        if name in ir._PROBE_RULES:
            dims: Tuple[int, ...] = (ir.rule_model_dim(name),)
        else:
            dims = BUDGET_MODEL_DIMS
        for n in BUDGET_NODE_COUNTS:
            for dim in dims:
                for circulant in (False, True):
                    key = budget_key(
                        name, n, dim, "circulant" if circulant else "dense"
                    )
                    try:
                        out[key] = measure_cell(name, n, circulant, dim=dim)
                    except Exception as e:  # noqa: BLE001 — cell error
                        out[key] = {"error": f"{type(e).__name__}: {e}"}
                if name in PALLAS_BUDGET_RULES:
                    # The fused-kernel circulant cell (mode "pallas"), so
                    # the kernel formulation's cost delta is committed
                    # perf history next to the lax cells.
                    key = budget_key(name, n, dim, "pallas")
                    try:
                        out[key] = measure_cell(
                            name, n, True, dim=dim, pallas=True
                        )
                    except Exception as e:  # noqa: BLE001 — cell error
                        out[key] = {"error": f"{type(e).__name__}: {e}"}
    _MEASURE_MEMO = dict(out)
    return out


def _load_doc(path: Optional[Path] = None) -> Dict[str, Any]:
    p = Path(path) if path is not None else BUDGETS_PATH
    if not p.exists():
        return {}
    return json.loads(p.read_text())


def load_budgets(path: Optional[Path] = None) -> Dict[str, Any]:
    return _load_doc(path).get("budgets", {})


def update_budgets(path: Optional[Path] = None) -> Path:
    """Measure the full grid and rewrite BUDGETS.json (sorted keys, stable
    formatting — the diff is the review artifact).

    Refuses to write when any cell failed to compile: committing an
    ``{"error": ...}`` record as a budget would later surface as a
    nonsensical infinite-drift finding instead of the real problem.
    """
    p = Path(path) if path is not None else BUDGETS_PATH
    measured = measure_all(force=True)
    broken = {k: v["error"] for k, v in measured.items() if "error" in v}
    if broken:
        raise RuntimeError(
            "refusing to rewrite budgets: "
            f"{len(broken)} grid cell(s) failed to compile — fix the rules "
            f"first: {json.dumps(broken, indent=2)}"
        )
    doc = {
        "_comment": (
            "Committed XLA cost-model budgets per aggregator grid cell "
            "(murmura check --ir, MUR206; see docs/ANALYSIS.md).  "
            "Regenerate with `python -m murmura_tpu check --update-budgets` "
            "and review the diff as perf history."
        ),
        "tolerance": TOLERANCE,
        "budgets": {k: measured[k] for k in sorted(measured)},
    }
    p.write_text(json.dumps(doc, indent=2, sort_keys=False) + "\n")
    return p


def _rel_delta(measured: float, budget: float) -> float:
    if budget == 0.0:
        return math.inf if measured else 0.0
    return (measured - budget) / budget


def check_budgets(
    path: Optional[Path] = None,
) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """Compare the measured grid against the committed budgets.

    Returns ``(findings, deltas)``: findings are MUR206 drift/missing/stale
    entries; ``deltas`` carries one record per cell (including in-tolerance
    ones) for ``check --json`` so CI can chart budget drift over time.
    """
    from murmura_tpu.analysis import ir

    budget_path = Path(path) if path is not None else BUDGETS_PATH
    anchor = str(budget_path)
    doc = _load_doc(budget_path)
    budgets = doc.get("budgets", {})
    # The committed file's tolerance governs (it is the reviewable knob the
    # file advertises); the module constant is only the default it is
    # written with.
    tolerance = float(doc.get("tolerance", TOLERANCE))
    measured = measure_all()

    findings: List[Finding] = []
    deltas: List[Dict[str, Any]] = []
    for key in sorted(measured):
        cell = measured[key]
        rule = key.split("/", 1)[0]
        rule_path, rule_line = ir._rule_anchor(rule)
        if "error" in cell:
            findings.append(Finding(
                "MUR206", rule_path, rule_line,
                f"cost sweep for {key} failed to compile: {cell['error']}",
            ))
            continue
        committed = budgets.get(key)
        if committed is None:
            findings.append(Finding(
                "MUR206", anchor, 1,
                f"no committed budget for {key} — run `python -m "
                "murmura_tpu check --update-budgets` and commit the diff",
            ))
            continue
        record = {
            "key": key,
            "flops": cell["flops"],
            "bytes": cell["bytes"],
            "budget_flops": committed.get("flops", 0.0),
            "budget_bytes": committed.get("bytes", 0.0),
        }
        record["flops_delta"] = _rel_delta(
            record["flops"], record["budget_flops"]
        )
        record["bytes_delta"] = _rel_delta(
            record["bytes"], record["budget_bytes"]
        )
        record["within_tolerance"] = (
            abs(record["flops_delta"]) <= tolerance
            and abs(record["bytes_delta"]) <= tolerance
        )
        deltas.append(record)
        for metric in ("flops", "bytes"):
            d = record[f"{metric}_delta"]
            if abs(d) > tolerance:
                findings.append(Finding(
                    "MUR206", rule_path, rule_line,
                    f"{key}: {metric} drifted {d:+.1%} from the committed "
                    f"budget ({record[metric]:.3g} vs "
                    f"{record[f'budget_{metric}']:.3g}, tolerance "
                    f"±{tolerance:.0%}) — if intended, run "
                    "--update-budgets and commit the diff as perf history",
                    data={"key": key, "metric": metric, "delta": d},
                ))
    for key in sorted(set(budgets) - set(measured)):
        findings.append(Finding(
            "MUR206", anchor, 1,
            f"stale budget entry {key} matches no measured grid cell — "
            "remove it (or run --update-budgets)",
        ))
    # Same suppression contract as the other IR findings (docs/ANALYSIS.md):
    # a factory-line `# murmura: ignore[MUR206]` exempts that rule's cells.
    return ir._apply_suppressions(findings), deltas
