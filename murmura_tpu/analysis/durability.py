"""MUR901/902: the resume-determinism contract (`murmura check
--durability`; docs/ROBUSTNESS.md "Run durability").

MUR900 (analysis/contracts.py) proves the snapshot *payload* is complete
— every reserved carried-state key survives the save→restore roundtrip.
This module proves the payload is *sufficient*: restoring a snapshot into
the warm compiled round program and re-running the interrupted rounds
must reproduce the uninterrupted run exactly, for every registered
aggregation rule in every exchange mode.  Executable, per cell:

- **MUR901 — crash-equivalence**: train 2 rounds, snapshot, train 2 more
  (the uninterrupted tail), restore the snapshot into the SAME network,
  replay the tail.  History, params and the full ``agg_state`` (EF
  residual, topk reference, trust state — whatever the cell carries) must
  match byte-for-byte.  Anything less means a resumed run silently
  diverges from the run it claims to continue.
- **MUR902 — zero-recompile restore**: the replay runs under
  :class:`~murmura_tpu.analysis.sanitizers.CompileTracker`; a restore
  that triggers even one compile would stall a real resume behind a full
  program rebuild and break the donation story (the restored arrays must
  land with the shapes/dtypes/layouts the warm program specialized on).

Both hold *by construction* — every random stream is a pure function of
``(seed, round)`` and the snapshot carries all round-crossing state — so
a finding here is a real regression: a new piece of carried state that
missed the snapshot, or a restore path that perturbs placement.

The grid is ``AGGREGATORS x (dense, circulant, sparse, compressed,
adaptive, stale)`` — the same rule inventory the IR/flow/budget sweeps use
(``AGG_CASES`` keeps the bijection under MUR205).  Cells are tiny (5-8 nodes, an
83-param MLP, 4 rounds) but compile-dominated (~3-4 s each), so the full
sweep is memoized per process and runs by default only for the package
check, like ``check_ir``/``check_flow``.  Tests gate a representative
subset per tier-1 run (tests/test_durability.py) and the full grid under
``-m slow``.

Findings anchor to the rule's factory ``def`` (the ir.py convention), so
``# murmura: ignore[MUR901]`` suppression applies there.
"""

import tempfile
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding

# The exchange formulations a rule's math can take (ISSUE 7/8
# vocabulary): dense allgather, circulant ppermute shifts, the sparse
# [k, N] edge-mask engine, and the int8+error-feedback codec (the mode
# with round-crossing COMPRESS_STATE_KEYS state — the one a shallow
# checkpoint would silently corrupt).  ``adaptive`` (ISSUE 11) runs the
# dense exchange under a closed-loop bisection attack: the mode with
# round-crossing ATTACK_STATE_KEYS state — a snapshot that dropped the
# attacker's bracket would resume a silently-cold adversary.  ``stale``
# (ISSUE 13) runs the dense exchange under a straggler/link-drop fault
# schedule with bounded staleness armed: the mode with round-crossing
# STALE_STATE_KEYS state — a snapshot that dropped the payload cache
# would resume serving zeros as "cached" neighbor models.  ``pipeline``
# (ISSUE 14) runs the dense exchange with pipelined rounds armed: the
# mode with round-crossing PIPELINE_STATE_KEYS state — a snapshot that
# dropped the double buffer would resume with the in-flight round's
# exchange silently discarded (the delayed displacement lost forever).
DURABILITY_MODES: Tuple[str, ...] = (
    "dense", "circulant", "sparse", "compressed", "adaptive", "stale",
    "pipeline",
)

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py twin pattern).
DURABILITY_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    DURABILITY_CHECK_FAMILIES[fn.__name__] = fn
    return fn


def history_equal(a: Any, b: Any) -> bool:
    """Recursive byte-equality over json-able history values, with
    ``NaN == NaN`` (a rule metric that is legitimately NaN — e.g. a
    masked mean over an empty mask — must not read as divergence just
    because the restored prefix round-tripped through JSON and came back
    as a different NaN object)."""
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(
            history_equal(a[k], b[k]) for k in a
        )
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(
            history_equal(x, y) for x, y in zip(a, b)
        )
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (a != a and b != b)
    return a == b


def _cell_config(rule: str, mode: str):
    """The cell's tiny-but-real config: synthetic data, an 83-param MLP,
    5 nodes (8 for the sparse exponential graph), 4 total rounds.  Rule
    params come from analysis/ir.py's AGG_CASES so the durability grid
    and the IR/budget grids stay one inventory."""
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.config import Config

    raw: Dict[str, Any] = {
        "experiment": {"name": f"durability-{rule}-{mode}", "seed": 7,
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
    }
    if mode == "circulant":
        # ppermute requires the tpu backend + a static circulant topology;
        # num_devices pinned to 1 so the cell runs on any host.
        raw["backend"] = "tpu"
        raw["tpu"] = {"exchange": "ppermute", "num_devices": 1,
                      "compute_dtype": "float32"}
    elif mode == "sparse":
        raw["topology"] = {"type": "exponential", "num_nodes": 8}
    elif mode == "compressed":
        raw["compression"] = {"algorithm": "int8", "error_feedback": True,
                              "block": 64}
    elif mode == "adaptive":
        raw["attack"] = {"enabled": True, "type": "gaussian",
                         "percentage": 0.3, "params": {"noise_std": 5.0},
                         "adaptive": {"enabled": True}}
    elif mode == "stale":
        raw["faults"] = {"enabled": True, "straggler_prob": 0.4,
                         "link_drop_prob": 0.2, "seed": 11}
        raw["exchange"] = {"max_staleness": 2, "staleness_discount": 0.5}
    elif mode == "pipeline":
        # Snapshot at round 2 => the pipeline buffer holds round 1's
        # un-aggregated exchange; the resumed run must aggregate it on
        # its first replayed round exactly as the uninterrupted one did.
        raw["exchange"] = {"pipeline": True}
    elif mode != "dense":
        raise ValueError(f"unknown durability mode {mode!r}")
    return Config.model_validate(raw)


def resume_cell_findings(rule: str, mode: str) -> List[Finding]:
    """Run ONE (rule, mode) cell of the resume-determinism contract and
    return its MUR901/902 findings (empty = crash-equivalent).

    The probe: train 2 rounds, snapshot, train 2 more uninterrupted and
    record (history, params, agg_state); restore the snapshot into the
    now-warm network and replay the 2 tail rounds under CompileTracker.
    Exposed per-cell so tests can gate a subset without paying for the
    full grid (tests/test_durability.py)."""
    import jax

    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.utils.factories import build_network_from_config

    path, line = _anchor(rule)
    net = build_network_from_config(_cell_config(rule, mode))
    with tempfile.TemporaryDirectory() as snap:
        net.train(rounds=2, verbose=False)
        net.save_checkpoint(snap)
        net.train(rounds=2, verbose=False)
        full_hist = {k: list(v) for k, v in net.history.items()}
        full_params = [
            np.asarray(x) for x in jax.tree_util.tree_leaves(net.params)
        ]
        full_agg = {k: np.asarray(v) for k, v in net.agg_state.items()}
        restored_round = net.restore_checkpoint(snap)
        if restored_round != 2:
            return [Finding(
                "MUR901", path, line,
                f"[{rule}/{mode}] snapshot saved at round 2 restored to "
                f"round {restored_round} — the round counter did not "
                "survive the roundtrip",
            )]
        with track_compiles() as tracker:
            net.train(rounds=2, verbose=False)
        compiles = tracker.total

    findings: List[Finding] = []
    resumed_hist = {k: list(v) for k, v in net.history.items()}
    if not history_equal(resumed_hist, full_hist):
        diverged = sorted(
            k for k in set(full_hist) | set(resumed_hist)
            if not history_equal(full_hist.get(k), resumed_hist.get(k))
        )
        findings.append(Finding(
            "MUR901", path, line,
            f"[{rule}/{mode}] resumed history diverges from the "
            f"uninterrupted run in {diverged} — save→restore→round is not "
            "byte-equal to the uninterrupted round; some round-crossing "
            "state is missing from the snapshot",
        ))
    for full_leaf, leaf in zip(
        full_params, jax.tree_util.tree_leaves(net.params)
    ):
        if not np.array_equal(full_leaf, np.asarray(leaf), equal_nan=True):
            findings.append(Finding(
                "MUR901", path, line,
                f"[{rule}/{mode}] resumed params diverge byte-wise from "
                "the uninterrupted run — the parameter/rng sections do "
                "not reproduce the interrupted trajectory",
            ))
            break
    for key in sorted(set(full_agg) | set(net.agg_state)):
        a, b = full_agg.get(key), net.agg_state.get(key)
        if a is None or b is None or not np.array_equal(
            a, np.asarray(b), equal_nan=True
        ):
            findings.append(Finding(
                "MUR901", path, line,
                f"[{rule}/{mode}] carried agg_state key '{key}' diverges "
                "after resume — the rule's round-crossing state (EF "
                "residual / reference / trust) is not crash-equivalent",
            ))
    if compiles:
        findings.append(Finding(
            "MUR902", path, line,
            f"[{rule}/{mode}] replaying {2} rounds after a warm restore "
            f"compiled {compiles} program(s) — restore must be value-only "
            "into the already-compiled round program (matching shapes/"
            "dtypes/placement), or a real resume stalls behind a rebuild",
        ))
    return findings


def _anchor(rule: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor

    return _rule_anchor(rule)


@_family
def check_resume_determinism() -> List[Finding]:
    """MUR901/902 over the full ``AGGREGATORS x DURABILITY_MODES`` grid.
    A cell that crashes outright is itself a MUR901 finding — a rule that
    cannot even run the save→restore→replay probe has no resume story."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for mode in DURABILITY_MODES:
            try:
                findings.extend(resume_cell_findings(rule, mode))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _anchor(rule)
                findings.append(Finding(
                    "MUR901", path, line,
                    f"[{rule}/{mode}] resume-determinism probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_DURABILITY_MEMO: Optional[List[Finding]] = None


def check_durability(force: bool = False) -> List[Finding]:
    """Run MUR901/902 over the durability grid; returns findings (empty =
    every rule x mode resumes crash-equivalently with zero recompiles).
    Memoized per process — the CLI and the slow test gate share one
    sweep.  Unlike check_flow this EXECUTES programs
    (compile + 6 tiny rounds per cell, ~2 min for the 36-cell grid on
    CPU), which is why it runs only for the package-level check."""
    global _DURABILITY_MEMO
    if _DURABILITY_MEMO is not None and not force:
        return list(_DURABILITY_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in DURABILITY_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR901", str(Path(__file__).resolve()), 1,
                f"durability check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _DURABILITY_MEMO = list(findings)
    return findings
