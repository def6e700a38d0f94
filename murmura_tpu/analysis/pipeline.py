"""Pipelined-rounds contracts (MUR1200-1203) — part of the default
package check (docs/PERFORMANCE.md "Pipelined rounds").

The pipeline stage (core/pipeline.py) threads a double buffer through
the compiled round program: round r's production (train + attack +
sentinels + codec + stale fold) writes the buffer that round r+1's
delayed aggregation consumes, while round r+1's training runs with no
data dependence on that aggregation.  Each link carries an invariant
that must stay machine-checked or the overlap story silently rots:

- **MUR1200 — pipeline-state registry bijection.**
  ``PIPELINE_STATE_KEYS`` must be registered in the MUR900 snapshot
  registry under its defining module, its keys distinct and
  ``pipe_``-prefixed, ``init_pipeline_state`` must emit exactly the
  ``pipeline_state_keys(stale)`` subset with the shapes the scan carry,
  gang vmap, mesh placement (node-leading ``pipe_adj``) and durability
  snapshot rely on, the buffer must start INVALID (``pipe_valid`` 0 —
  warm-up exactness), and with staleness armed ``pipe_bcast`` must be
  absent (the buffer-reuse bijection with the stale cache).
- **MUR1201 — recompile-free pipelining.**  The buffer is carried state;
  a pipelined round program compiles once and every buffer swap — churn
  varying the buffered adjacency round to round — is value-only
  (:class:`~murmura_tpu.analysis.sanitizers.CompileTracker`).  The probe
  also requires the pipeline to actually report a valid buffer after
  warm-up (``agg_pipe_valid``), so a silently-dead pipeline cannot pass
  vacuously.
- **MUR1202 — collective-inventory parity.**  The delayed aggregation
  runs the same rule kernels once per round on buffered values; the
  pipelined round program's traced collective inventory must equal the
  serialized program's, per rule x dense/sparse — overlapping the
  exchange must not add communication.
- **MUR1203 — delayed-step influence bounds + the lagging-verdict
  discipline.**  Run the taint interpreter (analysis/flow.py) over the
  composed produce -> buffer -> delayed-aggregate -> combine step:
  bounded rules (krum/median/trimmed/ubar) must keep their declared
  MUR800 per-coordinate influence cardinality when the aggregation
  consumes BUFFERED rows (a delayed row is still ONE neighbor), a
  sender scrubbed at production time must never enter the buffer, and a
  sender whose scrub verdict zeroed its buffered edges must not reach
  the delayed output through its cached payload — the scrub verdicts
  lag one round behind the aggregation, so containment must ride the
  buffer write, not the aggregation (the MUR1103 replay-hole
  discipline applied to the pipeline).

Like ``check_staleness``, MUR1201 compiles and runs tiny programs, so
the family is memoized per process and runs by default only for the
package check; tests gate representative cells per tier-1 run
(tests/test_pipeline.py) and negatives prove each probe can fire.
"""

from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/durability.py/staleness.py twin pattern).
PIPELINE_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    PIPELINE_CHECK_FAMILIES[fn.__name__] = fn
    return fn


_PKG = Path(__file__).resolve().parent.parent
_PIPE_PATH = str(_PKG / "core" / "pipeline.py")

# The trace-level collective vocabulary — IMPORTED from the MUR1002
# check so the parity checks cannot drift on what counts as
# communication (the staleness.py convention).
from murmura_tpu.analysis.adaptive import _COLLECTIVE_PRIMS  # noqa: E402

# The exchange layouts the pipeline grids sweep: the dense [N, N]
# adjacency and the sparse [k, N] edge-mask engine (the pipeline buffers
# whatever adjacency values the round consumed, so every per-round graph
# composes — dense and sparse cover both storage layouts of the buffer).
PIPELINE_MODES: Tuple[str, ...] = ("dense", "sparse")


def _rule_anchor(rule: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor as anchor

    return anchor(rule)


# --------------------------------------------------------------------------
# MUR1200 — pipeline-state registry bijection
# --------------------------------------------------------------------------


@_family
def check_pipeline_state_registry() -> List[Finding]:
    """MUR1200: PIPELINE_STATE_KEYS <-> init_pipeline_state <-> MUR900
    snapshot registry, all bijective and shape-sound, including the
    staleness buffer-reuse subset."""
    findings: List[Finding] = []
    try:
        from murmura_tpu.core.pipeline import (
            ADJ_KEY,
            BCAST_KEY,
            PIPELINE_STATE_KEYS,
            VALID_KEY,
            init_pipeline_state,
            pipeline_state_keys,
        )
        from murmura_tpu.durability.snapshot import (
            RESERVED_AGG_STATE_KEY_GROUPS,
        )
    except Exception as e:  # noqa: BLE001 — the import failure IS the finding
        return [Finding(
            "MUR1200", _PIPE_PATH, 1,
            f"the pipeline module failed to import "
            f"({type(e).__name__}: {e}) — the MUR1200 bijection cannot "
            "be checked",
        )]

    keys = tuple(PIPELINE_STATE_KEYS)
    if len(set(keys)) != len(keys) or any(
        not k.startswith("pipe_") for k in keys
    ):
        findings.append(Finding(
            "MUR1200", _PIPE_PATH, 1,
            f"PIPELINE_STATE_KEYS must be distinct 'pipe_'-prefixed "
            f"agg_state keys, got {keys} — the prefix is how telemetry "
            "and report consumers recognize pipeline state",
        ))
    reg = RESERVED_AGG_STATE_KEY_GROUPS.get("PIPELINE_STATE_KEYS")
    if reg != "murmura_tpu.core.pipeline":
        findings.append(Finding(
            "MUR1200", _PIPE_PATH, 1,
            "PIPELINE_STATE_KEYS is not registered in durability."
            f"snapshot.RESERVED_AGG_STATE_KEY_GROUPS under its defining "
            f"module (got {reg!r}) — the double buffer would be "
            "invisible to the MUR900 snapshot-completeness contract and "
            "a SIGKILL at a buffer-populated round boundary would "
            "silently resume with the in-flight exchange discarded",
        ))
    stale_keys = pipeline_state_keys(stale=True)
    if BCAST_KEY in stale_keys or set(stale_keys) != set(keys) - {BCAST_KEY}:
        findings.append(Finding(
            "MUR1200", _PIPE_PATH, 1,
            f"pipeline_state_keys(stale=True) returned {stale_keys} — "
            "with bounded staleness armed the broadcast buffer must be "
            "the stale cache (buffer reuse) and exactly pipe_bcast must "
            "be dropped from the carried set",
        ))
    if tuple(pipeline_state_keys(stale=False)) != keys:
        findings.append(Finding(
            "MUR1200", _PIPE_PATH, 1,
            "pipeline_state_keys(stale=False) must return the full "
            "PIPELINE_STATE_KEYS reservation",
        ))
    for n, p, offsets, stale in (
        (5, 7, (), False), (8, 3, (1, 2, 4), False), (6, 4, (), True),
    ):
        init = init_pipeline_state(
            n, p, np.float32, sparse_offsets=offsets, stale=stale,
        )
        want = set(pipeline_state_keys(stale))
        if set(init) != want:
            findings.append(Finding(
                "MUR1200", _PIPE_PATH, 1,
                f"init_pipeline_state keys {sorted(init)} != "
                f"pipeline_state_keys({stale}) {sorted(want)} — the "
                "round program seeds agg_state from the reservation",
            ))
            continue
        adj = np.asarray(init[ADJ_KEY])
        want_adj = (n, len(offsets)) if offsets else (n, n)
        if adj.shape != want_adj:
            findings.append(Finding(
                "MUR1200", _PIPE_PATH, 1,
                f"init pipe_adj is shape {adj.shape}, not {want_adj} — "
                "the buffered adjacency must be node-LEADING ([N, N] "
                "dense / [N, k] sparse) so the mesh's leading-axis "
                "sharding places it on the node axis",
            ))
        if not offsets and np.diagonal(adj).any():
            findings.append(Finding(
                "MUR1200", _PIPE_PATH, 1,
                "init pipe_adj has a non-zero diagonal — the warm-up "
                "placeholder graph must respect MUR301 (no self-loops)",
            ))
        valid = np.asarray(init[VALID_KEY])
        if valid.shape != () or valid.item() != 0.0:
            findings.append(Finding(
                "MUR1200", _PIPE_PATH, 1,
                f"init pipe_valid is {valid!r}, not a scalar 0.0 — the "
                "buffer must start invalid so round 0's placeholder "
                "aggregation is where-discarded (warm-up exactness: "
                "P_1 = Q_0)",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1201 — recompile-free pipelining (executable)
# --------------------------------------------------------------------------


def _cell_config(rule: str, mode: str, pipeline: bool = True):
    """One (rule, mode) pipeline cell's tiny-but-real config — the
    durability grid's cell plus a fault schedule (so the buffered
    adjacency varies round to round) and the exchange block."""
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.config import Config

    raw: Dict[str, Any] = {
        "experiment": {"name": f"pipe-{rule}-{mode}", "seed": 7,
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
    }
    if pipeline:
        raw["exchange"] = {"pipeline": True}
    if mode == "sparse":
        raw["topology"] = {"type": "exponential", "num_nodes": 8}
    elif mode != "dense":
        raise ValueError(f"unknown pipeline mode {mode!r}")
    return Config.model_validate(raw)


def recompile_cell_findings(rule: str, mode: str = "dense") -> List[Finding]:
    """Run ONE (rule, mode) MUR1201 cell: 2 warmup rounds (the compile),
    then 3 more under CompileTracker — the buffer fills, churn varies
    the buffered adjacency, and none of it may recompile.  The cell must
    also report a valid buffer after warm-up (``agg_pipe_valid`` > 0),
    so a dead pipeline cannot pass vacuously.  Exposed per-cell so tests
    gate a subset (tests/test_pipeline.py)."""
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
            "MUR1201", path, line,
            f"[{rule}/{mode}] 3 pipelined rounds after warmup compiled "
            f"{tracker.total} program(s) — the double buffer is carried "
            "state and the fault masks input values, so pipelining must "
            "be value-only over one compiled round program",
        ))
    valid = net.history.get("agg_pipe_valid") or []
    if not any(v > 0 for v in valid):
        findings.append(Finding(
            "MUR1201", path, line,
            f"[{rule}/{mode}] agg_pipe_valid never reported a valid "
            "buffer across 5 pipelined rounds — the recompile check is "
            "vacuous (the pipeline stage is not actually wired into "
            "this rule's round program; check core/rounds.py)",
        ))
    return findings


@_family
def check_pipeline_recompile() -> List[Finding]:
    """MUR1201 over ``AGGREGATORS x PIPELINE_MODES`` (compiles and runs
    tiny programs — the check_durability cost profile)."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for mode in PIPELINE_MODES:
            try:
                findings.extend(recompile_cell_findings(rule, mode))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1201", path, line,
                    f"[{rule}/{mode}] pipeline recompile probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1202 — collective-inventory parity (trace-level, per rule x mode)
# --------------------------------------------------------------------------


def _build_pipeline_programs(rule: str, mode: str):
    """(serialized program, pipelined program) for one (rule, mode) cell
    — identical in every respect except the pipeline flag."""
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES, canonical_offsets
    from murmura_tpu.attacks.gaussian import make_gaussian_attack
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
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
    if mode == "sparse":
        offsets = tuple(canonical_offsets(n))
        case["exchange_offsets"] = list(offsets)
        case["sparse_exchange"] = True
        sparse_offsets: Optional[Tuple[int, ...]] = offsets
    elif mode == "dense":
        sparse_offsets = None
    else:
        raise ValueError(f"unknown pipeline mode {mode!r}")
    agg = build_aggregator(
        rule, case, model_dim=int(flat0.size), total_rounds=4
    )
    attack = make_gaussian_attack(
        n, attack_percentage=0.3, noise_std=5.0, seed=7
    )
    common = dict(
        local_epochs=1, batch_size=8, lr=0.05, total_rounds=4, seed=7,
        attack=attack, sparse_offsets=sparse_offsets,
    )
    plain = build_round_program(model, agg, data, **common)
    piped = build_round_program(model, agg, data, pipeline=True, **common)
    return plain, piped


def _trace_collectives(prog) -> frozenset:
    """Collective primitive names in an (unfaulted) round program's
    traced jaxpr."""
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
        jnp.asarray(0.0, jnp.float32),
        {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
    )
    return frozenset(
        e.primitive.name for e in iter_eqns(closed)
        if e.primitive.name in _COLLECTIVE_PRIMS
    )


def collective_cell_findings(rule: str, mode: str) -> List[Finding]:
    """One (rule, mode) MUR1202 cell: the pipelined round program's
    traced collective inventory vs the serialized program's — hiding the
    exchange must not add communication."""
    path, line = _rule_anchor(rule)
    plain, piped = _build_pipeline_programs(rule, mode)
    stray = _trace_collectives(piped) - _trace_collectives(plain)
    if stray:
        return [Finding(
            "MUR1202", path, line,
            f"[{rule}/{mode}] the pipelined round program traces "
            f"collective(s) {sorted(stray)} absent from the serialized "
            "program — the delayed aggregation must run the same rule "
            "kernels on buffered values, adding no communication",
        )]
    return []


@_family
def check_pipeline_collectives() -> List[Finding]:
    """MUR1202 over ``AGGREGATORS x PIPELINE_MODES`` (trace-only: nothing
    compiles)."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        for mode in PIPELINE_MODES:
            try:
                findings.extend(collective_cell_findings(rule, mode))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1202", path, line,
                    f"[{rule}/{mode}] pipeline collective-inventory "
                    f"probe crashed: {type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1203 — delayed-step influence bounds + lagging-verdict discipline
# --------------------------------------------------------------------------

# The probe's cast over the canonical flow cell's graph: one sender
# scrubbed at THIS round's production (its row must never enter the next
# buffer), and one sender whose LAST-round scrub verdict zeroed its
# buffered edges (its buffered payload must never reach the delayed
# output — the lagging-verdict containment).
_SCRUBBED_NOW = 2
_SCRUBBED_PREV = 3

# Rules exempt from the probe-C buffered-taint check, with the reason —
# the same documented value-dataflow limitation as MUR802/MUR1103:
# geometric_median's dense Weiszfeld distances run through the Gram
# centering mean, which couples all rows in value dataflow while
# cancelling exactly in every distance.
_DELAYED_TAINT_EXEMPT: Dict[str, str] = {
    "geometric_median": "Weiszfeld distances run through the dense "
    "Gram centering mean, which couples all rows in value dataflow "
    "while cancelling exactly in every distance",
}


# Default-path memos: the composed cell build (make_jaxpr) and each
# taint evaluation are deterministic and pure, and the non-vacuity guard
# plus probes A and C would otherwise repeat identical sweeps — the
# memos keep the package check to one build + two taint runs per rule.
# Negative tests pass a combine_factory and bypass both memos.
_DEFAULT_CELL_MEMO: Dict[str, Any] = {}
_DEFAULT_TAINT_MEMO: Dict[Tuple[str, bool, bool], Any] = {}


def _delayed_cell(rule: str, combine_factory=None):
    """The composed produce-scrub -> buffer -> delayed-aggregate ->
    combine step over the canonical dense flow cell, plus the concrete
    seed values the probes share.  ``combine_factory`` overrides the
    combine/buffer-write wiring so negative tests can drive the probes
    with a broken pipeline (tests/test_pipeline.py): it receives
    ``(bcast_raw, own_now, scrub_ok, buf_bcast)`` and returns
    ``(next_buffer, delayed_bcast)`` — the default stores the scrubbed
    broadcast and serves the buffer.  Default-path results are memoized
    per rule (pure build; the probes and the non-vacuity guard share
    one trace).
    """
    if combine_factory is None and rule in _DEFAULT_CELL_MEMO:
        return _DEFAULT_CELL_MEMO[rule]
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.flow import _quiet_tracing, build_flow_cell

    cell = build_flow_cell(rule, "dense")
    n = cell.n
    own, bcast, adj0 = cell.args[0], cell.args[1], cell.args[2]
    base = np.asarray(adj0, np.float32)

    # This round's production verdicts: sender _SCRUBBED_NOW caught.
    scrub_np = np.ones((n,), np.float32)
    scrub_np[_SCRUBBED_NOW] = 0.0
    scrub_ok = jnp.asarray(scrub_np)
    # The BUFFERED adjacency: last round's folds already zeroed sender
    # _SCRUBBED_PREV's edges (its verdict was enforced at production
    # time, one round before this aggregation runs).
    buf_adj_np = base.copy()
    buf_adj_np[:, _SCRUBBED_PREV] = 0.0
    rng = np.random.default_rng(1)
    buf_own_np = np.asarray(rng.normal(size=bcast.shape) * 0.1, np.float32)
    buf_bcast_np = np.asarray(rng.normal(size=bcast.shape) * 0.1, np.float32)

    cell_fn = cell.fn
    rest = tuple(cell.args[3:])

    def default_combine(bcast_raw, own_now, scrub, buf_bcast):
        # The production sentinel substitution (rounds.py): a caught
        # row's broadcast is replaced by its own state before the
        # buffer write — the lagging verdict is enforced HERE.
        next_buffer = jnp.where(scrub[:, None] > 0, bcast_raw, own_now)
        return next_buffer, buf_bcast

    combine = combine_factory or default_combine

    def fn(own_now, bcast_raw, buf_own, buf_bcast, buf_adj, *rest_a):  # murmura: traced
        next_buffer, delayed_bcast = combine(
            bcast_raw, own_now, scrub_ok, buf_bcast
        )
        agg_out, _state, _stats = cell_fn(
            buf_own, delayed_bcast, buf_adj, *rest_a
        )
        disp = agg_out - buf_own
        out = own_now + disp
        return out, next_buffer

    args = (
        own, bcast, jnp.asarray(buf_own_np), jnp.asarray(buf_bcast_np),
        jnp.asarray(buf_adj_np),
    ) + rest
    with _quiet_tracing():
        closed = jax.make_jaxpr(fn)(*args)
    pack = (cell, closed, args, buf_adj_np, base)
    if combine_factory is None:
        _DEFAULT_CELL_MEMO[rule] = pack
    return pack


def _taint_run(closed, args, n, seed_bcast: bool, seed_buffer: bool):
    """Evaluate the composed step with row labels on the raw broadcast
    and/or buffered broadcast leaves; returns
    ``(out_taint [L, N, P], buffer_taint [L, N, P])``."""
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
        if (pos == 1 and seed_bcast) or (pos == 3 and seed_buffer):
            for lbl in range(n):
                t[lbl, lbl] = True
        pairs.append((v, t))
    ev = TaintEval(n)
    with _quiet_tracing():
        outs = ev.eval_closed(closed, pairs)
    return outs[0][1], outs[1][1]


def delayed_influence_findings(rule: str, combine_factory=None) -> List[Finding]:
    """One rule's MUR1203 probes over the composed delayed step.

    Probe A (buffer seeded): bounded rules keep their declared
    per-coordinate influence cardinality when the aggregation consumes
    buffered rows.
    Probe B (bcast seeded): a sender scrubbed at THIS round's production
    never reaches the next buffer; every clean sender's broadcast does.
    Probe C (buffer seeded): a sender whose lagging verdict zeroed its
    buffered edges never reaches the delayed output via its buffered
    payload.
    """
    path, line = _rule_anchor(rule)
    cell, closed, args, buf_adj, base = _delayed_cell(rule, combine_factory)
    n = cell.n
    findings: List[Finding] = []

    def taint(seed_bcast: bool, seed_buffer: bool):
        key = (rule, seed_bcast, seed_buffer)
        if combine_factory is None and key in _DEFAULT_TAINT_MEMO:
            return _DEFAULT_TAINT_MEMO[key]
        res = _taint_run(closed, args, n, seed_bcast, seed_buffer)
        if combine_factory is None:
            _DEFAULT_TAINT_MEMO[key] = res
        return res

    # -- Probe A: influence cardinality over buffered rows --------------
    # (the buffer-seeded evaluation; probe C reads the same result)
    out_t, _buf_t = taint(seed_bcast=False, seed_buffer=True)
    influence = cell.agg.influence
    if influence is not None and influence.kind == "bounded":
        eff = buf_adj > 0
        per_coord = out_t.sum(axis=0)  # [N, P] distinct-label counts
        self_t = out_t[np.arange(n), np.arange(n)]  # [N, P]
        card_i = (per_coord - self_t).max(axis=1)  # [N]
        for i in range(n):
            bound = influence.bound(int(eff[i].sum()))
            if int(card_i[i]) > bound:
                findings.append(Finding(
                    "MUR1203", path, line,
                    f"[{rule}] the composed delayed step mixes "
                    f"{int(card_i[i])} buffered neighbors into receiver "
                    f"{i}'s output coordinate but the rule declares a "
                    f"bound of {bound} at its buffered degree "
                    f"{int(eff[i].sum())} — delaying the aggregation "
                    "widened the rule's per-coordinate influence",
                ))

    # -- Probe B: a production-scrubbed row must never enter the buffer -
    _out_b, buf_t = taint(seed_bcast=True, seed_buffer=False)
    s = _SCRUBBED_NOW
    if buf_t[s].any():
        findings.append(Finding(
            "MUR1203", path, line,
            f"[{rule}] sender {s}'s scrubbed broadcast taints the next "
            "pipeline buffer — the sentinel verdict must be enforced at "
            "the buffer write (production time), because the delayed "
            "aggregation runs one round after the verdict",
        ))
    clean = [j for j in range(n) if j not in (_SCRUBBED_NOW,)]
    if clean and not buf_t[clean[0], clean[0]].any():
        findings.append(Finding(
            "MUR1203", path, line,
            f"[{rule}] clean sender {clean[0]}'s broadcast does not "
            "reach its own buffer row — the buffer write is not wired "
            "and the lagging-verdict probes are vacuous",
        ))

    # -- Probe C: a lag-scrubbed BUFFERED row must not be aggregated ----
    # (same seeding as probe A — one evaluation serves both)
    if rule in _DELAYED_TAINT_EXEMPT:
        return findings
    out_c = out_t
    if out_c[_SCRUBBED_PREV].any():
        findings.append(Finding(
            "MUR1203", path, line,
            f"[{rule}] sender {_SCRUBBED_PREV}'s BUFFERED payload "
            "taints the delayed output although its scrub verdict "
            "zeroed its buffered edges — a caught row survives one "
            "round late through the pipeline buffer",
        ))
    return findings


@_family
def check_pipeline_influence() -> List[Finding]:
    """MUR1203 over every registered rule (trace-only), plus the
    non-vacuity guard: on fedavg — declared-unbounded, every neighbor
    admitted — a live buffered sender's payload MUST reach some
    receiver's output, proving the probes exercise a live delayed path
    rather than an edgeless one."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        try:
            findings.extend(delayed_influence_findings(rule))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1203", path, line,
                f"[{rule}] delayed influence probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    try:
        # The memoized default-path cell + buffer-seeded taint run: the
        # fedavg probes above already computed both, so the guard costs
        # nothing extra.
        cell, closed, args, buf_adj, base = _delayed_cell("fedavg")
        memo = _DEFAULT_TAINT_MEMO.get(("fedavg", False, True))
        out_c, _ = memo if memo is not None else _taint_run(
            closed, args, cell.n, seed_bcast=False, seed_buffer=True
        )
        live = next(
            j for j in range(cell.n)
            if j not in (_SCRUBBED_NOW, _SCRUBBED_PREV)
        )
        receivers = np.nonzero(buf_adj[:, live] > 0)[0]
        served = any(out_c[live, r].any() for r in receivers)
        if not served:
            path, line = _rule_anchor("fedavg")
            findings.append(Finding(
                "MUR1203", path, line,
                "[fedavg] a live buffered sender's payload reaches NO "
                "receiver through the delayed aggregation — the "
                "delayed path is dead and every MUR1203 containment "
                "verdict above is vacuous",
            ))
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR1203", _PIPE_PATH, 1,
            f"the MUR1203 non-vacuity guard crashed: "
            f"{type(e).__name__}: {e}",
        ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_PIPELINE_MEMO: Optional[List[Finding]] = None


def check_pipeline(force: bool = False) -> List[Finding]:
    """Run MUR1200-1203; returns findings (empty = every pipelined-
    rounds contract holds).  Memoized per process — the CLI and the slow
    test gate share one sweep.  MUR1201 compiles
    and runs tiny programs (the check_durability cost profile), which is
    why the family runs only for the package-level check."""
    global _PIPELINE_MEMO
    if _PIPELINE_MEMO is not None and not force:
        return list(_PIPELINE_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in PIPELINE_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1200", str(Path(__file__).resolve()), 1,
                f"pipeline check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _PIPELINE_MEMO = list(findings)
    return findings
