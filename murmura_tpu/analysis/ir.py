"""jaxpr/HLO-level IR contracts (MUR200-205) — ``murmura check --ir``.

The AST pass (analysis/lint.py) can only *approximate* what a traced scope
does; the jaxpr and the AOT-compiled executable show what it actually does.
The invariants the north star lives on — no host round-trips inside the
round program, bf16 exchange tensors that stay bf16, masked exchange that
lowers to boundary ppermutes instead of an all-gather, one compiled program
per shape family, donated round buffers that are actually donated — are
only visible at this level, so each is enforced here as a machine-checked
contract over a canonical (n_nodes x model_dim x dtype) grid:

====== ===================== ==============================================
rule   name                  contract
====== ===================== ==============================================
MUR200 ir-host-callback      no ``pure_callback``/``io_callback``/
                             ``jax.debug.*`` callback primitive anywhere in
                             an aggregation jaxpr — each is a device→host
                             round-trip serializing the round hot path.
MUR201 ir-dtype-discipline   dataflow dtype truth behind AST rule MUR006:
                             the aggregated [N, P] tensor and carried state
                             keep their input dtypes (bf16 in → bf16 out);
                             in bf16 programs no matmul takes a full-size
                             f32 operand (f32 belongs in *accumulation* —
                             ``preferred_element_type`` — not operands);
                             float64 appears nowhere.
MUR202 ir-collective-inventory
                             the communication primitives in the lowered
                             SPMD program are a subset of the rule's
                             ``declared_collectives()``
                             (aggregation/base.py); a stray all_gather on a
                             circulant path is a finding, not an ICI
                             surprise.  Undeclared rules are findings.
MUR203 ir-shape-polymorphism jaxprs traced at two different n are
                             structurally identical (same primitive tree) —
                             a rule whose *program* changes with n would
                             recompile per network size beyond the
                             unavoidable shape specialization.
MUR204 ir-donation           buffers the round step marks donated are
                             actually aliased in the compiled executable
                             (params + carried aggregation state) — a lost
                             alias is a silent extra [N, P] HBM copy per
                             round.
MUR205 ir-coverage           every registry aggregator has a canonical IR
                             case (the MUR101-style bijection that keeps
                             MUR200-203 from going vacuous for new rules).
====== ===================== ==============================================

Suppression: IR findings anchor to the rule's factory (``def make_*``)
line, so the ordinary line suppression applies there, e.g.
``def make_fedavg(...):  # murmura: ignore[MUR202]``.
"""

import dataclasses
import inspect
import os
import re
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from murmura_tpu.analysis.lint import Finding, _suppressed

# --------------------------------------------------------------------------
# Canonical grid
# --------------------------------------------------------------------------

# Two network sizes: MUR200-202 run at the first, MUR203 compares the two.
IR_NODE_COUNTS: Tuple[int, int] = (8, 12)
# Flat parameter dimension for rules that never run the model; probe-based
# rules use the canonical probe model's own dimension instead.
IR_MODEL_DIM = 256
_PROBE_IN = 8
_PROBE_BATCH = 8
_PROBE_CLASSES = 4

# Canonical constructor params per registry rule — the IR twin of the
# contracts pass's _TOPOLOGY_CASES.  MUR205 enforces the bijection with
# aggregation.AGGREGATORS, so a new rule cannot land without an IR case
# (and therefore without MUR200-203 coverage and a cost budget).
AGG_CASES: Dict[str, Dict[str, Any]] = {
    "fedavg": {},
    "krum": {"num_compromised": 1},
    "balance": {},
    "sketchguard": {"sketch_size": 64},
    "ubar": {},
    "evidential_trust": {},
    "median": {},
    "trimmed_mean": {},
    "geometric_median": {"max_iters": 4},
}

# Rules that evaluate the model on probe batches (AggContext.apply_fn).
_PROBE_RULES = frozenset({"ubar", "evidential_trust"})

# HLO op → canonical collective name (aggregation.base.COLLECTIVE_NAMES).
# -start variants cover async collectives on backends that split them.
_HLO_COLLECTIVES = {
    "all-gather": "all_gather",
    "all-gather-start": "all_gather",
    "all-reduce": "all_reduce",
    "all-reduce-start": "all_reduce",
    "collective-permute": "ppermute",
    "collective-permute-start": "ppermute",
    "all-to-all": "all_to_all",
    "reduce-scatter": "reduce_scatter",
}
_COLL_RE = re.compile(
    r"\b(" + "|".join(sorted(_HLO_COLLECTIVES, key=len, reverse=True)) + r")\b"
)

_ALIAS_RE = re.compile(r"\b(?:may|must)-alias\b")

# `= <result shape(s)> all-reduce(`: the statement whose OPCODE is an
# all-reduce, with its result shapes captured.
_ALL_REDUCE_STMT_RE = re.compile(
    r"=\s*(\S.*?)\s(all-reduce(?:-start)?)\(", re.MULTILINE
)
_ANY_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")

# The one all-reduce traffic MUR303 licenses in the faulted round: its two
# scalar fault metrics, ``agg_alive`` and ``agg_quarantined`` (the
# ``alive.sum()`` / quarantined count of core/rounds.py), one rank-0 f32
# each.  This XLA spells a scalar sum over the sharded node axis as an
# all-reduce (and fuses the two into one tuple op); the last one spelled it
# all-gather + local reduce, inside the ``all_gather`` every round has.
FAULT_METRIC_ALL_REDUCES = ("f32[]", "f32[]")


def all_reduce_results(hlo_text: str) -> tuple:
    """Sorted ``dtype[dims]`` of every result of every all-reduce statement
    in an HLO module (tuple results flattened)."""
    return tuple(sorted(
        f"{dtype}[{dims}]"
        for result, _op in _ALL_REDUCE_STMT_RE.findall(hlo_text)
        for dtype, dims in _ANY_SHAPE_RE.findall(result)
    ))


def collective_names(hlo_text: str, licensed_all_reduces: tuple = ()) -> frozenset:
    """Canonical names of the collectives in an HLO module, by op name.

    ``licensed_all_reduces``: the exact multiset of all-reduce results
    (:func:`all_reduce_results`) the caller's contract allows by count,
    dtype and shape.  Only when the module's all-reduces are exactly those
    is ``all_reduce`` left out; one more scalar, another dtype (a
    ``pred[]`` any-non-finite sync) or any row-shaped result keeps the
    name in the inventory, where the caller's comparison finds it."""
    names = {_HLO_COLLECTIVES[m] for m in _COLL_RE.findall(hlo_text)}
    if licensed_all_reduces and all_reduce_results(hlo_text) == tuple(
        sorted(licensed_all_reduces)
    ):
        names.discard("all_reduce")
    return frozenset(names)


# Registry of round-program-level check families ``check_ir`` runs after
# the per-rule canonical sweep: name -> (callable, crash rule id, crash
# anchor file relative to the package).  Populated by the ``@_ir_family``
# decorator on each ``check_*`` function below; ``check_coverage`` scans
# this module (and analysis/flow.py's twin registry) for any module-level
# ``check_*`` function that is NOT registered — a new MUR family someone
# wrote but never wired into ``check_ir``/tier-1 becomes a finding, not a
# silent gap.
IR_CHECK_FAMILIES: Dict[str, Tuple[Callable, str, str]] = {}

# Entry points / meta-checks that are wired elsewhere by design: check_ir
# IS the runner, check_coverage runs first inside it, and analysis/flow's
# check_flow / analysis/durability's check_durability are their own
# runners composed by run_check_detailed.
_CHECK_ENTRY_POINTS = frozenset(
    {"check_ir", "check_coverage", "check_flow", "check_durability",
     "check_adaptive", "check_staleness", "check_pipeline",
     "check_sharded", "check_composition", "check_memory", "check_serve",
     "check_observe"}
)


def _ir_family(crash_rule: str, crash_anchor: str):
    def deco(fn):
        IR_CHECK_FAMILIES[fn.__name__] = (fn, crash_rule, crash_anchor)
        return fn

    return deco


def _ensure_host_devices(count: int = 8) -> None:
    """Request a multi-device host platform for the MUR202 sharded
    lowerings, when the XLA backend is not initialized yet (the CLI path;
    tests get their devices from conftest.py).  A no-op afterwards —
    backend flags cannot change post-init."""
    from murmura_tpu.parallel.mesh import backend_initialized

    if backend_initialized():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={count}"
        )


# --------------------------------------------------------------------------
# Canonical programs
# --------------------------------------------------------------------------


_PROBE_MODEL_MEMO = None


def _probe_model():
    """(apply_fn, unravel, dim) of the canonical probe model — a tiny MLP
    shared by every probe-based rule's canonical program.  Memoized: the
    init/ravel is constant per process and every build_canonical call for
    a probe rule (plus every rule_model_dim) would otherwise re-run it."""
    global _PROBE_MODEL_MEMO
    if _PROBE_MODEL_MEMO is not None:
        return _PROBE_MODEL_MEMO
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.models import make_mlp

    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )
    flat0, unravel = ravel_pytree(model.init(jax.random.PRNGKey(0)))
    _PROBE_MODEL_MEMO = (model.apply, unravel, int(flat0.size))
    return _PROBE_MODEL_MEMO


def rule_model_dim(name: str) -> int:
    """Canonical flat dimension for one rule (probe rules carry the probe
    model's parameter count; everything else uses IR_MODEL_DIM)."""
    if name in _PROBE_RULES:
        return _probe_model()[2]
    return IR_MODEL_DIM


def canonical_offsets(n: int) -> List[int]:
    """Circulant offsets of the canonical k-regular(4) topology at size n —
    derived from the real generator so the IR pass exercises each
    topology's masked-exchange program, not a hand-typed stand-in."""
    from murmura_tpu.topology.generators import create_topology

    offsets = create_topology("k-regular", num_nodes=n, k=4).circulant_offsets()
    if not offsets:
        raise AssertionError(f"k-regular({n}) stopped being circulant")
    return offsets


def _canonical_adj(n: int, circulant: bool):
    import numpy as np

    from murmura_tpu.topology.generators import create_topology

    if circulant:
        adj = np.zeros((n, n), dtype=np.float32)
        for o in canonical_offsets(n):
            adj[np.arange(n), (np.arange(n) + o) % n] = 1.0
        return adj
    return create_topology("fully", num_nodes=n).mask()


@dataclasses.dataclass
class CanonicalProgram:
    """One traceable aggregation cell of the canonical grid.

    ``fn(*args)`` closes over the AggContext (static under trace) and takes
    only array arguments, so it can be handed directly to ``make_jaxpr``,
    ``eval_shape`` and sharded ``jit``.
    """

    name: str
    n: int
    dim: int
    circulant: bool
    fn: Callable
    args: Tuple
    # (node_sharding, replicated[, edge_sharding]) -> pytree of args; the
    # third parameter carries the sparse [k, N] edge-mask sharding and is
    # optional for legacy two-parameter callables.
    arg_shardings: Callable
    agg: Any = None  # the AggregatorDef (declared_collectives hook)
    # Sparse exchange mode: the adjacency argument is the [k, N] edge mask
    # (topology/sparse.py) instead of the [N, N] matrix.
    sparse: bool = False


def build_canonical(
    name: str,
    n: int,
    dtype: str = "float32",
    circulant: bool = False,
    node_axis_sharded: bool = False,
    params: Optional[Dict[str, Any]] = None,
    dim: Optional[int] = None,
    audit: bool = False,
    sparse: bool = False,
) -> CanonicalProgram:
    """Instantiate one rule over one grid cell.

    Probe batches are explicit *arguments* (not closed-over constants) so
    the MUR202 sharded lowering sees them node-sharded, exactly as the real
    round program's data arrays are.  ``dim`` overrides the flat parameter
    dimension for non-probe rules (the budgets sweep uses two sizes); probe
    rules are pinned to the canonical probe model's own dimension.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.aggregation.base import AggContext

    dt = jnp.dtype(dtype)
    if dim is None or name in _PROBE_RULES:
        dim = rule_model_dim(name)
    case = dict(AGG_CASES.get(name, {}) if params is None else params)
    if sparse:
        circulant = True  # sparse IS the circulant machinery, mask-weighted
        case["exchange_offsets"] = canonical_offsets(n)
        case["sparse_exchange"] = True
    elif circulant:
        case["exchange_offsets"] = canonical_offsets(n)
    agg = build_aggregator(name, case, model_dim=dim, total_rounds=10)

    rng = np.random.default_rng(0)
    own = jnp.asarray(rng.normal(size=(n, dim)) * 0.1, dt)
    bcast = jnp.asarray(rng.normal(size=(n, dim)) * 0.1, dt)
    if sparse:
        # The [k, N] all-active edge mask — the sparse program's adjacency
        # input; nothing [N, N] is built for the cell (MUR600's subject).
        adj = jnp.ones((len(canonical_offsets(n)), n), jnp.float32)
    else:
        adj = jnp.asarray(_canonical_adj(n, circulant))
    ridx = jnp.asarray(0.0, jnp.float32)
    state = {k: jnp.asarray(v) for k, v in agg.init_state(n).items()}

    base_ctx = AggContext(
        total_rounds=10,
        num_classes=_PROBE_CLASSES,
        node_axis_sharded=node_axis_sharded,
        audit=audit,
    )

    if name in _PROBE_RULES:
        apply_fn, unravel, _ = _probe_model()
        probe = {
            "x": jnp.asarray(
                rng.normal(size=(n, _PROBE_BATCH, _PROBE_IN)), jnp.float32
            ),
            "y": jnp.asarray(
                rng.integers(0, _PROBE_CLASSES, size=(n, _PROBE_BATCH)),
                jnp.int32,
            ),
            "mask": jnp.ones((n, _PROBE_BATCH), jnp.float32),
        }

        def fn(own, bcast, adj, ridx, state, probe):  # murmura: traced
            ctx = dataclasses.replace(
                base_ctx,
                apply_fn=apply_fn,
                unravel=unravel,
                probe_x=probe["x"],
                probe_y=probe["y"],
                probe_mask=probe["mask"],
            )
            return agg.aggregate(own, bcast, adj, ridx, state, ctx)

        args = (own, bcast, adj, ridx, state, probe)

        def arg_shardings(node_s, repl, edge_s=None):
            adj_s = edge_s if (sparse and edge_s is not None) else node_s
            return (
                node_s, node_s, adj_s, repl,
                {k: node_s for k in state},
                {k: node_s for k in probe},
            )

    else:

        def fn(own, bcast, adj, ridx, state):  # murmura: traced
            return agg.aggregate(own, bcast, adj, ridx, state, base_ctx)

        args = (own, bcast, adj, ridx, state)

        def arg_shardings(node_s, repl, edge_s=None):
            adj_s = edge_s if (sparse and edge_s is not None) else node_s
            return (node_s, node_s, adj_s, repl, {k: node_s for k in state})

    return CanonicalProgram(
        name=name, n=n, dim=dim, circulant=circulant, fn=fn, args=args,
        arg_shardings=arg_shardings, agg=agg, sparse=sparse,
    )


# --------------------------------------------------------------------------
# jaxpr utilities
# --------------------------------------------------------------------------


def trace_jaxpr(prog: CanonicalProgram):
    """The cell's ClosedJaxpr (tracing only — nothing compiles or runs)."""
    import jax

    return jax.make_jaxpr(prog.fn)(*prog.args)


def iter_eqns(jaxpr) -> Iterator:
    """All equations of a (Closed)Jaxpr, recursing into sub-jaxprs
    (pjit/scan/while/cond branches, custom_* calls)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        yield eqn
        for sub in eqn.params.values():
            subs = sub if isinstance(sub, (list, tuple)) else [sub]
            for s in subs:
                if hasattr(s, "jaxpr") or hasattr(s, "eqns"):
                    yield from iter_eqns(s)


def jaxpr_signature(jaxpr) -> Tuple[str, ...]:
    """Structural signature: the depth-annotated primitive sequence.  Two
    traces of the same rule at different n must produce identical
    signatures (MUR203) — dimension constants change, the program must
    not."""
    sig: List[str] = []

    def walk(jx, depth: int) -> None:
        jx = getattr(jx, "jaxpr", jx)
        for eqn in jx.eqns:
            sig.append(f"{depth}:{eqn.primitive.name}")
            for sub in eqn.params.values():
                subs = sub if isinstance(sub, (list, tuple)) else [sub]
                for s in subs:
                    if hasattr(s, "jaxpr") or hasattr(s, "eqns"):
                        walk(s, depth + 1)

    walk(jaxpr, 0)
    return tuple(sig)


def collective_inventory(prog: CanonicalProgram, mesh=None) -> Optional[frozenset]:
    """Canonical collective names in the cell's compiled SPMD program.

    Compiles the cell with the node axis sharded over a >= 2 device mesh
    (the tpu-backend layout, parallel/mesh.py) and scans the optimized HLO.
    Returns ``None`` when no multi-device platform is available — the
    inventory is then unobservable and MUR202 degrades with a warning.
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    if mesh is None:
        devices = jax.devices()
        usable = [d for d in (2, 4, 8) if d <= len(devices) and prog.n % d == 0]
        if not usable:
            return None
        mesh = Mesh(np.array(devices[: max(usable)]), ("nodes",))
    node_s = NamedSharding(mesh, P("nodes"))
    repl = NamedSharding(mesh, P())
    edge_s = NamedSharding(mesh, P(None, "nodes"))  # sparse [k, N] mask
    try:
        in_s = prog.arg_shardings(node_s, repl, edge_s)
    except TypeError:  # legacy two-parameter callables (tests)
        in_s = prog.arg_shardings(node_s, repl)
    jitted = jax.jit(prog.fn, in_shardings=in_s)
    txt = jitted.lower(*prog.args).compile().as_text()
    return frozenset(_HLO_COLLECTIVES[m] for m in _COLL_RE.findall(txt))


# --------------------------------------------------------------------------
# Checks
# --------------------------------------------------------------------------


def _rule_anchor(name: str) -> Tuple[str, int]:
    """(path, line) of the rule's factory ``def`` — where IR findings point
    and where line suppressions apply."""
    from murmura_tpu.aggregation import AGGREGATORS

    fn = AGGREGATORS.get(name)
    try:
        path = inspect.getsourcefile(fn)
        line = inspect.getsourcelines(fn)[1]
        return str(path), int(line)
    except (OSError, TypeError):
        pkg = Path(__file__).resolve().parent.parent
        return str(pkg / "aggregation" / "__init__.py"), 1


def _mode(circulant: bool) -> str:
    return "circulant" if circulant else "dense"


def is_host_callback(primitive_name: str) -> bool:
    """pure_callback / io_callback / debug_callback, and ``debug_print`` —
    the primitive ``jax.debug.print`` traces to in the installed JAX."""
    return "callback" in primitive_name or primitive_name == "debug_print"


def _check_callbacks(name: str, prog: CanonicalProgram, jaxpr) -> List[Finding]:
    """MUR200: host callback primitives in the aggregation jaxpr."""
    path, line = _rule_anchor(name)
    found = sorted(
        {
            eqn.primitive.name
            for eqn in iter_eqns(jaxpr)
            if is_host_callback(eqn.primitive.name)
        }
    )
    if not found:
        return []
    return [Finding(
        "MUR200", path, line,
        f"aggregator '{name}' ({_mode(prog.circulant)}) traces host "
        f"callback primitive(s) {found} into the round program — each is a "
        "device->host round-trip serializing the hot path; remove the "
        "jax.debug/pure_callback/io_callback call",
    )]


def _check_dtypes(
    name: str, prog_f32: CanonicalProgram, prog_bf16: CanonicalProgram
) -> List[Finding]:
    """MUR201: dtype discipline through the dataflow (see module table)."""
    import jax
    import jax.numpy as jnp

    path, line = _rule_anchor(name)
    findings: List[Finding] = []
    mode = _mode(prog_f32.circulant)

    for prog, label in ((prog_f32, "float32"), (prog_bf16, "bfloat16")):
        own, state = prog.args[0], prog.args[4]
        out = jax.eval_shape(prog.fn, *prog.args)
        new_flat, new_state, _stats = out
        if new_flat.dtype != own.dtype:
            findings.append(Finding(
                "MUR201", path, line,
                f"aggregator '{name}' ({mode}, {label} params) returns the "
                f"aggregated [N, P] tensor as {new_flat.dtype} — the "
                "exchanged state must keep the resident param dtype "
                "(accumulate in f32, store in the input dtype)",
            ))
        for k, v in new_state.items():
            if k in state and v.dtype != state[k].dtype:
                findings.append(Finding(
                    "MUR201", path, line,
                    f"aggregator '{name}' ({mode}, {label} params) drifts "
                    f"carried state '{k}' from {state[k].dtype} to "
                    f"{v.dtype} — state dtypes must be round-stable",
                ))

    # f64 anywhere + full-size f32 matmul operands in the bf16 program.
    jaxpr = trace_jaxpr(prog_bf16)
    full = prog_bf16.n * prog_bf16.dim
    f64_prims = set()
    for eqn in iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt == jnp.float64:
                f64_prims.add(eqn.primitive.name)
            if (
                eqn.primitive.name == "dot_general"
                and var in eqn.invars
                and dt == jnp.float32
                and getattr(aval, "size", 0) >= full
            ):
                findings.append(Finding(
                    "MUR201", path, line,
                    f"aggregator '{name}' ({mode}, bfloat16 params) feeds a "
                    f"full-size float32 operand {tuple(aval.shape)} into a "
                    "matmul — promote via preferred_element_type (f32 "
                    "accumulation over bf16 operands), not via f32 "
                    "operands, which double the matmul's HBM reads",
                ))
    if f64_prims:
        findings.append(Finding(
            "MUR201", path, line,
            f"aggregator '{name}' ({mode}) traces float64 values (via "
            f"{sorted(f64_prims)[:4]}) — nothing in the round program may "
            "run double precision",
        ))
    return findings


def _check_structure(
    name: str, prog_a: CanonicalProgram, prog_b: CanonicalProgram
) -> List[Finding]:
    """MUR203: same primitive tree at both canonical network sizes."""
    path, line = _rule_anchor(name)
    sig_a = jaxpr_signature(trace_jaxpr(prog_a))
    sig_b = jaxpr_signature(trace_jaxpr(prog_b))
    if sig_a == sig_b:
        return []
    # First structural divergence, for a legible message.
    i = next(
        (k for k, (x, y) in enumerate(zip(sig_a, sig_b)) if x != y),
        min(len(sig_a), len(sig_b)),
    )
    at_a = sig_a[i] if i < len(sig_a) else "<end>"
    at_b = sig_b[i] if i < len(sig_b) else "<end>"
    return [Finding(
        "MUR203", path, line,
        f"aggregator '{name}' ({_mode(prog_a.circulant)}) traces to "
        f"structurally different programs at n={prog_a.n} "
        f"({len(sig_a)} eqns) vs n={prog_b.n} ({len(sig_b)} eqns); first "
        f"divergence at eqn {i}: {at_a} vs {at_b} — the program must be "
        "identical up to dimension constants or every network size "
        "recompiles a different computation",
    )]


def _check_collectives(name: str, prog: CanonicalProgram) -> List[Finding]:
    """MUR202: lowered collective inventory vs declared_collectives()."""
    path, line = _rule_anchor(name)
    declared = prog.agg.declared_collectives(prog.circulant)
    if declared is None:
        return [Finding(
            "MUR202", path, line,
            f"aggregator '{name}' declares no collective inventory — set "
            "AggregatorDef.collectives (dense/circulant sets drawn from "
            "aggregation.base.COLLECTIVE_NAMES) so stray communication "
            "becomes a check failure instead of an ICI surprise",
        )]
    found = collective_inventory(prog)
    if found is None:
        warnings.warn(
            "murmura check --ir: fewer than 2 devices available — the "
            "MUR202 collective inventory is unobservable on this platform "
            "(run under XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
        return []
    stray = found - declared
    if not stray:
        return []
    return [Finding(
        "MUR202", path, line,
        f"aggregator '{name}' ({_mode(prog.circulant)}) lowers to "
        f"undeclared collective(s) {sorted(stray)} (declared: "
        f"{sorted(declared)}) — either the rule grew unintended "
        "communication or its declared_collectives() contract is stale",
    )]


@_ir_family("MUR204", "core/rounds.py")
def check_donation() -> List[Finding]:
    """MUR204: the round step's donated buffers are actually aliased.

    Compiles two canonical tiny round programs (a stateless rule and one
    with carried aggregation state) exactly as the simulation backend does
    (jit + donate_argnums=(0, 1), core/network.py) and requires one
    input/output alias per donated leaf in the optimized HLO.  A missing
    alias means XLA rejected the donation — params or state silently cost
    an extra full copy per round.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp

    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "rounds.py")
    findings: List[Finding] = []

    n, s = 4, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n, s, _PROBE_IN)).astype(np.float32),
        y=rng.integers(0, _PROBE_CLASSES, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=_PROBE_CLASSES,
    )
    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )

    model_dim = _probe_model()[2]
    for rule in ("fedavg", "sketchguard"):
        agg = build_aggregator(
            rule, dict(AGG_CASES[rule]), model_dim=model_dim, total_rounds=5
        )
        prog = build_round_program(
            model, agg, data, total_rounds=5, batch_size=8
        )
        args = (
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(0),
            jnp.asarray(_canonical_adj(n, circulant=False)),
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
        )
        donated = len(jax.tree_util.tree_leaves(args[0])) + len(
            jax.tree_util.tree_leaves(args[1])
        )
        # Two one-shot analysis compiles, not a hot path — the per-iteration
        # fresh jit cache is the point (each rule gets its own executable).
        step = jax.jit(prog.train_step, donate_argnums=(0, 1))  # murmura: ignore[MUR004]
        txt = step.lower(*args).compile().as_text()
        aliased = len(_ALIAS_RE.findall(txt))
        if aliased < donated:
            findings.append(Finding(
                "MUR204", anchor, 1,
                f"round step with '{rule}': only {aliased} of {donated} "
                "donated buffers (params + carried aggregation state) are "
                "aliased in the compiled executable — the rest pay a full "
                "extra copy per round despite donate_argnums=(0, 1)",
            ))
    return findings


@_ir_family("MUR302", "core/rounds.py")
def check_fault_round() -> List[Finding]:
    """MUR302/MUR303: the fault model is IR-inert.

    The faults subsystem's core promise (docs/ROBUSTNESS.md) is that churn
    composes into the compiled round as *values*, not structure.  Two
    machine-checked halves:

    MUR302 — alive-mask variation causes no recompile: the faulted round
    step compiles once and three rounds with three different alive masks
    re-use that executable (CompileTracker, analysis/sanitizers.py).

    MUR303 — faulted jaxprs stay collective-clean (the MUR202 companion):
    sharding the faulted round over a node mesh must lower to exactly the
    collective inventory of the unfaulted round — the sentinel's
    isfinite/where/rollback plumbing is elementwise over node-local rows
    and may not grow cross-device communication.  One exception, licensed
    by count, dtype and shape (:data:`FAULT_METRIC_ALL_REDUCES`): the
    round's two scalar fault metrics are summed over the node axis; any
    further all-reduce, scalar or not, is a finding.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.sanitizers import RecompileError, track_compiles
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.faults.schedule import FaultSpec
    from murmura_tpu.models import make_mlp

    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "rounds.py")
    findings: List[Finding] = []

    n, s = 4, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n, s, _PROBE_IN)).astype(np.float32),
        y=rng.integers(0, _PROBE_CLASSES, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=_PROBE_CLASSES,
    )
    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )
    agg = build_aggregator(
        "fedavg", {}, model_dim=_probe_model()[2], total_rounds=5
    )
    base = build_round_program(model, agg, data, total_rounds=5, batch_size=8)
    faulted = build_round_program(
        model, agg, data, total_rounds=5, batch_size=8, faults=FaultSpec()
    )
    adj = jnp.asarray(_canonical_adj(n, circulant=False))
    d = {k: jnp.asarray(v) for k, v in faulted.data_arrays.items()}

    def args_for(prog, alive, r):
        a = [
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(r),
            adj,
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(float(r), jnp.float32),
            d,
        ]
        if prog.faulted:
            a.insert(5, jnp.asarray(alive, jnp.float32))
        return a

    # -- MUR302 ------------------------------------------------------------
    # One-shot analysis compile, not a hot path (the MUR204 pattern).
    step = jax.jit(faulted.train_step)  # murmura: ignore[MUR004]
    masks = [
        np.ones(n, np.float32),
        np.array([1, 0, 1, 1], np.float32),
        np.array([0, 1, 0, 1], np.float32),
    ]
    try:
        with track_compiles() as tracker:
            tracker.begin("warmup")
            jax.block_until_ready(step(*args_for(faulted, masks[0], 0))[0])
            tracker.end(allow=True)
            for r, alive in enumerate(masks[1:], start=1):
                tracker.begin(f"round {r}")
                jax.block_until_ready(step(*args_for(faulted, alive, r))[0])
                tracker.end(allow=False)
    except RecompileError as e:
        findings.append(Finding(
            "MUR302", anchor, 1,
            f"varying the alive mask recompiled the faulted round step "
            f"({e}) — churn must reach the compiled program as input "
            "values, never as structure",
        ))

    # -- MUR303 ------------------------------------------------------------
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from murmura_tpu.parallel.mesh import _shard_round_fn

    devices = jax.devices()
    usable = [c for c in (2, 4) if c <= len(devices) and n % c == 0]
    if not usable:
        warnings.warn(
            "murmura check --ir: fewer than 2 devices available — the "
            "MUR303 faulted collective inventory is unobservable on this "
            "platform (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
        return findings
    mesh = Mesh(np.array(devices[: max(usable)]), ("nodes",))
    node_s = NamedSharding(mesh, P("nodes"))

    def inventory(prog, licensed_all_reduces=()):
        sharded = _shard_round_fn(
            prog.train_step, prog, mesh, node_s, donate=False,
            alive_sharding=node_s,
        )
        txt = sharded.lower(*args_for(prog, masks[1], 1)).compile().as_text()
        return collective_names(txt, licensed_all_reduces)

    stray = inventory(faulted, FAULT_METRIC_ALL_REDUCES) - inventory(base)
    if stray:
        findings.append(Finding(
            "MUR303", anchor, 1,
            f"the faulted round step lowers to collective(s) "
            f"{sorted(stray)} absent from the unfaulted round — the fault "
            "plumbing (alive freeze, NaN sentinel, rollback) must stay "
            "node-local and communication-free",
        ))
    return findings


@_ir_family("MUR500", "core/gang.py")
def check_gang_round() -> List[Finding]:
    """MUR500/MUR501: gang batching (core/gang.py) is IR-inert.

    The gang subsystem's core promise (docs/PERFORMANCE.md) is that
    stacking S experiments and vmapping the round program over the seed
    axis changes neither the program's communication nor its compile
    stability.  Two machine-checked halves:

    MUR500 — vmap adds zero collectives, in two sharded lowerings: on the
    node axis the gang program's collective inventory equals the single
    run's (same exchange, batched); on the seed axis ALONE it must be
    collective-FREE — members are independent experiments, so any
    seed-axis collective means a rule accidentally reduced across
    members.

    MUR501 — growing S within a bucket causes zero recompiles: the gang
    pads to power-of-two buckets (core.gang.next_bucket), so a padded
    S=2 gang and a padded S=3 gang present identical shapes and must reuse
    one compiled executable (CompileTracker, analysis/sanitizers.py).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.sanitizers import RecompileError, track_compiles
    from murmura_tpu.core import gang as gang_mod
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp

    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "gang.py")
    findings: List[Finding] = []

    n, s = 4, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n, s, _PROBE_IN)).astype(np.float32),
        y=rng.integers(0, _PROBE_CLASSES, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=_PROBE_CLASSES,
    )
    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )
    agg = build_aggregator(
        "fedavg", {}, model_dim=_probe_model()[2], total_rounds=5
    )
    prog = build_round_program(model, agg, data, total_rounds=5, batch_size=8)
    adj = jnp.asarray(_canonical_adj(n, circulant=False))
    d = {k: jnp.asarray(v) for k, v in prog.data_arrays.items()}
    gang_axes = (0, 0, 0, None, 0, None, 0)
    vstep = jax.vmap(prog.train_step, in_axes=gang_axes)

    def gang_args(batch: int, live: int):
        """Stacked gang inputs for ``live`` members padded to ``batch``
        (the core.gang padding: tail slots replicate member 0)."""
        idx = list(range(live)) + [0] * (batch - live)
        stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda l: jnp.stack([l] * batch), t
        )
        return (
            stack(prog.init_params),
            stack({k: jnp.asarray(v) for k, v in prog.init_agg_state.items()}),
            jnp.stack([jax.random.PRNGKey(i) for i in idx]),
            adj,
            jnp.zeros((batch, n), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            stack(d),
        )

    # -- MUR501 ------------------------------------------------------------
    # One-shot analysis compile, not a hot path (the MUR204 pattern).
    # S=3 and S=4 share the power-of-two bucket (next_bucket -> 4), so the
    # padded shapes are identical and the second gang must be a cache hit
    # — the bucket mapping itself is the contract under test (resolved via
    # the gang module so a broken implementation is observable).
    step = jax.jit(vstep)  # murmura: ignore[MUR004]
    try:
        with track_compiles() as tracker:
            tracker.begin("gang warmup (S=3)")
            jax.block_until_ready(
                step(*gang_args(gang_mod.next_bucket(3), 3))[0]
            )
            tracker.end(allow=True)
            tracker.begin("gang grown to S=4 (same bucket)")
            jax.block_until_ready(
                step(*gang_args(gang_mod.next_bucket(4), 4))[0]
            )
            tracker.end(allow=False)
    except RecompileError as e:
        findings.append(Finding(
            "MUR501", anchor, 1,
            f"growing the gang within a bucket recompiled the gang round "
            f"step ({e}) — bucket padding must make member count a pure "
            "input-value change (core.gang.next_bucket)",
        ))

    # -- MUR500 ------------------------------------------------------------
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from murmura_tpu.parallel import mesh as mesh_mod

    devices = jax.devices()
    usable = [c for c in (2, 4) if c <= len(devices) and n % c == 0]
    if not usable:
        warnings.warn(
            "murmura check --ir: fewer than 2 devices available — the "
            "MUR500 gang collective inventory is unobservable on this "
            "platform (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
        return findings
    n_shards = max(usable)
    single_mesh = Mesh(np.array(devices[:n_shards]), ("nodes",))
    node_s = NamedSharding(single_mesh, P("nodes"))

    def single_inventory():
        sharded = mesh_mod._shard_round_fn(
            prog.train_step, prog, single_mesh, node_s, donate=False,
            alive_sharding=node_s,
        )
        args = (
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(0),
            adj,
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            d,
        )
        txt = sharded.lower(*args).compile().as_text()
        return frozenset(_HLO_COLLECTIVES[m] for m in _COLL_RE.findall(txt))

    def gang_inventory(batch: int, seed_ax: int, node_ax: int):
        gang_mesh = Mesh(
            np.array(devices[: seed_ax * node_ax]).reshape(seed_ax, node_ax),
            ("seed", "nodes"),
        )
        sharded = mesh_mod.shard_gang_step(
            vstep, prog, batch, gang_mesh, donate=False
        )
        txt = sharded.lower(*gang_args(batch, batch)).compile().as_text()
        return frozenset(_HLO_COLLECTIVES[m] for m in _COLL_RE.findall(txt))

    # Half 1 — node-axis inventory equality: vmapping over the seed axis
    # must not change which collectives the node-sharded exchange lowers
    # to (same kinds as the single run on the same node mesh).
    stray = gang_inventory(2, 1, n_shards) - single_inventory()
    if stray:
        findings.append(Finding(
            "MUR500", anchor, 1,
            f"the vmapped gang round step lowers to collective(s) "
            f"{sorted(stray)} absent from the single-run round — vmap over "
            "the experiment axis must not change the node exchange's "
            "communication",
        ))
    # Half 2 — seed-axis isolation: sharded along the seed axis ALONE
    # (node axis unsharded), the gang program must lower to ZERO
    # collectives.  The experiment axis is embarrassingly parallel by
    # construction; any collective here is cross-member communication — a
    # rule accidentally reducing across gang members.
    cross_member = gang_inventory(2, 2, 1)
    if cross_member:
        findings.append(Finding(
            "MUR500", anchor, 1,
            f"the gang round step sharded along the seed axis alone "
            f"lowers to collective(s) {sorted(cross_member)} — members are "
            "independent experiments and may never communicate; a "
            "collective on the seed axis means something reduced across "
            "gang members",
        ))
    return findings


# Rules whose sparse-exchange programs must be free of any [N, N]-sized
# value (MUR600).  evidential_trust is the documented exception: its
# carried smoothed-trust state keeps the dense [N, N] layout (indexed
# O(k·N) per round) for checkpoint/statistics parity.
SPARSE_DENSE_FREE: Tuple[str, ...] = (
    "fedavg", "krum", "ubar", "median", "trimmed_mean",
    "geometric_median", "balance", "sketchguard",
)
# Rules whose sparse collective inventory must EQUAL the circulant one
# (== ppermute-only) under MUR601 — the north-star set the 4096-node
# exponential run rides on.  The remaining SPARSE_DENSE_FREE rules are
# trace-checked by MUR600 but skip the (expensive) sharded compile.
SPARSE_INVENTORY_RULES: Tuple[str, ...] = ("fedavg", "krum", "ubar", "median")


@_ir_family("MUR600", "core/rounds.py")
def check_sparse_exchange() -> List[Finding]:
    """MUR600/MUR601: the sparse exchange engine is dense-free and
    communication-clean (docs/SCALING.md).

    MUR600 — no O(N²) value anywhere in a sparse-mode program: each
    SPARSE_DENSE_FREE rule's sparse cell, plus a full sparse *round
    program* (build_round_program(sparse_offsets=...) with faults armed),
    is traced and every equation's avals are scanned for a shape carrying
    the node extent on two axes.  A dense adjacency (or distance matrix)
    reappearing in sparse mode is exactly the O(N²) ceiling the engine
    exists to remove — at N=4096 one such f32 value is 64 MB and the Gram
    that usually follows is the real regression.

    MUR601 — sparse collective inventory == circulant inventory per rule:
    the SPARSE_INVENTORY_RULES cells are compiled with the node axis
    sharded (edge mask sharded on its node columns) and must lower to
    exactly the circulant mode's collectives — boundary ppermutes only; a
    stray all_gather means the mask plumbing gathered something global.
    """
    import jax.numpy as jnp
    import numpy as np

    findings: List[Finding] = []
    n = IR_NODE_COUNTS[1]  # 12: avoids colliding with the probe batch (8)

    def dense_offenders(jaxpr, extent: int):
        hits = set()
        for eqn in iter_eqns(jaxpr):
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                shape = tuple(getattr(aval, "shape", ()) or ())
                if (
                    sum(1 for d in shape if d == extent) >= 2
                    and int(np.prod(shape or (0,))) >= extent * extent
                ):
                    hits.add((eqn.primitive.name, shape))
        return sorted(hits)

    # -- MUR600, rule cells --------------------------------------------------
    for name in SPARSE_DENSE_FREE:
        path, line = _rule_anchor(name)
        try:
            prog = build_canonical(name, n, "float32", sparse=True)
            hits = dense_offenders(trace_jaxpr(prog), n)
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR600", path, line,
                f"aggregator '{name}' (sparse) crashed the dense-free "
                f"sweep: {type(e).__name__}: {e}",
            ))
            continue
        if hits:
            findings.append(Finding(
                "MUR600", path, line,
                f"aggregator '{name}' (sparse) traces O(N^2) value(s) "
                f"{hits[:4]} — the sparse exchange engine must never "
                "materialize a node-by-node object (use [k, N] edge-mask "
                "forms and rolls)",
            ))

    # -- MUR600, full round program -----------------------------------------
    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "rounds.py")
    try:
        import jax

        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.core.rounds import build_round_program
        from murmura_tpu.data.base import FederatedArrays
        from murmura_tpu.faults.schedule import FaultSpec
        from murmura_tpu.models import make_mlp

        s = 16
        rng = np.random.default_rng(0)
        data = FederatedArrays(
            x=rng.normal(size=(n, s, _PROBE_IN)).astype(np.float32),
            y=rng.integers(0, _PROBE_CLASSES, size=(n, s)).astype(np.int32),
            mask=np.ones((n, s), np.float32),
            num_samples=np.full((n,), s),
            num_classes=_PROBE_CLASSES,
        )
        model = make_mlp(
            input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
        )
        offsets = tuple(canonical_offsets(n))
        agg = build_aggregator(
            "fedavg",
            {"exchange_offsets": list(offsets), "sparse_exchange": True},
            model_dim=_probe_model()[2], total_rounds=5,
        )
        # Faults armed: the alive/quarantine/scrub edge folds are the part
        # of the round body most tempted to rebuild [N, N].
        prog = build_round_program(
            model, agg, data, total_rounds=5, batch_size=8,
            sparse_offsets=offsets, faults=FaultSpec(),
        )
        args = (
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(0),
            jnp.ones((len(offsets), n), jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.ones((n,), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
        )
        hits = dense_offenders(jax.make_jaxpr(prog.train_step)(*args), n)
        if hits:
            findings.append(Finding(
                "MUR600", anchor, 1,
                f"the faulted sparse round program traces O(N^2) value(s) "
                f"{hits[:4]} — sparse-mode adjacency folds must stay in "
                "[k, N] edge-mask space (rolls of node flags)",
            ))
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        findings.append(Finding(
            "MUR600", anchor, 1,
            f"the sparse round-program dense-free sweep crashed: "
            f"{type(e).__name__}: {e}",
        ))

    # -- MUR601 --------------------------------------------------------------
    # The flagship rules compare sparse vs circulant inventories; every
    # swept rule is ALSO held to its declared_collectives("sparse") set,
    # which is how sketchguard's tighter sparse declaration ({"ppermute"}
    # — its sparse filter runs in circulant sketch space while its
    # circulant mode still gathers the dense sketches) stays enforced.
    for name in SPARSE_INVENTORY_RULES + ("sketchguard",):
        path, line = _rule_anchor(name)
        try:
            sparse_prog = build_canonical(
                name, n, "float32", sparse=True, node_axis_sharded=True
            )
            inv_sparse = collective_inventory(sparse_prog)
            if name in SPARSE_INVENTORY_RULES:
                circ_prog = build_canonical(
                    name, n, "float32", circulant=True,
                    node_axis_sharded=True,
                )
                inv_circ = collective_inventory(circ_prog)
            else:
                inv_circ = inv_sparse
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR601", path, line,
                f"aggregator '{name}' crashed the sparse collective "
                f"inventory sweep: {type(e).__name__}: {e}",
            ))
            continue
        if inv_sparse is None or inv_circ is None:
            warnings.warn(
                "murmura check --ir: fewer than 2 devices available — the "
                "MUR601 sparse collective inventory is unobservable on "
                "this platform (run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
                stacklevel=2,
            )
            break
        if name in SPARSE_INVENTORY_RULES and inv_sparse != inv_circ:
            findings.append(Finding(
                "MUR601", path, line,
                f"aggregator '{name}' sparse mode lowers to "
                f"{sorted(inv_sparse)} but its circulant mode lowers to "
                f"{sorted(inv_circ)} — the [k, N] edge-mask weighting must "
                "not change the exchange's communication (rolls stay "
                "boundary ppermutes; nothing gathers)",
            ))
        declared = sparse_prog.agg.declared_collectives("sparse")
        stray = inv_sparse - (declared or frozenset())
        if stray:
            findings.append(Finding(
                "MUR601", path, line,
                f"aggregator '{name}' sparse mode lowers to undeclared "
                f"collective(s) {sorted(stray)} (declared sparse set: "
                f"{sorted(declared or ())}) — either the sparse path grew "
                "unintended communication or its collectives declaration "
                "is stale",
            ))
    return findings


# Rules whose circulant/sparse exchange accepts the int8 compressed
# payload (AggregatorDef.quantized_exchange — they touch the broadcast
# only through the shared roll kernels).  MUR700 runs over the flagship
# subset; the remaining quantized rules share the same kernels, so the
# payload contract transfers.
QUANTIZED_EXCHANGE_RULES: Tuple[str, ...] = (
    "fedavg", "krum", "balance", "median", "trimmed_mean",
    "geometric_median",
)
MUR700_RULES: Tuple[str, ...] = ("fedavg", "krum", "median")
_COMPRESS_BLOCK = 64

# Only lines whose OPCODE is a collective (`= <shape> <op>(...)`), not
# every line that references a collective's result name as a fusion
# operand.  The capture starts at the RESULT shape: this XLA prints operands
# by name only (`collective-permute(%slice.1)`), so the result shape is the
# one place the moved dtype and width are written — equal to the operand's
# for a permute/all-to-all, its gathered/scattered twin otherwise (the
# exchanged width P survives in both).
_COLL_OP_LINE_RE = re.compile(
    r"^.*?=\s*(\S.*?\s(?:collective-permute|all-gather|all-to-all|"
    r"reduce-scatter)(?:-start)?\(.*)$",
    re.MULTILINE,
)
_FLOAT_SHAPE_RE = re.compile(r"\b(f32|bf16|f64)\[([0-9,]*)\]")


def float_exchange_operands(hlo_text: str, width: int):
    """(offending floats, collective statements) of an HLO module:
    floating shapes of exchanged width (any dim >= ``width`` — boundary
    roll slices are [o, P]) appearing as the result or an operand of a
    collective op.  The MUR700 scan,
    factored out so its negatives are unit-testable
    (tests/test_analysis_ir.py)."""
    coll_lines = _COLL_OP_LINE_RE.findall(hlo_text)
    offending = sorted({
        m.group(0)
        for ln in coll_lines
        for m in _FLOAT_SHAPE_RE.finditer(ln)
        if any(
            d >= width for d in (int(x) for x in m.group(2).split(",") if x)
        )
    })
    return offending, coll_lines


@_ir_family("MUR700", "core/rounds.py")
def check_compressed_exchange() -> List[Finding]:
    """MUR700/701/702: the compressed exchange moves compressed bytes and
    is IR-inert (docs/PERFORMANCE.md; ops/compress.py).

    MUR700 — the compressed payload is what crosses the collective: each
    MUR700_RULES cell is compiled with the node axis sharded and an int8
    payload standing in for the broadcast; no collective in the lowered
    SPMD program may carry a floating operand of exchanged width (a dim >=
    the flat model dimension — boundary roll slices are [o, P]), and at
    least one int8 collective must be present (the positive control that
    keeps the scan non-vacuous).  Runs in circulant and sparse modes; the
    dense path is documented as values-compressed only (the gathered
    matmul operand is the dequantized tensor).

    MUR701 — compression is recompile-free across rounds: an int8 +
    error-feedback round program compiles once and rounds with different
    adjacency values reuse the executable (CompileTracker) — scales,
    residuals and reference estimates are traced values, never structure.

    MUR702 — the error-feedback state is donation-clean: the compressed
    round step's donated buffers (params + agg_state including the [N, P]
    residual) are all aliased in the compiled executable; a lost alias
    would cost a full extra [N, P] copy per round.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.aggregation.base import AggContext
    from murmura_tpu.analysis.sanitizers import RecompileError, track_compiles
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp
    from murmura_tpu.ops.compress import (
        CompressionSpec,
        Int8Blocks,
        quantize_int8,
    )

    findings: List[Finding] = []
    n = IR_NODE_COUNTS[1]  # 12: distinct from the probe batch and P dims
    dim = IR_MODEL_DIM

    # -- MUR700 ------------------------------------------------------------
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    usable = [c for c in (2, 4) if c <= len(devices) and n % c == 0]
    if not usable:
        warnings.warn(
            "murmura check --ir: fewer than 2 devices available — the "
            "MUR700 compressed-payload inventory is unobservable on this "
            "platform (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
    else:
        mesh = Mesh(np.array(devices[: max(usable)]), ("nodes",))
        node_s = NamedSharding(mesh, P("nodes"))
        repl = NamedSharding(mesh, P())
        edge_s = NamedSharding(mesh, P(None, "nodes"))
        for name in MUR700_RULES:
            path, line = _rule_anchor(name)
            for mode in ("circulant", "sparse"):
                try:
                    rng = np.random.default_rng(0)
                    case = dict(AGG_CASES[name])
                    offsets = canonical_offsets(n)
                    case["exchange_offsets"] = offsets
                    if mode == "sparse":
                        case["sparse_exchange"] = True
                    agg = build_aggregator(
                        name, case, model_dim=dim, total_rounds=10
                    )
                    own = jnp.asarray(
                        rng.normal(size=(n, dim)) * 0.1, jnp.float32
                    )
                    bcast = jnp.asarray(
                        rng.normal(size=(n, dim)) * 0.1, jnp.float32
                    )
                    qb = quantize_int8(bcast, _COMPRESS_BLOCK)
                    if mode == "sparse":
                        adj = jnp.ones((len(offsets), n), jnp.float32)
                        adj_s = edge_s
                    else:
                        adj = jnp.asarray(_canonical_adj(n, circulant=True))
                        adj_s = node_s
                    state = {
                        k: jnp.asarray(v)
                        for k, v in agg.init_state(n).items()
                    }
                    ctx = AggContext(
                        total_rounds=10, num_classes=_PROBE_CLASSES,
                        node_axis_sharded=True,
                    )

                    def fn(own, q, scale, adj, ridx, state):  # murmura: traced
                        qv = Int8Blocks(
                            q, scale, _COMPRESS_BLOCK, dim, jnp.float32
                        )
                        return agg.aggregate(own, qv, adj, ridx, state, ctx)

                    args = (
                        own, qb.q, qb.scale, adj,
                        jnp.asarray(0.0, jnp.float32), state,
                    )
                    in_s = (
                        node_s, node_s, node_s, adj_s, repl,
                        {k: node_s for k in state},
                    )
                    # One-shot analysis compile per cell, not a hot path
                    # (the MUR204 pattern).
                    jitted = jax.jit(fn, in_shardings=in_s)  # murmura: ignore[MUR004]
                    txt = jitted.lower(*args).compile().as_text()
                except Exception as e:  # noqa: BLE001 — a crash IS the finding
                    findings.append(Finding(
                        "MUR700", path, line,
                        f"aggregator '{name}' ({mode}) crashed the "
                        f"compressed-payload sweep: {type(e).__name__}: {e}",
                    ))
                    continue
                offending, coll_lines = float_exchange_operands(txt, dim)
                if offending:
                    findings.append(Finding(
                        "MUR700", path, line,
                        f"aggregator '{name}' ({mode}, compressed) moves "
                        f"full-width float operand(s) {offending[:4]} "
                        "through a collective — the compressed int8 "
                        "payload (plus per-block scales) is what must "
                        "cross; dequantize after the roll, not before",
                    ))
                if coll_lines and not any("s8[" in ln for ln in coll_lines):
                    findings.append(Finding(
                        "MUR700", path, line,
                        f"aggregator '{name}' ({mode}, compressed) lowers "
                        "to no int8 collective at all — the payload scan "
                        "is vacuous; the exchange no longer moves the "
                        "compressed representation",
                    ))

    # -- MUR701 / MUR702 over a full compressed round program ---------------
    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "rounds.py")
    n4, s = 4, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n4, s, _PROBE_IN)).astype(np.float32),
        y=rng.integers(0, _PROBE_CLASSES, size=(n4, s)).astype(np.int32),
        mask=np.ones((n4, s), np.float32),
        num_samples=np.full((n4,), s),
        num_classes=_PROBE_CLASSES,
    )
    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )
    agg = build_aggregator(
        "fedavg", {}, model_dim=_probe_model()[2], total_rounds=5
    )
    spec = CompressionSpec("int8", block=_COMPRESS_BLOCK, error_feedback=True)
    prog = build_round_program(
        model, agg, data, total_rounds=5, batch_size=8, compression=spec
    )
    d = {k: jnp.asarray(v) for k, v in prog.data_arrays.items()}

    def args_for(adj_seed: int, r: int):
        rng_a = np.random.default_rng(adj_seed)
        adj = (rng_a.uniform(size=(n4, n4)) < 0.8).astype(np.float32)
        np.fill_diagonal(adj, 0.0)
        return (
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(r),
            jnp.asarray(adj),
            jnp.zeros((n4,), jnp.float32),
            jnp.asarray(float(r), jnp.float32),
            d,
        )

    # One-shot analysis compile, not a hot path (the MUR204 pattern).
    step = jax.jit(prog.train_step)  # murmura: ignore[MUR004]
    try:
        with track_compiles() as tracker:
            tracker.begin("warmup")
            jax.block_until_ready(step(*args_for(0, 0))[0])
            tracker.end(allow=True)
            for r in (1, 2):
                tracker.begin(f"round {r}")
                jax.block_until_ready(step(*args_for(r, r))[0])
                tracker.end(allow=False)
    except RecompileError as e:
        findings.append(Finding(
            "MUR701", anchor, 1,
            f"varying round inputs recompiled the compressed round step "
            f"({e}) — scales, residuals and reference estimates must reach "
            "the program as traced values, never as structure",
        ))

    args = args_for(0, 0)
    donated = len(jax.tree_util.tree_leaves(args[0])) + len(
        jax.tree_util.tree_leaves(args[1])
    )
    # One-shot analysis compile, not a hot path (the MUR204 pattern).
    dstep = jax.jit(prog.train_step, donate_argnums=(0, 1))  # murmura: ignore[MUR004]
    txt = dstep.lower(*args).compile().as_text()
    aliased = len(_ALIAS_RE.findall(txt))
    if aliased < donated:
        findings.append(Finding(
            "MUR702", anchor, 1,
            f"compressed round step: only {aliased} of {donated} donated "
            "buffers (params + agg_state including the error-feedback "
            "residual) are aliased in the compiled executable — the rest "
            "pay a full extra copy per round despite donate_argnums=(0, 1)",
        ))
    return findings


# Rules that surface per-node audit taps under telemetry.audit_taps
# (tap_* stats).  MUR400/402 run over exactly this set; a new tapped rule
# joins the contract by being added here.
TAPPED_RULES: Tuple[str, ...] = ("krum", "balance", "ubar", "evidential_trust")


@_ir_family("MUR400", "core/rounds.py")
def check_telemetry_taps() -> List[Finding]:
    """MUR400/MUR402: the audit taps are IR-inert (docs/OBSERVABILITY.md).

    The telemetry subsystem's core promise is that observing a round does
    not change it.  Two machine-checked halves:

    MUR400 — taps add zero collectives: each tapped rule's sharded-lowered
    collective inventory with ``ctx.audit`` on equals the untapped
    inventory (circulant taps are roll-assembled so they stay
    ppermute-only; dense taps are axis reductions inside the already-
    declared all_reduce).

    MUR402 — tap recording toggles cause zero recompiles: a tapped round
    program compiles once, and rounds that fetch the tap metrics
    interleaved with rounds that ignore them reuse that executable
    (CompileTracker, analysis/sanitizers.py) — recording is a host-side
    decision, never a program change.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.sanitizers import RecompileError, track_compiles
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp

    findings: List[Finding] = []
    n_a = IR_NODE_COUNTS[0]

    # -- MUR400 ------------------------------------------------------------
    inventory_observable = True
    for name in TAPPED_RULES:
        for circulant in (False, True):
            path, line = _rule_anchor(name)
            try:
                base = build_canonical(
                    name, n_a, "float32", circulant, node_axis_sharded=True
                )
                tapped = build_canonical(
                    name, n_a, "float32", circulant, node_axis_sharded=True,
                    audit=True,
                )
                inv_base = collective_inventory(base)
                if inv_base is None:
                    inventory_observable = False
                    break
                inv_tap = collective_inventory(tapped)
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                findings.append(Finding(
                    "MUR400", path, line,
                    f"aggregator '{name}' ({_mode(circulant)}) crashed the "
                    f"tapped inventory sweep: {type(e).__name__}: {e}",
                ))
                continue
            stray = (inv_tap or frozenset()) - inv_base
            if stray:
                findings.append(Finding(
                    "MUR400", path, line,
                    f"aggregator '{name}' ({_mode(circulant)}) audit taps "
                    f"lower to collective(s) {sorted(stray)} absent from "
                    "the untapped program — observing a round must not add "
                    "communication (assemble circulant taps from rolls, "
                    "dense taps from declared-inventory reductions)",
                ))
        if not inventory_observable:
            break
    if not inventory_observable:
        warnings.warn(
            "murmura check --ir: fewer than 2 devices available — the "
            "MUR400 tapped collective inventory is unobservable on this "
            "platform (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )

    # -- MUR402 ------------------------------------------------------------
    pkg = Path(__file__).resolve().parent.parent
    anchor = str(pkg / "core" / "rounds.py")
    n, s = 4, 16
    rng = np.random.default_rng(0)
    data = FederatedArrays(
        x=rng.normal(size=(n, s, _PROBE_IN)).astype(np.float32),
        y=rng.integers(0, _PROBE_CLASSES, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=_PROBE_CLASSES,
    )
    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES
    )
    agg = build_aggregator(
        "krum", dict(AGG_CASES["krum"]), model_dim=_probe_model()[2],
        total_rounds=5,
    )
    tapped_prog = build_round_program(
        model, agg, data, total_rounds=5, batch_size=8, audit_taps=True
    )
    adj = jnp.asarray(_canonical_adj(n, circulant=False))
    d = {k: jnp.asarray(v) for k, v in tapped_prog.data_arrays.items()}
    # One-shot analysis compile, not a hot path (the MUR204 pattern).
    step = jax.jit(tapped_prog.train_step)  # murmura: ignore[MUR004]

    def run_round(r: int, fetch_taps: bool):
        out = step(
            tapped_prog.init_params,
            {k: jnp.asarray(v) for k, v in tapped_prog.init_agg_state.items()},
            jax.random.PRNGKey(r),
            adj,
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(float(r), jnp.float32),
            d,
        )
        params, _state, metrics = out
        if fetch_taps:
            # A recording round: the host fetches the per-node tap arrays.
            jax.device_get(
                {k: v for k, v in metrics.items() if k.startswith("agg_tap_")}
            )
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])

    try:
        with track_compiles() as tracker:
            tracker.begin("warmup")
            run_round(0, fetch_taps=True)
            tracker.end(allow=True)
            for r, fetch in ((1, False), (2, True), (3, False)):
                tracker.begin(f"round {r} (record={fetch})")
                run_round(r, fetch_taps=fetch)
                tracker.end(allow=False)
    except RecompileError as e:
        findings.append(Finding(
            "MUR402", anchor, 1,
            f"toggling audit-tap recording across rounds recompiled the "
            f"tapped round step ({e}) — tap recording must be a host-side "
            "decision over a single compiled executable, never a program "
            "change",
        ))
    return findings


def _unwired_family_findings(module, registry: Dict[str, Any]) -> List[Finding]:
    """Module-level ``check_*`` callables that are neither in the module's
    check-family registry nor a known entry point — a new MUR family that
    would otherwise silently never run in ``check``/tier-1."""
    findings: List[Finding] = []
    mod_path = str(Path(module.__file__).resolve())
    for attr, obj in sorted(vars(module).items()):
        if not attr.startswith("check_") or not callable(obj):
            continue
        if attr in registry or attr in _CHECK_ENTRY_POINTS:
            continue
        findings.append(Finding(
            "MUR205", mod_path, 1,
            f"{module.__name__.rsplit('.', 1)[-1]}.{attr} is a check "
            "family that is not registered in its module's check-family "
            "registry — it will never run in `check`/tier-1; register it "
            "(@_ir_family in analysis/ir.py, @_family in analysis/flow.py) "
            "or rename it",
        ))
    return findings


def check_coverage() -> List[Finding]:
    """MUR205: registry <-> canonical-case bijection (the MUR101
    counterpart that keeps every other MUR2xx rule non-vacuous), plus the
    check-family wiring audit: every module-level ``check_*`` function in
    analysis/ir.py, analysis/flow.py and analysis/durability.py must be
    enumerated by its module's check-family registry (IR_CHECK_FAMILIES /
    FLOW_CHECK_FAMILIES / DURABILITY_CHECK_FAMILIES) — enumeration comes
    from the registry, never a hand-maintained call list, so a future MUR
    family that is written but not wired into
    ``check_ir``/``check_flow``/``check_durability`` is a finding, not a
    silent gap."""
    import sys

    from murmura_tpu.aggregation import AGGREGATORS

    pkg = Path(__file__).resolve().parent.parent
    agg_path = str(pkg / "aggregation" / "__init__.py")
    here = str(Path(__file__).resolve())
    findings: List[Finding] = []
    for name in sorted(set(AGGREGATORS) - set(AGG_CASES)):
        findings.append(Finding(
            "MUR205", agg_path, 1,
            f"aggregation rule '{name}' has no AGG_CASES entry "
            "(analysis/ir.py) — the IR contracts (MUR200-203) and cost "
            "budgets never run for it; add a canonical case",
        ))
    for name in sorted(set(AGG_CASES) - set(AGGREGATORS)):
        findings.append(Finding(
            "MUR205", here, 1,
            f"AGG_CASES entry '{name}' names no registered aggregation "
            "rule — remove the stale canonical case",
        ))
    from murmura_tpu.analysis import adaptive as adaptive_mod
    from murmura_tpu.analysis import durability as durability_mod
    from murmura_tpu.analysis import flow as flow_mod

    findings.extend(
        _unwired_family_findings(sys.modules[__name__], IR_CHECK_FAMILIES)
    )
    findings.extend(
        _unwired_family_findings(flow_mod, flow_mod.FLOW_CHECK_FAMILIES)
    )
    findings.extend(
        _unwired_family_findings(
            durability_mod, durability_mod.DURABILITY_CHECK_FAMILIES
        )
    )
    findings.extend(
        _unwired_family_findings(
            adaptive_mod, adaptive_mod.ADAPTIVE_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import staleness as staleness_mod

    findings.extend(
        _unwired_family_findings(
            staleness_mod, staleness_mod.STALE_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import pipeline as pipeline_mod

    findings.extend(
        _unwired_family_findings(
            pipeline_mod, pipeline_mod.PIPELINE_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import sharded as sharded_mod

    findings.extend(
        _unwired_family_findings(
            sharded_mod, sharded_mod.SHARDED_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import composition as composition_mod

    findings.extend(
        _unwired_family_findings(
            composition_mod, composition_mod.COMPOSE_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import memory as memory_mod

    findings.extend(
        _unwired_family_findings(
            memory_mod, memory_mod.MEMORY_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import serve as serve_check_mod

    findings.extend(
        _unwired_family_findings(
            serve_check_mod, serve_check_mod.SERVE_CHECK_FAMILIES
        )
    )
    from murmura_tpu.analysis import observe as observe_mod

    findings.extend(
        _unwired_family_findings(
            observe_mod, observe_mod.OBSERVE_CHECK_FAMILIES
        )
    )
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_IR_MEMO: Optional[List[Finding]] = None


def _apply_suppressions(findings: List[Finding]) -> List[Finding]:
    """Line suppressions at each finding's anchor (the factory def line)."""
    out: List[Finding] = []
    by_path: Dict[str, List[Finding]] = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    for path, fs in by_path.items():
        try:
            lines = Path(path).read_text().splitlines()
        except OSError:
            out.extend(fs)
            continue
        out.extend(_suppressed(fs, lines))
    return out


def check_ir(force: bool = False) -> List[Finding]:
    """Run MUR200-205 over the canonical grid; returns findings (empty =
    every IR contract holds).  Memoized per process — the tier-1 gate and
    the CLI test share one sweep.

    Cost budgets (MUR206) live in :mod:`murmura_tpu.analysis.budgets` and
    are composed by ``run_check``, not here — they need AOT compiles per
    grid cell while everything here except MUR202/204 is trace-only.
    """
    global _IR_MEMO
    if _IR_MEMO is not None and not force:
        return list(_IR_MEMO)

    _ensure_host_devices()
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = list(check_coverage())
    n_a, n_b = IR_NODE_COUNTS
    for name in sorted(AGGREGATORS):
        if name not in AGG_CASES:
            continue  # already a MUR205 finding
        for circulant in (False, True):
            # A crash anywhere — building the canonical program, tracing,
            # or the sharded lowering — IS the finding: one broken rule
            # must not take down the whole check run and hide every other
            # finding.
            try:
                prog = build_canonical(name, n_a, "float32", circulant)
                prog_b = build_canonical(name, n_b, "float32", circulant)
                prog_bf16 = build_canonical(name, n_a, "bfloat16", circulant)
                sharded = build_canonical(
                    name, n_a, "float32", circulant, node_axis_sharded=True
                )
                jaxpr = trace_jaxpr(prog)
                findings.extend(_check_callbacks(name, prog, jaxpr))
                findings.extend(_check_dtypes(name, prog, prog_bf16))
                findings.extend(_check_structure(name, prog, prog_b))
                findings.extend(_check_collectives(name, sharded))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(name)
                findings.append(Finding(
                    "MUR205", path, line,
                    f"aggregator '{name}' ({_mode(circulant)}) crashed the "
                    f"canonical IR sweep: {type(e).__name__}: {e}",
                ))
    # Round-program-level families run off the registry — adding a family
    # is one decorator, and an unregistered ``check_*`` function is itself
    # a MUR205 finding (check_coverage's unwired-family scan).
    pkg = Path(__file__).resolve().parent.parent
    for fam_name, (fam, crash_rule, crash_anchor) in IR_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                crash_rule, str(pkg / crash_anchor), 1,
                f"the '{fam_name}' IR contracts crashed: "
                f"{type(e).__name__}: {e}",
            ))

    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _IR_MEMO = list(findings)
    return findings
