"""Param-axis sharding contracts (MUR1300-1303) — part of the default
package check (docs/PERFORMANCE.md "Param-axis sharding").

The ``"param"`` mesh axis (parallel/mesh.py) splits the flattened
parameter vector so every [N, P] round tensor is resident at
``N x P/shards`` per device.  Each link of that story carries an
invariant that must stay machine-checked or the memory-scaling claim
silently rots:

- **MUR1300 — sharded-P collective inventory.**  Compile each rule's
  canonical circulant/sparse cell on a ("seed", "nodes", "param") mesh
  with the [N, P] operands column-sharded: the lowered program's
  collectives must stay within the rule's DECLARED inventory for the
  mode plus at most ``all_reduce`` — the one new collective param
  sharding is allowed to add is the small scalar ``psum`` over the param
  groups (distance partials, norm partials).  Every all-reduce in the
  optimized HLO must be strictly smaller than the [N, P] class: a
  full-width gathered or reduced [N, P] tensor is exactly the resident
  copy the axis exists to eliminate.
- **MUR1301 — recompile-free sharded rounds.**  A param-sharded run
  (backend tpu, ``tpu.param_shards`` > 1 over the forced-host mesh)
  compiles once and every subsequent round is value-only
  (:class:`~murmura_tpu.analysis.sanitizers.CompileTracker`) — shard
  layout is program structure, round data is values.
- **MUR1302 — shards=1 bit-parity.**  ``build_round_program(...,
  param_shards=1)`` must be byte-identical to the default build: same
  traced jaxpr signature, ``flat_dim == model_dim`` (no pad), identical
  initial carried state.  The sharded code path may not perturb the
  unsharded program in any way.
- **MUR1303 — sharded execution parity.**  The MUR1300 cell's sharded
  program must produce the same aggregation output as the unsharded
  single-device cell to float-reassociation tolerance (the shard-local
  partial reductions regroup f32 sums; they must not change the math).

Probe-based rules (ubar, evidential_trust) are exempt from
MUR1300/MUR1303 with a documented reason (the MUR802-style limitation
pattern): their probe sweeps unravel every broadcast row into a full
model for the forward pass, so their sharded-P program necessarily
re-gathers rows — correct, but not psum-only, and not the regime param
sharding targets (a 50M-param model is not probe-evaluated N x N times
per round).

MUR1301 compiles and runs tiny programs (the check_durability cost
profile), so the family is memoized per process and runs by default only
for the package check; tests gate representative cells per tier-1 run
(tests/test_param_sharding.py) and negatives prove each probe can fire.
"""

import re
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from murmura_tpu.analysis.lint import Finding

# Registry of check families in this module: name -> callable, scanned by
# analysis/ir.py's check_coverage so an unwired family is a MUR205
# finding (the flow.py/pipeline.py twin pattern).
SHARDED_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    SHARDED_CHECK_FAMILIES[fn.__name__] = fn
    return fn


_PKG = Path(__file__).resolve().parent.parent
_MESH_PATH = str(_PKG / "parallel" / "mesh.py")
_ROUNDS_PATH = str(_PKG / "core" / "rounds.py")

# The exchange modes whose declared inventories the sharded-P contract
# extends: circulant (tpu.exchange: ppermute) and the sparse [k, N]
# edge-mask engine.  Dense mode already declares all_gather/all_reduce,
# so "ppermute-only on nodes" is not its contract to keep.
SHARDED_MODES: Tuple[str, ...] = ("circulant", "sparse")

# The canonical param-axis layout the probes compile on: 8 forced host
# devices as ("seed", "nodes", "param") = (1, 2, 4).
_SHARDS = 4
_NODE_AX = 2

# Probe-rule exemption (see module docstring).
SHARDED_EXEMPT: Dict[str, str] = {
    "ubar": "the probe sweep unravels each broadcast row into a full "
    "model per forward pass — the sharded-P program re-gathers rows by "
    "construction",
    "evidential_trust": "the trust probe sweep unravels each broadcast "
    "row into a full model per forward pass — the sharded-P program "
    "re-gathers rows by construction",
}


def _rule_anchor(rule: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor as anchor

    return anchor(rule)


def _param_mesh():
    """The (1, 2, 4) check mesh, or None when the platform cannot give
    8 devices (the inventory is then unobservable — degrade with a
    warning, the MUR202 convention)."""
    import jax
    from jax.sharding import Mesh

    from murmura_tpu.analysis.ir import _ensure_host_devices

    _ensure_host_devices(8)
    devices = jax.devices()
    if len(devices) < _NODE_AX * _SHARDS:
        return None
    sel = np.array(devices[: _NODE_AX * _SHARDS])
    return Mesh(
        sel.reshape(1, _NODE_AX, _SHARDS), ("seed", "nodes", "param")
    )


# --------------------------------------------------------------------------
# MUR1300 + MUR1303 — sharded-P collective inventory and execution parity
# --------------------------------------------------------------------------

# LHS shapes of an HLO all-reduce (covers tuple-shaped variants): capture
# everything between "= " and " all-reduce(" and pull each "[dims]" out.
_AR_LINE_RE = re.compile(r"= (.{0,200}?) all-reduce(?:-start)?\(")
_DIMS_RE = re.compile(r"\[([0-9,]*)\]")


def oversized_all_reduces(hlo_text: str, max_elements: int) -> List[int]:
    """Element counts of all-reduce outputs exceeding ``max_elements`` —
    the "small scalar psum" half of the MUR1300 contract."""
    bad: List[int] = []
    for m in _AR_LINE_RE.finditer(hlo_text):
        for dims in _DIMS_RE.findall(m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n > max_elements:
                bad.append(n)
    return bad


def _sharded_cell(rule: str, mode: str, mesh):
    """(jitted sharded fn, canonical cell) for one (rule, mode) cell on
    the param mesh: [N, dim] operands and state column-sharded, the cell
    traced under the param-axis scope so the chunk-alignment and pallas
    consumers see the layout (parallel/mesh.param_axis_scope)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from murmura_tpu.analysis.ir import build_canonical
    from murmura_tpu.parallel.mesh import param_axis_scope

    prog = build_canonical(
        rule, 8, circulant=(mode == "circulant"), node_axis_sharded=True,
        sparse=(mode == "sparse"),
    )
    if prog.dim % _SHARDS:
        raise ValueError(
            f"canonical dim {prog.dim} not divisible by {_SHARDS} shards"
        )
    node_s = NamedSharding(mesh, P("nodes"))
    repl = NamedSharding(mesh, P())
    edge_s = NamedSharding(mesh, P(None, "nodes"))
    flat_s = NamedSharding(mesh, P("nodes", "param"))

    base = prog.arg_shardings(node_s, repl, edge_s)

    def flatten_spec(arg, spec):
        # [N, dim] leaves gain the param axis; everything else keeps the
        # canonical node-leading layout.
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

    return jax.jit(scoped, in_shardings=in_s), prog


def inventory_cell_findings(rule: str, mode: str, mesh=None) -> List[Finding]:
    """One (rule, mode) MUR1300 + MUR1303 cell (exposed per-cell so tests
    gate a subset — tests/test_param_sharding.py)."""
    import jax

    from murmura_tpu.analysis.ir import _HLO_COLLECTIVES, _COLL_RE

    path, line = _rule_anchor(rule)
    if mesh is None:
        mesh = _param_mesh()
    if mesh is None:
        warnings.warn(
            "MUR1300 sharded-P collective inventory is unobservable on "
            "this platform (needs >= 8 devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
            stacklevel=2,
        )
        return []
    jitted, prog = _sharded_cell(rule, mode, mesh)
    lowered = jitted.lower(*prog.args)
    txt = lowered.compile().as_text()
    findings: List[Finding] = []

    inventory = frozenset(
        _HLO_COLLECTIVES[m] for m in _COLL_RE.findall(txt)
    )
    declared = prog.agg.declared_collectives(mode)
    if declared is not None:
        allowed = frozenset(declared) | {"all_reduce"}
        stray = inventory - allowed
        if stray:
            findings.append(Finding(
                "MUR1300", path, line,
                f"[{rule}/{mode}] the param-sharded lowering contains "
                f"collective(s) {sorted(stray)} outside the declared "
                f"{sorted(declared)} + the all_reduce psum — param "
                "sharding may add ONLY the small scalar reduction over "
                "the param groups",
            ))
    limit = (prog.n * prog.dim) // 2
    big = oversized_all_reduces(txt, limit)
    if big:
        findings.append(Finding(
            "MUR1300", path, line,
            f"[{rule}/{mode}] the param-sharded lowering all-reduces "
            f"tensor(s) of {sorted(set(big), reverse=True)} elements "
            f"(limit {limit}, strictly below the [N, P] class) — a "
            "full-width reduction re-materializes exactly the resident "
            "copy the param axis exists to eliminate",
        ))

    # -- MUR1303: execution parity vs the unsharded single-device cell --
    out_sh = jax.device_get(jitted(*prog.args)[0])
    out_ref = jax.device_get(jax.jit(prog.fn)(*prog.args)[0])
    if not np.allclose(
        np.asarray(out_sh, np.float32), np.asarray(out_ref, np.float32),
        rtol=5e-5, atol=5e-6,
    ):
        err = float(np.max(np.abs(
            np.asarray(out_sh, np.float32) - np.asarray(out_ref, np.float32)
        )))
        findings.append(Finding(
            "MUR1303", path, line,
            f"[{rule}/{mode}] the param-sharded aggregation diverges "
            f"from the single-device program by {err:.2e} — shard-local "
            "partial reductions may regroup f32 sums but must not "
            "change the math",
        ))
    return findings


@_family
def check_sharded_inventory() -> List[Finding]:
    """MUR1300/MUR1303 over ``AGGREGATORS x SHARDED_MODES`` (compiles one
    sharded cell per pair; probe rules exempt with reason)."""
    from murmura_tpu.aggregation import AGGREGATORS

    mesh = _param_mesh()
    if mesh is None:
        warnings.warn(
            "MUR1300/MUR1303 are unobservable on this platform (needs "
            ">= 8 devices)", stacklevel=2,
        )
        return []
    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        if rule in SHARDED_EXEMPT:
            continue
        for mode in SHARDED_MODES:
            try:
                findings.extend(inventory_cell_findings(rule, mode, mesh))
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                path, line = _rule_anchor(rule)
                findings.append(Finding(
                    "MUR1300", path, line,
                    f"[{rule}/{mode}] sharded-P inventory probe crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# MUR1301 — recompile-free sharded rounds (executable)
# --------------------------------------------------------------------------

# Representative cells (rule, topology mode): the full rule sweep is the
# MUR1300 trace pass; the executable recompile probe needs only one cell
# per storage layout of the adjacency input.
MUR1301_CELLS: Tuple[Tuple[str, str], ...] = (
    ("fedavg", "dense"),
    ("krum", "dense"),
    ("median", "sparse"),
)


def _cell_config(rule: str, mode: str, param_shards: int = _SHARDS):
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.config import Config

    raw: Dict[str, Any] = {
        "experiment": {"name": f"sharded-{rule}-{mode}", "seed": 7,
                       "rounds": 5},
        "topology": {"type": "ring", "num_nodes": 8},
        "aggregation": {"algorithm": rule,
                        "params": dict(AGG_CASES.get(rule, {}))},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 40, "input_shape": [6],
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 6, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "tpu",
        "tpu": {"param_shards": param_shards, "param_dtype": "float32"},
    }
    if mode == "sparse":
        raw["topology"] = {"type": "exponential", "num_nodes": 8}
    elif mode != "dense":
        raise ValueError(f"unknown sharded mode {mode!r}")
    return Config.model_validate(raw)


def recompile_cell_findings(rule: str, mode: str = "dense") -> List[Finding]:
    """Run ONE (rule, mode) MUR1301 cell: 2 warmup rounds (the compile),
    then 3 more under CompileTracker — shard layout is program structure,
    round data is values, so nothing may recompile."""
    import jax

    from murmura_tpu.analysis.ir import _ensure_host_devices
    from murmura_tpu.analysis.sanitizers import track_compiles
    from murmura_tpu.utils.factories import build_network_from_config

    _ensure_host_devices(8)
    path, line = _rule_anchor(rule)
    if len(jax.devices()) < 2:
        warnings.warn(
            "MUR1301 is unobservable on this platform (needs >= 2 "
            "devices)", stacklevel=2,
        )
        return []
    net = build_network_from_config(_cell_config(rule, mode))
    net.train(rounds=2, verbose=False)
    with track_compiles() as tracker:
        net.train(rounds=3, verbose=False)
    if tracker.total:
        return [Finding(
            "MUR1301", path, line,
            f"[{rule}/{mode}] 3 param-sharded rounds after warmup "
            f"compiled {tracker.total} program(s) — the shard layout is "
            "program structure and round data is values, so sharded "
            "rounds must be value-only over one compiled program",
        )]
    return []


@_family
def check_sharded_recompile() -> List[Finding]:
    """MUR1301 over the representative cells (compiles and runs tiny
    sharded programs — the check_durability cost profile)."""
    findings: List[Finding] = []
    for rule, mode in MUR1301_CELLS:
        try:
            findings.extend(recompile_cell_findings(rule, mode))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1301", path, line,
                f"[{rule}/{mode}] sharded recompile probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return findings


# --------------------------------------------------------------------------
# MUR1302 — shards=1 bit-parity (trace-only)
# --------------------------------------------------------------------------


def _tiny_programs(rule: str):
    """(default build, param_shards=1 build) of one rule's tiny round
    program — identical in every argument except the explicit shards."""
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.base import FederatedArrays
    from murmura_tpu.models import make_mlp

    n, s = 5, 12
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
    common = dict(
        local_epochs=1, batch_size=8, lr=0.05, total_rounds=4, seed=7,
    )
    default = build_round_program(model, agg, data, **common)
    explicit = build_round_program(
        model, agg, data, param_shards=1, **common
    )
    return default, explicit


def bit_parity_findings(rule: str) -> List[Finding]:
    """One rule's MUR1302 probes: flat_dim == model_dim, identical
    initial carried state, identical traced jaxpr signature."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.analysis.ir import jaxpr_signature

    path, line = _rule_anchor(rule)
    default, explicit = _tiny_programs(rule)
    findings: List[Finding] = []
    if (
        explicit.flat_dim != explicit.model_dim
        or explicit.flat_dim != default.flat_dim
    ):
        findings.append(Finding(
            "MUR1302", _ROUNDS_PATH, 1,
            f"[{rule}] param_shards=1 padded the flat width "
            f"({explicit.flat_dim} vs model_dim {explicit.model_dim}) — "
            "the unsharded program must carry no pad",
        ))
    for k in set(default.init_agg_state) | set(explicit.init_agg_state):
        a = default.init_agg_state.get(k)
        b = explicit.init_agg_state.get(k)
        if a is None or b is None or not np.array_equal(
            np.asarray(a), np.asarray(b), equal_nan=True
        ):
            findings.append(Finding(
                "MUR1302", _ROUNDS_PATH, 1,
                f"[{rule}] initial carried state key '{k}' differs "
                "between the default and param_shards=1 builds",
            ))

    def trace(prog):
        n = prog.num_nodes
        adj = jnp.asarray(
            np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)
        )
        return jax.make_jaxpr(prog.train_step)(
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(0),
            adj,
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
        )

    if jaxpr_signature(trace(default)) != jaxpr_signature(trace(explicit)):
        findings.append(Finding(
            "MUR1302", _ROUNDS_PATH, 1,
            f"[{rule}] the param_shards=1 build traces a different "
            "program than the default build — the sharded code path must "
            "be byte-invisible at shards=1",
        ))
    return findings


@_family
def check_sharded_bit_parity() -> List[Finding]:
    """MUR1302 over every registered rule (trace-only: nothing
    compiles)."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for rule in sorted(AGGREGATORS):
        try:
            findings.extend(bit_parity_findings(rule))
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            path, line = _rule_anchor(rule)
            findings.append(Finding(
                "MUR1302", path, line,
                f"[{rule}] shards=1 bit-parity probe crashed: "
                f"{type(e).__name__}: {e}",
            ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_SHARDED_MEMO: Optional[List[Finding]] = None


def check_sharded(force: bool = False) -> List[Finding]:
    """Run MUR1300-1303; returns findings (empty = every param-axis
    sharding contract holds).  Memoized per process — the CLI and the
    test gate share one sweep."""
    global _SHARDED_MEMO
    if _SHARDED_MEMO is not None and not force:
        return list(_SHARDED_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in SHARDED_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR1300", str(Path(__file__).resolve()), 1,
                f"sharded check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _SHARDED_MEMO = list(findings)
    return findings
