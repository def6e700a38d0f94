"""jaxpr dataflow contracts (MUR800-804) — ``murmura check --flow``.

The third layer of the analysis subsystem, and the first that reasons about
*values* rather than program shape: two composable abstract domains over
the lowered jaxprs of every registered aggregation rule.

**Domain 1 — per-neighbor taint/influence (MUR800-802).**  Each exchanged
broadcast row is seeded with a distinct taint label and propagated through
the rule's jaxpr by a concrete taint interpreter: every equation is
evaluated on the canonical inputs while a boolean label tensor rides along.
The semantics track *value* dataflow — selection dataflow is excluded by
construction:

- comparison outputs carry no taint (they decide WHICH values are chosen,
  not what they are);
- ``sort`` permutes taints by the concrete sort permutation (each output
  element IS one input element);
- ``gather``/``dynamic_slice``/``top_k`` move the gathered elements' taints
  and ignore the index operands';
- ``select_n`` follows the concretely chosen case and drops the predicate;
- multiplication by an exact zero kills the other operand's taint (a
  0-weighted neighbor contributes nothing — sound because the MUR803
  scrub-dominance check separately proves rule math only sees finite
  values, so 0 * x == 0).

The result is, per output coordinate, the set of neighbors whose broadcast
VALUES can enter it — Krum analyzes to its single winner, the trimmed mean
to its kept interior, fedavg to the whole neighborhood.  MUR800 checks the
cardinality against the rule's declared ``AggregatorDef.influence``
contract; MUR801 requires every registered rule to declare one; MUR802
pins the analyzed per-node cardinality's parity across the
dense/circulant/sparse/compressed exchange modes of the same rule (all
built over the SAME canonical k-regular graph so the numbers are
comparable).

**Domain 2 — interval/finiteness (MUR803-804).**  A classic abstract
interpreter: whole-array [lo, hi] intervals plus a finiteness-contamination
flag propagated from the exchange inputs.  The contamination flag tracks
non-finiteness *originating from data* (diverged training math, attack
noise, bit-cast RNG output) — deliberate ``inf`` literals (sort padding)
stay clean, and arithmetic semantics are real-valued (float overflow is
out of scope; the runtime sentinel owns it).  The ``isfinite`` guard
pattern is recognized relationally: a predicate derived from
``isfinite(x)`` (through ``all``/``&``/``~``/broadcasts) discharges x's
contamination on the branch it implies finite, so the rounds.py sentinel
scrubs — ``where(isfinite(update).all(1)[:, None], update, snapshot)`` —
provably dominate.

- MUR803 runs the interpreter over full *faulted* round programs
  (attack + NaN sentinel armed) with divergence-capable seeds and fails if
  contamination can reach the output parameters or carried aggregation
  state — the static retirement of the ``0 * inf`` class PR 3's runtime
  sentinel handles dynamically.  A mask applied by multiplication instead
  of ``where``-replacement leaves the contamination flag set (0 * nan is
  nan), so the exact bug class PR 3 fixed by hand cannot come back
  silently.
- MUR804 scans every rule cell (all exchange modes) and the compression
  codec for division/rsqrt equations whose denominator interval contains
  zero given the post-scrub seeds (inputs finite but arbitrary, adjacency
  in [0, 1], the codec's symmetric-scale invariants) — the Weiszfeld
  ``1/max(d, nu)`` guards and compress.py's guarded scale division
  verify clean; an unguarded denominator is a finding anchored at its
  source line.

Suppression: MUR800-802 anchor to the rule factory ``def`` line (the IR
pass's convention); MUR803 anchors to core/rounds.py; MUR804 anchors to
the offending source line (falling back to the factory line), where the
ordinary ``# murmura: ignore[MUR804]`` applies.
"""

import contextlib
import dataclasses
import math
import warnings
from pathlib import Path
from typing import (
    Any,
    Callable,
    Dict,
    FrozenSet,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

import numpy as np

from murmura_tpu.analysis.lint import Finding

# --------------------------------------------------------------------------
# Canonical flow grid
# --------------------------------------------------------------------------

FLOW_N = 8  # nodes == taint labels; the canonical k-regular(4) graph
FLOW_DIM = 100  # non-probe flat dimension (pads to 4 blocks of 32)
FLOW_BLOCK = 32  # compressed-cell quant block (exercises padding: 100 % 32)
_PROBE_IN = 8
_PROBE_BATCH = 8
_PROBE_CLASSES = 4

# Exchange modes the influence analysis sweeps.  ``compressed`` applies to
# quantized_exchange rules only (the others receive the receiver-side
# dequantized tensor, which is taint-identical to the dense float path).
FLOW_MODES: Tuple[str, ...] = ("dense", "circulant", "sparse", "compressed")

# Check families this module registers (the ir.check_coverage registry
# sweep asserts every module-level ``check_*`` is wired through here).
FLOW_CHECK_FAMILIES: Dict[str, Callable[[], List[Finding]]] = {}


def _family(fn):
    FLOW_CHECK_FAMILIES[fn.__name__] = fn
    return fn


# --------------------------------------------------------------------------
# Shared jaxpr walking
# --------------------------------------------------------------------------


@contextlib.contextmanager
def _quiet_tracing():
    """Tracing/eager-binding rule cells constant-folds over deliberate inf
    padding; numpy's 'invalid value encountered in cast' warnings there
    are expected and non-actionable."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        yield


def _closed(sub) -> Any:
    """Normalize an eqn param that holds a jaxpr into a ClosedJaxpr."""
    from jax.extend import core as jex_core

    if isinstance(sub, jex_core.ClosedJaxpr):
        return sub
    return jex_core.ClosedJaxpr(sub, ())


def _sub_jaxpr(eqn):
    """The callee ClosedJaxpr of a call-like primitive, else None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None and (hasattr(sub, "eqns") or hasattr(sub, "jaxpr")):
            return _closed(sub)
    return None


def eqn_source(eqn) -> Optional[Tuple[str, int]]:
    """(path, line) of the user frame that created this equation, if the
    traceback survived tracing (it does for normal python-traced code)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return str(frame.file_name), int(frame.start_line)


# --------------------------------------------------------------------------
# Domain 1: concrete taint interpreter
# --------------------------------------------------------------------------

# Elementwise value maps: output taint is the broadcast-OR of operand
# taints (selection exclusion happens at comparisons, not here).
_ELEMENTWISE = frozenset({
    "add", "add_any", "sub", "pow", "integer_pow", "exp", "exp2", "log",
    "log2",
    "log1p", "expm1", "sqrt", "rsqrt", "cbrt", "abs", "sign", "neg",
    "floor", "ceil", "round", "tanh", "sin", "cos", "tan", "asin", "acos",
    "atan", "atan2", "sinh", "cosh", "asinh", "acosh", "atanh", "erf",
    "erfc", "erf_inv", "logistic", "lgamma", "digamma", "rem", "nextafter",
    "real", "imag", "square", "clamp", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "population_count",
    "clz", "reduce_precision", "copy", "convert_element_type",
    "bitcast_convert_type", "stop_gradient",
})

# Predicate producers: output carries NO taint (selection dataflow).
# and/or/not/xor join this set only for BOOLEAN operands — on integers the
# same primitives are bitwise VALUE ops (payload bit-twiddling, PRNG lanes)
# and must carry taint like any other arithmetic (see TaintEval._eqn).
_PREDICATES = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite",
})
_BOOL_OR_BITWISE = frozenset({"and", "or", "not", "xor"})


def _tz(L: int, shape) -> np.ndarray:
    return np.zeros((L,) + tuple(shape), bool)


def _bt(t: np.ndarray, L: int, shape) -> np.ndarray:
    """Broadcast a taint tensor to (L,) + shape (rank-aligning trailing
    dims, the numpy rule — lax elementwise operands share ranks)."""
    target = (L,) + tuple(shape)
    if t.shape == target:
        return t
    # Align trailing dims: insert axes after the label axis as needed.
    extra = len(target) - t.ndim
    if extra > 0:
        t = t.reshape(t.shape[:1] + (1,) * extra + t.shape[1:])
    return np.broadcast_to(t, target)


class TaintEval:
    """Concrete evaluator with per-label boolean taint riding each value."""

    def __init__(self, num_labels: int):
        self.L = num_labels
        self.unknown: Set[str] = set()

    # -- entry ------------------------------------------------------------

    def eval_closed(self, closed, pairs: Sequence[Tuple[Any, np.ndarray]]):
        jaxpr = closed.jaxpr
        env: Dict[Any, Tuple[Any, np.ndarray]] = {}

        def write(var, pair):
            env[var] = pair

        def read(atom):
            from jax.extend import core as jex_core

            if isinstance(atom, jex_core.Literal):
                v = np.asarray(atom.val)
                return v, _tz(self.L, v.shape)
            return env[atom]

        for var, const in zip(jaxpr.constvars, closed.consts):
            c = np.asarray(const)
            write(var, (c, _tz(self.L, c.shape)))
        if len(jaxpr.invars) != len(pairs):
            raise ValueError(
                f"taint eval got {len(pairs)} inputs for "
                f"{len(jaxpr.invars)} invars"
            )
        for var, pair in zip(jaxpr.invars, pairs):
            write(var, pair)

        for eqn in jaxpr.eqns:
            in_pairs = [read(a) for a in eqn.invars]
            outs = self._eqn(eqn, in_pairs)
            for var, pair in zip(eqn.outvars, outs):
                write(var, pair)
        return [read(a) for a in jaxpr.outvars]

    # -- helpers ----------------------------------------------------------

    def _concrete(self, eqn, vals) -> List[Any]:
        import warnings

        with warnings.catch_warnings():
            # Eager binds on inf-padded literals emit numpy cast warnings
            # (jax's own constant folding path) — expected, not actionable.
            warnings.simplefilter("ignore", RuntimeWarning)
            out = eqn.primitive.bind(*vals, **eqn.params)
        return list(out) if eqn.primitive.multiple_results else [out]

    def _coarse(self, eqn, pairs) -> List[Tuple[Any, np.ndarray]]:
        """Sound fallback: every output fully tainted by the join of all
        operand taints (any label set anywhere contaminates everything)."""
        vals = [p[0] for p in pairs]
        outs = self._concrete(eqn, vals)
        joined = np.zeros((self.L,), bool)
        for _, t in pairs:
            joined |= t.reshape(self.L, -1).any(axis=1)
        return [
            (
                o,
                np.broadcast_to(
                    joined.reshape((self.L,) + (1,) * np.ndim(o)),
                    (self.L,) + np.shape(o),
                ).copy(),
            )
            for o in outs
        ]

    # -- dispatch ---------------------------------------------------------

    def _eqn(self, eqn, pairs) -> List[Tuple[Any, np.ndarray]]:
        name = eqn.primitive.name.replace("-", "_")
        handler = getattr(self, f"_t_{name}", None)
        if handler is not None:
            return handler(eqn, pairs)
        if name in _BOOL_OR_BITWISE:
            dt = getattr(eqn.invars[0].aval, "dtype", None)
            if dt == np.bool_:
                name = "__predicate__"
            else:
                name = "__elementwise__"
        if name in _PREDICATES or name == "__predicate__":
            outs = self._concrete(eqn, [p[0] for p in pairs])
            return [(o, _tz(self.L, np.shape(o))) for o in outs]
        if name in _ELEMENTWISE or name == "__elementwise__":
            outs = self._concrete(eqn, [p[0] for p in pairs])
            out = outs[0]
            t = _tz(self.L, np.shape(out))
            for _, ti in pairs:
                t = t | _bt(ti, self.L, np.shape(out))
            return [(out, t)]
        sub = _sub_jaxpr(eqn)
        if sub is not None:
            return self.eval_closed(sub, pairs)
        self.unknown.add(name)
        return self._coarse(eqn, pairs)

    # -- structural primitives -------------------------------------------

    def _t_broadcast_in_dim(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        target = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        new_shape = [1] * len(target)
        for i, d in enumerate(bdims):
            new_shape[d] = np.shape(v)[i]
        t_out = np.broadcast_to(
            t.reshape((self.L,) + tuple(new_shape)), (self.L,) + target
        ).copy()
        return [(outs[0], t_out)]

    def _t_reshape(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        dims = eqn.params.get("dimensions")
        if dims is not None:
            t = np.transpose(t, (0,) + tuple(d + 1 for d in dims))
        t_out = t.reshape((self.L,) + tuple(eqn.params["new_sizes"]))
        return [(outs[0], t_out)]

    def _t_transpose(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        perm = tuple(eqn.params["permutation"])
        return [(outs[0], np.transpose(t, (0,) + tuple(p + 1 for p in perm)))]

    def _t_squeeze(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        dims = tuple(d + 1 for d in eqn.params["dimensions"])
        return [(outs[0], np.squeeze(t, axis=dims))]

    def _t_rev(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        dims = tuple(d + 1 for d in eqn.params["dimensions"])
        return [(outs[0], np.flip(t, axis=dims))]

    def _t_slice(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(starts)
        sl = (slice(None),) + tuple(
            slice(s, l, st) for s, l, st in zip(starts, limits, strides)
        )
        return [(outs[0], t[sl])]

    def _t_split(self, eqn, pairs):
        # jnp.split traces to one multi-result ``split`` in the installed
        # JAX (it was a run of ``slice``s): each piece keeps its own
        # elements' taint.
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        cuts = np.cumsum(eqn.params["sizes"])[:-1]
        pieces = np.split(t, cuts, axis=eqn.params["axis"] + 1)
        return list(zip(outs, pieces))

    def _t_concatenate(self, eqn, pairs):
        outs = self._concrete(eqn, [p[0] for p in pairs])
        dim = eqn.params["dimension"] + 1
        return [(outs[0], np.concatenate([p[1] for p in pairs], axis=dim))]

    def _t_pad(self, eqn, pairs):
        import jax

        (v, t), (pv, pt) = pairs
        outs = self._concrete(eqn, [v, pv])
        cfg = eqn.params["padding_config"]
        t_rows = [
            np.asarray(jax.lax.pad(
                t[l].astype(np.int8), np.int8(pt[l].any()), cfg
            )) > 0
            for l in range(self.L)
        ]
        return [(outs[0], np.stack(t_rows))]

    def _t_iota(self, eqn, pairs):
        outs = self._concrete(eqn, [])
        return [(outs[0], _tz(self.L, np.shape(outs[0])))]

    # -- data movement with index operands --------------------------------

    # scatter variants join every operand's labels over the whole output —
    # deliberately coarse (no ``unknown`` mark): the rules only scatter
    # predicate-derived masks and carried state, never selection payloads,
    # so precision is irrelevant while soundness is preserved.
    def _t_scatter(self, eqn, pairs):
        return self._coarse(eqn, pairs)

    _t_scatter_add = _t_scatter
    _t_scatter_mul = _t_scatter
    _t_scatter_min = _t_scatter
    _t_scatter_max = _t_scatter

    def _t_gather(self, eqn, pairs):
        (op, t_op), (idx, t_idx) = pairs
        outs = self._concrete(eqn, [op, idx])
        del t_idx  # selection influence: index taint excluded
        try:
            t_rows = [
                np.asarray(
                    eqn.primitive.bind(
                        np.asarray(t_op[l], np.int8), idx, **eqn.params
                    )
                ) > 0
                for l in range(self.L)
            ]
        except Exception:  # noqa: BLE001 — params may be dtype-entangled
            return self._coarse(eqn, pairs)
        return [(outs[0], np.stack(t_rows))]

    def _t_dynamic_slice(self, eqn, pairs):
        op, t_op = pairs[0]
        idx_vals = [p[0] for p in pairs[1:]]
        outs = self._concrete(eqn, [op] + idx_vals)
        t_rows = [
            np.asarray(
                eqn.primitive.bind(
                    np.asarray(t_op[l], np.int8), *idx_vals, **eqn.params
                )
            ) > 0
            for l in range(self.L)
        ]
        return [(outs[0], np.stack(t_rows))]

    def _t_dynamic_update_slice(self, eqn, pairs):
        (op, t_op), (up, t_up) = pairs[0], pairs[1]
        idx_vals = [p[0] for p in pairs[2:]]
        outs = self._concrete(eqn, [op, up] + idx_vals)
        t_rows = [
            np.asarray(
                eqn.primitive.bind(
                    np.asarray(t_op[l], np.int8),
                    np.asarray(t_up[l], np.int8),
                    *idx_vals,
                    **eqn.params,
                )
            ) > 0
            for l in range(self.L)
        ]
        return [(outs[0], np.stack(t_rows))]

    # -- selection / ordering ---------------------------------------------

    def _t_select_n(self, eqn, pairs):
        (pred, _t_pred) = pairs[0]
        cases = pairs[1:]
        outs = self._concrete(eqn, [pred] + [c[0] for c in cases])
        pred_np = np.asarray(pred)
        shape = np.shape(outs[0])
        t = _bt(cases[0][1], self.L, shape).copy()
        for i, (cv, ct) in enumerate(cases):
            if i == 0:
                continue
            sel = np.broadcast_to(pred_np == i, shape)
            t = np.where(sel[None], _bt(ct, self.L, shape), t)
        return [(outs[0], t)]

    def _t_sort(self, eqn, pairs):
        import jax

        dim = eqn.params["dimension"]
        num_keys = eqn.params["num_keys"]
        vals = [p[0] for p in pairs]
        shape = np.shape(vals[0])
        iota = np.broadcast_to(
            np.arange(shape[dim]).reshape(
                (1,) * dim + (shape[dim],) + (1,) * (len(shape) - dim - 1)
            ),
            shape,
        ).astype(np.int32)
        sorted_all = jax.lax.sort_p.bind(
            *vals, iota, dimension=dim, is_stable=True, num_keys=num_keys
        )
        perm = np.asarray(sorted_all[-1])
        outs = [np.take_along_axis(np.asarray(v), perm, axis=dim) for v in vals]
        t_outs = [
            np.take_along_axis(p[1], perm[None], axis=dim + 1) for p in pairs
        ]
        return list(zip(outs, t_outs))

    def _t_top_k(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        idx = np.asarray(outs[1])
        t_vals = np.take_along_axis(t, idx[None], axis=t.ndim - 1)
        return [(outs[0], t_vals), (outs[1], _tz(self.L, idx.shape))]

    def _t_argmax(self, eqn, pairs):
        outs = self._concrete(eqn, [pairs[0][0]])
        return [(outs[0], _tz(self.L, np.shape(outs[0])))]

    _t_argmin = _t_argmax

    # -- elementwise with kill rules --------------------------------------

    def _t_mul(self, eqn, pairs):
        (a, ta), (b, tb) = pairs
        outs = self._concrete(eqn, [a, b])
        shape = np.shape(outs[0])
        a_nz = np.broadcast_to(np.asarray(a) != 0, shape)
        b_nz = np.broadcast_to(np.asarray(b) != 0, shape)
        t = (_bt(ta, self.L, shape) & b_nz[None]) | (
            _bt(tb, self.L, shape) & a_nz[None]
        )
        return [(outs[0], t)]

    def _t_div(self, eqn, pairs):
        (a, ta), (b, tb) = pairs
        outs = self._concrete(eqn, [a, b])
        shape = np.shape(outs[0])
        a_nz = np.broadcast_to(np.asarray(a) != 0, shape)
        t = _bt(ta, self.L, shape) | (_bt(tb, self.L, shape) & a_nz[None])
        return [(outs[0], t)]

    def _winner(self, eqn, pairs, pick_first):
        (a, ta), (b, tb) = pairs
        outs = self._concrete(eqn, [a, b])
        shape = np.shape(outs[0])
        first = np.broadcast_to(pick_first(np.asarray(a), np.asarray(b)), shape)
        t = np.where(
            first[None], _bt(ta, self.L, shape), _bt(tb, self.L, shape)
        )
        return [(outs[0], t)]

    def _t_max(self, eqn, pairs):
        return self._winner(eqn, pairs, lambda a, b: a >= b)

    def _t_min(self, eqn, pairs):
        return self._winner(eqn, pairs, lambda a, b: a <= b)

    # -- reductions --------------------------------------------------------

    def _reduce_or(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        axes = tuple(a + 1 for a in eqn.params["axes"])
        return [(outs[0], t.any(axis=axes))]

    _t_reduce_sum = _reduce_or
    _t_reduce_prod = _reduce_or
    _t_reduce_and = _reduce_or
    _t_reduce_or = _reduce_or
    _t_reduce_xor = _reduce_or

    def _reduce_winner(self, eqn, pairs, argfn):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        axes = tuple(eqn.params["axes"])
        vv = np.asarray(v)
        kept = [d for d in range(vv.ndim) if d not in axes]
        perm = kept + list(axes)
        red = int(np.prod([vv.shape[d] for d in axes])) if axes else 1
        vt = np.transpose(vv, perm).reshape(
            tuple(vv.shape[d] for d in kept) + (red,)
        )
        tt = np.transpose(t, (0,) + tuple(p + 1 for p in perm)).reshape(
            (self.L,) + tuple(vv.shape[d] for d in kept) + (red,)
        )
        w = argfn(vt, axis=-1)
        t_out = np.take_along_axis(tt, w[None, ..., None], axis=-1)[..., 0]
        return [(outs[0], t_out)]

    def _t_reduce_max(self, eqn, pairs):
        return self._reduce_winner(eqn, pairs, np.argmax)

    def _t_reduce_min(self, eqn, pairs):
        return self._reduce_winner(eqn, pairs, np.argmin)

    def _cumulative(self, eqn, pairs):
        (v, t), = pairs
        outs = self._concrete(eqn, [v])
        axis = eqn.params["axis"] + 1
        rev = eqn.params.get("reverse", False)
        tt = np.flip(t, axis=axis) if rev else t
        acc = np.logical_or.accumulate(tt, axis=axis)
        if rev:
            acc = np.flip(acc, axis=axis)
        return [(outs[0], acc)]

    _t_cumsum = _cumulative
    _t_cumprod = _cumulative
    _t_cummax = _cumulative
    _t_cummin = _cumulative
    _t_cumlogsumexp = _cumulative

    # -- linear algebra ----------------------------------------------------

    def _t_dot_general(self, eqn, pairs):
        import jax

        (a, ta), (b, tb) = pairs
        outs = self._concrete(eqn, [a, b])
        dims = eqn.params["dimension_numbers"]
        a_nz = (np.asarray(a) != 0).astype(np.float32)
        b_nz = (np.asarray(b) != 0).astype(np.float32)
        rows = []
        for l in range(self.L):
            from_a = np.asarray(jax.lax.dot_general(
                ta[l].astype(np.float32), b_nz, dims
            )) > 0
            from_b = np.asarray(jax.lax.dot_general(
                a_nz, tb[l].astype(np.float32), dims
            )) > 0
            rows.append(from_a | from_b)
        return [(outs[0], np.stack(rows))]

    # -- identity-ish ------------------------------------------------------

    def _t_optimization_barrier(self, eqn, pairs):
        outs = self._concrete(eqn, [p[0] for p in pairs])
        return [(o, p[1]) for o, p in zip(outs, pairs)]

    def _t_device_put(self, eqn, pairs):
        outs = self._concrete(eqn, [p[0] for p in pairs])
        return [(o, p[1]) for o, p in zip(outs, pairs)]

    # -- control flow ------------------------------------------------------

    def _t_pjit(self, eqn, pairs):
        return self.eval_closed(_closed(eqn.params["jaxpr"]), pairs)

    def _t_custom_jvp_call(self, eqn, pairs):
        return self.eval_closed(_closed(eqn.params["call_jaxpr"]), pairs)

    def _t_custom_vjp_call(self, eqn, pairs):
        sub = _sub_jaxpr(eqn)
        return self.eval_closed(sub, pairs)

    _t_custom_vjp_call_jaxpr = _t_custom_vjp_call
    _t_remat2 = _t_pjit
    _t_checkpoint = _t_pjit
    _t_closed_call = _t_pjit

    def _t_cond(self, eqn, pairs):
        idx = int(np.asarray(pairs[0][0]))
        branch = _closed(eqn.params["branches"][idx])
        return self.eval_closed(branch, pairs[1:])

    def _t_while(self, eqn, pairs):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_j, body_j = _closed(p["cond_jaxpr"]), _closed(p["body_jaxpr"])
        cc, bc, carry = pairs[:cn], pairs[cn:cn + bn], list(pairs[cn + bn:])
        for _ in range(1_000_000):
            pred = self.eval_closed(cond_j, list(cc) + carry)[0][0]
            if not bool(np.asarray(pred)):
                break
            carry = self.eval_closed(body_j, list(bc) + carry)
        else:
            raise RuntimeError("taint eval: while loop iteration cap hit")
        return carry

    def _t_scan(self, eqn, pairs):
        p = eqn.params
        nc, ncarry = p["num_consts"], p["num_carry"]
        length, reverse = p["length"], p["reverse"]
        body = _closed(p["jaxpr"])
        consts = list(pairs[:nc])
        carry = list(pairs[nc:nc + ncarry])
        xs = pairs[nc + ncarry:]
        ys_slots: List[Optional[List[Tuple[Any, np.ndarray]]]] = [
            None
        ] * length
        order = range(length - 1, -1, -1) if reverse else range(length)
        num_ys = len(eqn.outvars) - ncarry
        for i in order:
            sliced = [
                (np.asarray(v)[i], t[:, i]) for v, t in xs
            ]
            outs = self.eval_closed(body, consts + carry + sliced)
            carry = list(outs[:ncarry])
            ys_slots[i] = list(outs[ncarry:])
        ys: List[Tuple[Any, np.ndarray]] = []
        for j in range(num_ys):
            if length == 0:
                outs_shapes = eqn.outvars[ncarry + j].aval
                ys.append((
                    np.zeros(outs_shapes.shape, outs_shapes.dtype),
                    _tz(self.L, outs_shapes.shape),
                ))
                continue
            vals = np.stack(
                [np.asarray(ys_slots[i][j][0]) for i in range(length)]
            )
            ts = np.stack(
                [ys_slots[i][j][1] for i in range(length)], axis=1
            )
            ys.append((vals, ts))
        return carry + ys


# --------------------------------------------------------------------------
# Domain 2: interval / finiteness abstract interpreter
# --------------------------------------------------------------------------

_INF = float("inf")


@dataclasses.dataclass(frozen=True)
class IVal:
    """Whole-array abstract value: [lo, hi] bounds over every element, a
    finiteness-contamination flag (``nf`` — may carry NaN/inf originating
    from the contaminated seeds), elementwise-copy identity (``ids``) and,
    for predicates, the sets of value-ids whose finiteness their truth
    (``tif``) or falsity (``fif``) implies."""

    lo: float
    hi: float
    nf: bool = False
    ids: FrozenSet[int] = frozenset()
    tif: FrozenSet[int] = frozenset()
    fif: FrozenSet[int] = frozenset()

    def widen_to(self, other: "IVal") -> "IVal":
        return IVal(
            min(self.lo, other.lo), max(self.hi, other.hi),
            self.nf or other.nf,
        )

    def same_bounds(self, other: "IVal") -> bool:
        return (
            self.lo == other.lo
            and self.hi == other.hi
            and self.nf == other.nf
        )


def _iv(lo, hi, nf=False, **kw) -> IVal:
    lo = float(lo) if not math.isnan(float(lo)) else -_INF
    hi = float(hi) if not math.isnan(float(hi)) else _INF
    return IVal(lo, hi, nf, **kw)


TOP_F = _iv(-_INF, _INF)
BOOL_IV = _iv(0.0, 1.0)


def _contains_zero(v: IVal) -> bool:
    return v.lo <= 0.0 <= v.hi


def _mul_bounds(a: IVal, b: IVal) -> Tuple[float, float]:
    with np.errstate(invalid="ignore"):
        cands = np.array(
            [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi], np.float64
        )
    cands = np.where(np.isnan(cands), 0.0, cands)  # 0 * inf -> 0 (reals)
    return float(cands.min()), float(cands.max())


class IntervalEval:
    """Abstract interpreter over whole-array intervals + contamination."""

    WIDEN_AFTER = 4
    MAX_FIX = 24

    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        self.unknown: Set[str] = set()
        self.record_denominators = True

    def _event(self, kind: str, eqn, detail: str):
        src = eqn_source(eqn)
        self.events.append({
            "kind": kind,
            "prim": eqn.primitive.name,
            "path": src[0] if src else None,
            "line": src[1] if src else None,
            "detail": detail,
        })

    # -- entry ------------------------------------------------------------

    def eval_closed(self, closed, ivals: Sequence[IVal]) -> List[IVal]:
        jaxpr = closed.jaxpr
        env: Dict[Any, IVal] = {}

        def write(var, v: IVal):
            env[var] = dataclasses.replace(v, ids=v.ids | {id(var)})

        def read(atom) -> IVal:
            from jax.extend import core as jex_core

            if isinstance(atom, jex_core.Literal):
                a = np.asarray(atom.val)
                if a.size == 0:
                    return _iv(0.0, 0.0)
                if a.dtype == bool:
                    return _iv(float(a.min()), float(a.max()))
                lo = float(np.min(a.astype(np.float64)))
                hi = float(np.max(a.astype(np.float64)))
                # Deliberate literal inf (sort padding) is CLEAN: nf tracks
                # contamination from the seeded inputs only.
                return _iv(lo, hi)
            return env[atom]

        for var, const in zip(jaxpr.constvars, closed.consts):
            write(var, read_const(const))
        for var, v in zip(jaxpr.invars, ivals):
            write(var, v)
        for eqn in jaxpr.eqns:
            ins = [read(a) for a in eqn.invars]
            outs = self._eqn(eqn, ins)
            for var, v in zip(eqn.outvars, outs):
                write(var, v)
        return [read(a) for a in jaxpr.outvars]

    # -- dispatch ---------------------------------------------------------

    def _eqn(self, eqn, ins: Sequence[IVal]) -> List[IVal]:
        name = eqn.primitive.name.replace("-", "_")
        handler = getattr(self, f"_i_{name}", None)
        if handler is not None:
            return handler(eqn, ins)
        if name in _IV_TABLE:
            return [_IV_TABLE[name](self, eqn, ins)]
        sub = _sub_jaxpr(eqn)
        if sub is not None:
            return self.eval_closed(sub, ins)
        # Unknown primitive: sound defaults by output dtype.  Float outputs
        # become contaminated TOP (the safe direction for MUR803); the prim
        # name is surfaced so coverage gaps are debuggable, not silent.
        self.unknown.add(name)
        outs = []
        for var in eqn.outvars:
            dt = getattr(var.aval, "dtype", None)
            if dt is not None and np.issubdtype(dt, np.floating):
                outs.append(_iv(-_INF, _INF, nf=True))
            else:
                outs.append(TOP_F)
        return outs

    # -- explicit handlers -------------------------------------------------

    def _join(self, ins: Sequence[IVal]) -> IVal:
        lo = min((v.lo for v in ins), default=0.0)
        hi = max((v.hi for v in ins), default=0.0)
        return _iv(lo, hi, any(v.nf for v in ins))

    @staticmethod
    def _same_operand(eqn) -> bool:
        """Both invars are literally the same jaxpr Var — the only safe
        notion of elementwise self-application.  (The ``ids`` copy-chains
        survive value-CHANGING ops like reduce_max/floor, so using them
        here would constant-fold ``x == max(x)``-style data-dependent
        masks — verified unsound.)"""
        from jax.extend import core as jex_core

        return (
            len(eqn.invars) == 2
            and not isinstance(eqn.invars[0], jex_core.Literal)
            and eqn.invars[0] is eqn.invars[1]
        )

    def _i_mul(self, eqn, ins):
        a, b = ins
        lo, hi = _mul_bounds(a, b)
        if self._same_operand(eqn):
            # x * x (the jnp.square/variance idiom): the product of a value
            # with itself is nonnegative — the refinement that proves
            # layernorm's sqrt(var + eps) denominator positive.
            lo = max(lo, 0.0)
        if (a.nf and _contains_zero(b)) or (b.nf and _contains_zero(a)):
            self._event(
                "mask-mul", eqn,
                "possibly-non-finite operand multiplied by a value that "
                "can be exactly 0 (0*inf == nan) — masks over possibly "
                "non-finite data must be where-style replacements",
            )
        return [_iv(lo, hi, a.nf or b.nf)]

    def _i_ne(self, eqn, ins):
        a, b = ins
        if self._same_operand(eqn) and not (a.nf or b.nf):
            # x != x is isnan(x); a value that cannot be NaN (real-valued
            # semantics, uncontaminated) makes it constantly False — which
            # is what keeps logaddexp/softplus's NaN-repair branch from
            # joining an unbounded interval into every softplus output.
            return [_iv(0.0, 0.0)]
        return [BOOL_IV]

    def _i_eq(self, eqn, ins):
        a, b = ins
        if self._same_operand(eqn) and not (a.nf or b.nf):
            return [_iv(1.0, 1.0)]
        return [BOOL_IV]

    # Order comparisons resolve to constants when the intervals are
    # disjoint (and the operands provably non-NaN) — which is what lets
    # jnp.var's ``where(count > 0, var, nan)`` repair branch drop its NaN
    # literal instead of joining it into every layernorm denominator.
    def _cmp(self, ins, true_when, false_when):
        a, b = ins
        if not (a.nf or b.nf):
            if true_when(a, b):
                return [_iv(1.0, 1.0)]
            if false_when(a, b):
                return [_iv(0.0, 0.0)]
        return [BOOL_IV]

    def _i_gt(self, eqn, ins):
        return self._cmp(
            ins, lambda a, b: a.lo > b.hi, lambda a, b: a.hi <= b.lo
        )

    def _i_ge(self, eqn, ins):
        return self._cmp(
            ins, lambda a, b: a.lo >= b.hi, lambda a, b: a.hi < b.lo
        )

    def _i_lt(self, eqn, ins):
        return self._cmp(
            ins, lambda a, b: a.hi < b.lo, lambda a, b: a.lo >= b.hi
        )

    def _i_le(self, eqn, ins):
        return self._cmp(
            ins, lambda a, b: a.hi <= b.lo, lambda a, b: a.lo > b.hi
        )

    def _i_dot_general(self, eqn, ins):
        a, b = ins
        lo, hi = _mul_bounds(a, b)
        dims = eqn.params["dimension_numbers"]
        lhs_contract = dims[0][0]
        shape = eqn.invars[0].aval.shape
        c = 1
        for d in lhs_contract:
            c *= int(shape[d])
        c = max(c, 1)
        if (a.nf and _contains_zero(b)) or (b.nf and _contains_zero(a)):
            self._event(
                "mask-mul", eqn,
                "possibly-non-finite matmul operand against a value that "
                "can be exactly 0",
            )
        return [_iv(c * lo if lo != 0 else 0.0, c * hi if hi != 0 else 0.0,
                    a.nf or b.nf)]

    def _i_div(self, eqn, ins):
        a, b = ins
        nf = a.nf or b.nf
        if _contains_zero(b):
            if self.record_denominators:
                self._event(
                    "zero-denominator", eqn,
                    f"denominator interval [{b.lo:g}, {b.hi:g}] contains 0 "
                    "— guard with jnp.maximum(x, eps) or a where()",
                )
            return [_iv(-_INF, _INF, True)]
        with np.errstate(invalid="ignore", divide="ignore"):
            cands = np.array(
                [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi],
                np.float64,
            )
        cands = np.where(np.isnan(cands), 0.0, cands)
        return [_iv(float(cands.min()), float(cands.max()), nf)]

    def _i_rsqrt(self, eqn, ins):
        (x,) = ins
        nf = x.nf
        if x.lo <= 0.0 <= x.hi or (x.lo < 0):
            if self.record_denominators and x.hi >= 0.0 >= x.lo:
                self._event(
                    "zero-denominator", eqn,
                    f"rsqrt operand interval [{x.lo:g}, {x.hi:g}] reaches 0 "
                    "— 1/sqrt(0) is inf; floor the operand first",
                )
            nf = True
        return [_iv(0.0, _INF, nf)]

    def _i_integer_pow(self, eqn, ins):
        (x,) = ins
        y = eqn.params["y"]
        if y < 0 and _contains_zero(x):
            if self.record_denominators:
                self._event(
                    "zero-denominator", eqn,
                    f"x**{y} with base interval [{x.lo:g}, {x.hi:g}] "
                    "containing 0",
                )
            return [_iv(-_INF, _INF, True)]
        if y >= 0 and y % 2 == 0:
            m = max(abs(x.lo), abs(x.hi))
            return [_iv(0.0, m ** y if math.isfinite(m) else _INF, x.nf)]
        try:
            lo, hi = x.lo ** y, x.hi ** y
        except (OverflowError, ZeroDivisionError):
            lo, hi = -_INF, _INF
        return [_iv(min(lo, hi), max(lo, hi), x.nf)]

    def _i_is_finite(self, eqn, ins):
        (x,) = ins
        return [dataclasses.replace(BOOL_IV, tif=x.ids)]

    def _is_bool_op(self, eqn) -> bool:
        # and/or/not/xor on integers are bitwise VALUE ops, not predicate
        # algebra — no [0, 1] bounds, no finiteness implications.
        return getattr(eqn.invars[0].aval, "dtype", None) == np.bool_

    def _i_and(self, eqn, ins):
        if not self._is_bool_op(eqn):
            return [TOP_F]
        a, b = ins
        return [dataclasses.replace(BOOL_IV, tif=a.tif | b.tif)]

    def _i_or(self, eqn, ins):
        if not self._is_bool_op(eqn):
            return [TOP_F]
        a, b = ins
        return [dataclasses.replace(BOOL_IV, fif=a.fif | b.fif)]

    def _i_not(self, eqn, ins):
        if not self._is_bool_op(eqn):
            return [TOP_F]
        (a,) = ins
        return [dataclasses.replace(BOOL_IV, tif=a.fif, fif=a.tif)]

    def _i_xor(self, eqn, ins):
        return [BOOL_IV if self._is_bool_op(eqn) else TOP_F]

    def _i_reduce_and(self, eqn, ins):
        (a,) = ins
        return [dataclasses.replace(BOOL_IV, tif=a.tif)]

    def _i_reduce_or(self, eqn, ins):
        (a,) = ins
        return [dataclasses.replace(BOOL_IV, fif=a.fif)]

    def _i_reduce_min(self, eqn, ins):
        # all(x) over bools lowers to reduce_min on some paths: min true
        # => ALL true, so tif survives; min false only means SOME element
        # is false, so fif must NOT (the reduce_and asymmetry, mirrored).
        (a,) = ins
        return [dataclasses.replace(a, ids=frozenset(), fif=frozenset())]

    def _i_select_n(self, eqn, ins):
        pred, cases = ins[0], list(ins[1:])
        if pred.hi <= 0.0 and not pred.nf:
            return [cases[0]]  # predicate constantly false
        if pred.lo >= len(cases) - 1 and not pred.nf:
            return [cases[-1]]  # predicate constantly picks the last case
        lo = min(c.lo for c in cases)
        hi = max(c.hi for c in cases)
        nf = False
        for i, c in enumerate(cases):
            c_nf = c.nf
            if c_nf and i == len(cases) - 1 and pred.tif & c.ids:
                c_nf = False  # chosen when pred true => proven finite
            if c_nf and i == 0 and pred.fif & c.ids:
                c_nf = False  # chosen when pred false => proven finite
            nf = nf or c_nf
        return [_iv(lo, hi, nf)]

    def _i_select(self, eqn, ins):  # legacy select
        return self._i_select_n(eqn, ins)

    def _i_reduce_sum(self, eqn, ins):
        (a,) = ins
        shape = eqn.invars[0].aval.shape
        n = 1
        for d in eqn.params["axes"]:
            n *= int(shape[d])
        n = max(n, 1)
        return [_iv(
            a.lo * n if a.lo < 0 else a.lo,
            a.hi * n if a.hi > 0 else a.hi,
            a.nf,
        )]

    def _i_convert_element_type(self, eqn, ins):
        (a,) = ins
        dt = eqn.params["new_dtype"]
        if np.issubdtype(dt, np.bool_):
            return [BOOL_IV]
        # keep ids: elementwise value-preserving (up to rounding) — the
        # isfinite-pattern matching tolerates it (finite stays finite).
        return [dataclasses.replace(a, tif=frozenset(), fif=frozenset())]

    def _i_bitcast_convert_type(self, eqn, ins):
        dt = eqn.params["new_dtype"]
        if np.issubdtype(dt, np.floating):
            # Arbitrary bit patterns include NaN/inf encodings: RNG-derived
            # floats count as contaminated until a guard proves otherwise.
            return [_iv(-_INF, _INF, True)]
        return [TOP_F]

    def _i_iota(self, eqn, ins):
        shape = eqn.params["shape"]
        dim = eqn.params["dimension"]
        n = int(shape[dim]) if shape else 1
        return [_iv(0.0, max(0, n - 1))]

    def _i_clamp(self, eqn, ins):
        # Both bounds must land inside [mn.lo, mx.hi] or the interval
        # inverts when x lies entirely outside the clamp window (e.g.
        # clip(d, 0, cap) with d in [5, 6] and cap == 0 is exactly 0) —
        # and an inverted interval vacuously "excludes" zero.
        mn, x, mx = ins
        lo = min(max(x.lo, mn.lo), mx.hi)
        hi = max(min(x.hi, mx.hi), mn.lo)
        return [_iv(lo, hi, x.nf or mn.nf or mx.nf)]

    def _i_pad(self, eqn, ins):
        return [self._join(ins)]

    def _i_concatenate(self, eqn, ins):
        return [self._join(ins)]

    def _i_split(self, eqn, ins):
        # Every piece is a shape-only view of the operand (_iv_view).
        return [ins[0]] * len(eqn.outvars)

    def _i_dynamic_update_slice(self, eqn, ins):
        return [self._join(ins[:2])]

    def _i_gather(self, eqn, ins):
        op = ins[0]
        return [dataclasses.replace(op, ids=frozenset(),
                                    tif=frozenset(), fif=frozenset())]

    def _i_dynamic_slice(self, eqn, ins):
        return self._i_gather(eqn, ins)

    def _i_sort(self, eqn, ins):
        return [dataclasses.replace(v, ids=frozenset(), tif=frozenset(),
                                    fif=frozenset()) for v in ins]

    def _i_top_k(self, eqn, ins):
        (x,) = ins
        k_extent = 0
        shape = eqn.invars[0].aval.shape
        if shape:
            k_extent = max(0, int(shape[-1]) - 1)
        return [dataclasses.replace(x, ids=frozenset()), _iv(0.0, k_extent)]

    def _i_optimization_barrier(self, eqn, ins):
        return list(ins)

    def _i_stop_gradient(self, eqn, ins):
        return [ins[0]]

    # -- control flow ------------------------------------------------------

    def _i_pjit(self, eqn, ins):
        return self.eval_closed(_closed(eqn.params["jaxpr"]), ins)

    def _i_custom_jvp_call(self, eqn, ins):
        return self.eval_closed(_closed(eqn.params["call_jaxpr"]), ins)

    def _i_custom_vjp_call(self, eqn, ins):
        return self.eval_closed(_sub_jaxpr(eqn), ins)

    _i_custom_vjp_call_jaxpr = _i_custom_vjp_call
    _i_remat2 = _i_pjit
    _i_checkpoint = _i_pjit
    _i_closed_call = _i_pjit

    def _i_cond(self, eqn, ins):
        branches = [
            self.eval_closed(_closed(b), list(ins[1:]))
            for b in eqn.params["branches"]
        ]
        out = []
        for outs in zip(*branches):
            v = outs[0]
            for o in outs[1:]:
                v = v.widen_to(o)
            out.append(v)
        return out

    def _fixpoint(self, body, consts, carry, xs):
        carry = [dataclasses.replace(c, ids=frozenset(), tif=frozenset(),
                                     fif=frozenset()) for c in carry]
        outs = None
        for it in range(self.MAX_FIX):
            outs = self.eval_closed(body, consts + carry + xs)
            new_carry = [
                c.widen_to(o) for c, o in zip(carry, outs[:len(carry)])
            ]
            if all(c.same_bounds(n) for c, n in zip(carry, new_carry)):
                return new_carry, outs
            if it >= self.WIDEN_AFTER:
                new_carry = [
                    n if c.same_bounds(n)
                    else _iv(-_INF, _INF, c.nf or n.nf)
                    for c, n in zip(carry, new_carry)
                ]
            carry = new_carry
        return carry, outs

    def _i_scan(self, eqn, ins):
        p = eqn.params
        nc, ncarry = p["num_consts"], p["num_carry"]
        body = _closed(p["jaxpr"])
        consts = list(ins[:nc])
        carry = list(ins[nc:nc + ncarry])
        xs = list(ins[nc + ncarry:])
        if p["length"] == 0:
            num_ys = len(eqn.outvars) - ncarry
            return carry + [_iv(0.0, 0.0)] * num_ys
        carry, outs = self._fixpoint(body, consts, carry, xs)
        ys = [
            dataclasses.replace(y, ids=frozenset(), tif=frozenset(),
                                fif=frozenset())
            for y in outs[ncarry:]
        ]
        return carry + ys

    def _i_while(self, eqn, ins):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body = _closed(p["body_jaxpr"])
        bc = list(ins[cn:cn + bn])
        carry = list(ins[cn + bn:])
        fixed, _ = self._fixpoint(body, bc, carry, [])
        # The loop may execute zero times: join with the initial carry.
        return [c.widen_to(f) for c, f in zip(carry, fixed)]


def read_const(const) -> IVal:
    a = np.asarray(const)
    if a.size == 0:
        return _iv(0.0, 0.0)
    if a.dtype == bool:
        return _iv(float(a.min()), float(a.max()))
    if not np.issubdtype(a.dtype, np.number):
        return TOP_F
    af = a.astype(np.float64)
    finite = af[np.isfinite(af)]
    lo = float(finite.min()) if finite.size else 0.0
    hi = float(finite.max()) if finite.size else 0.0
    if not np.isfinite(af).all():
        # Literal inf padding is deliberate and CLEAN (nf stays False);
        # bounds still record the infinities.
        lo = -_INF if (af == -_INF).any() else lo
        hi = _INF if (af == _INF).any() else hi
    return _iv(lo, hi)


def _mk_unary(fn) -> Callable:
    def h(self, eqn, ins):
        return fn(self, ins[0])

    return h


def _monotone(f, lo_clip=None, hi_clip=None):
    def t(self, x: IVal) -> IVal:
        try:
            lo = f(x.lo)
        except (ValueError, OverflowError):
            lo = -_INF
        try:
            hi = f(x.hi)
        except (ValueError, OverflowError):
            hi = _INF
        if lo_clip is not None:
            lo, hi = max(lo, lo_clip), max(hi, lo_clip)
        if hi_clip is not None:
            lo, hi = min(lo, hi_clip), min(hi, hi_clip)
        return _iv(lo, hi, x.nf)

    return t


def _iv_add(self, eqn, ins):
    a, b = ins
    return _iv(a.lo + b.lo if not math.isnan(a.lo + b.lo) else -_INF,
               a.hi + b.hi if not math.isnan(a.hi + b.hi) else _INF,
               a.nf or b.nf)


def _iv_sub(self, eqn, ins):
    a, b = ins
    lo = a.lo - b.hi
    hi = a.hi - b.lo
    return _iv(lo if not math.isnan(lo) else -_INF,
               hi if not math.isnan(hi) else _INF, a.nf or b.nf)


def _iv_max(self, eqn, ins):
    a, b = ins
    return _iv(max(a.lo, b.lo), max(a.hi, b.hi), a.nf or b.nf)


def _iv_min(self, eqn, ins):
    a, b = ins
    return _iv(min(a.lo, b.lo), min(a.hi, b.hi), a.nf or b.nf)


def _iv_abs(self, eqn, ins):
    (x,) = ins
    if x.lo >= 0:
        return _iv(x.lo, x.hi, x.nf)
    if x.hi <= 0:
        return _iv(-x.hi, -x.lo, x.nf)
    return _iv(0.0, max(-x.lo, x.hi), x.nf)


def _iv_neg(self, eqn, ins):
    (x,) = ins
    return _iv(-x.hi, -x.lo, x.nf)


def _iv_square(self, eqn, ins):
    v = _iv_abs(self, eqn, ins)
    lo, hi = _mul_bounds(v, v)
    return _iv(max(lo, 0.0), hi, ins[0].nf)


def _iv_sqrt(self, eqn, ins):
    (x,) = ins
    nf = x.nf or x.lo < 0
    return _iv(math.sqrt(max(x.lo, 0.0)) if math.isfinite(x.lo) else 0.0,
               math.sqrt(x.hi) if math.isfinite(x.hi) and x.hi >= 0 else _INF,
               nf)


def _iv_log(self, eqn, ins):
    (x,) = ins
    nf = x.nf or x.lo <= 0
    hi = math.log(x.hi) if math.isfinite(x.hi) and x.hi > 0 else _INF
    lo = math.log(x.lo) if x.lo > 0 and math.isfinite(x.lo) else -_INF
    return _iv(lo, hi, nf)


def _iv_log2(self, eqn, ins):
    (x,) = ins
    nf = x.nf or x.lo <= 0
    hi = math.log2(x.hi) if math.isfinite(x.hi) and x.hi > 0 else _INF
    lo = math.log2(x.lo) if x.lo > 0 and math.isfinite(x.lo) else -_INF
    return _iv(lo, hi, nf)


def _iv_log1p(self, eqn, ins):
    (x,) = ins
    nf = x.nf or x.lo <= -1.0
    return _iv(
        math.log1p(x.lo) if x.lo > -1.0 and math.isfinite(x.lo) else -_INF,
        math.log1p(x.hi) if math.isfinite(x.hi) else _INF,
        nf,
    )


def _iv_poles_nonpos(self, eqn, ins):
    (x,) = ins
    return _iv(-_INF, _INF, x.nf or x.lo <= 0)


def _iv_domain_pm1(self, eqn, ins):
    (x,) = ins
    return _iv(-_INF, _INF, x.nf or x.lo <= -1.0 or x.hi >= 1.0)


def _iv_bool_out(self, eqn, ins):
    return BOOL_IV


def _iv_passthrough(self, eqn, ins):
    x = ins[0]
    return dataclasses.replace(x, tif=frozenset(), fif=frozenset())


def _iv_view(self, eqn, ins):
    """Shape-only view of one operand: bounds, contamination, identity AND
    predicate implications all survive — the sentinel pattern broadcasts
    its row predicate (``ok[:, None]``) before the select, and reshapes/
    slices keep elementwise correspondence for the reduce_and-based
    implications (pred true => the whole reduced group is finite, which
    implies any subset)."""
    return ins[0]


def _iv_join_all(self, eqn, ins):
    return self._join(ins)


def _iv_int_top(self, eqn, ins):
    return TOP_F


def _iv_rem(self, eqn, ins):
    a, b = ins
    if _contains_zero(b):
        if self.record_denominators:
            self._event(
                "zero-denominator", eqn,
                f"rem divisor interval [{b.lo:g}, {b.hi:g}] contains 0",
            )
        return _iv(-_INF, _INF, True)
    m = max(abs(b.lo), abs(b.hi))
    return _iv(-m, m, a.nf or b.nf)


def _iv_cumulative(self, eqn, ins):
    (a,) = ins
    shape = eqn.invars[0].aval.shape
    axis = eqn.params.get("axis", 0)
    n = int(shape[axis]) if shape else 1
    n = max(n, 1)
    return _iv(a.lo * n if a.lo < 0 else a.lo,
               a.hi * n if a.hi > 0 else a.hi, a.nf)


_IV_TABLE: Dict[str, Callable] = {
    "add": _iv_add,
    "add_any": _iv_add,  # the AD transpose's accumulating add
    "sub": _iv_sub,
    "max": _iv_max,
    "min": _iv_min,
    "abs": _iv_abs,
    "neg": _iv_neg,
    "sign": _mk_unary(lambda self, x: _iv(-1.0, 1.0, x.nf)),
    "square": _iv_square,
    "sqrt": _iv_sqrt,
    "cbrt": _mk_unary(lambda self, x: _iv(-_INF, _INF, x.nf)),
    "exp": _mk_unary(_monotone(math.exp, lo_clip=0.0)),
    "exp2": _mk_unary(_monotone(lambda v: 2.0 ** v, lo_clip=0.0)),
    "expm1": _mk_unary(_monotone(math.expm1, lo_clip=-1.0)),
    "log": _iv_log,
    "log1p": _iv_log1p,
    "log2": _iv_log2,
    "lgamma": _iv_poles_nonpos,
    "digamma": _iv_poles_nonpos,
    "logistic": _mk_unary(lambda self, x: _iv(0.0, 1.0, x.nf)),
    "tanh": _mk_unary(lambda self, x: _iv(-1.0, 1.0, x.nf)),
    "erf": _mk_unary(lambda self, x: _iv(-1.0, 1.0, x.nf)),
    "erfc": _mk_unary(lambda self, x: _iv(0.0, 2.0, x.nf)),
    "erf_inv": _iv_domain_pm1,
    "atanh": _iv_domain_pm1,
    "sin": _mk_unary(lambda self, x: _iv(-1.0, 1.0, x.nf)),
    "cos": _mk_unary(lambda self, x: _iv(-1.0, 1.0, x.nf)),
    "tan": _mk_unary(lambda self, x: _iv(-_INF, _INF, x.nf)),
    "asin": _iv_domain_pm1,
    "acos": _iv_domain_pm1,
    "atan": _mk_unary(lambda self, x: _iv(-2.0, 2.0, x.nf)),
    "atan2": _iv_join_all,
    "sinh": _mk_unary(lambda self, x: _iv(-_INF, _INF, x.nf)),
    "cosh": _mk_unary(lambda self, x: _iv(1.0, _INF, x.nf)),
    "asinh": _mk_unary(lambda self, x: _iv(-_INF, _INF, x.nf)),
    "acosh": _mk_unary(lambda self, x: _iv(0.0, _INF, x.nf or x.lo < 1.0)),
    # floor/ceil/round are monotone but move values off the input bounds
    # (floor(0.6) == 0 < 0.6): transfer through the function itself so
    # 1/floor(x) with x in [0.5, 2] correctly flags a zero denominator.
    "floor": _mk_unary(_monotone(math.floor)),
    "ceil": _mk_unary(_monotone(math.ceil)),
    "round": _mk_unary(_monotone(lambda v: float(round(v)))),
    "nextafter": _iv_join_all,
    "rem": _iv_rem,
    "pow": _iv_join_all,
    "eq": _iv_bool_out,
    "ne": _iv_bool_out,
    "lt": _iv_bool_out,
    "le": _iv_bool_out,
    "gt": _iv_bool_out,
    "ge": _iv_bool_out,
    "reduce_max": _iv_passthrough,
    "reduce_prod": _mk_unary(
        lambda self, x: _iv(0.0 if x.lo >= 0 else -_INF, _INF, x.nf)
    ),
    "reduce_xor": _iv_bool_out,
    "broadcast_in_dim": _iv_view,
    "reshape": _iv_view,
    "transpose": _iv_view,
    "squeeze": _iv_view,
    "expand_dims": _iv_view,
    "rev": _iv_view,
    "slice": _iv_view,
    "copy": _iv_view,
    "real": _iv_passthrough,
    "imag": _iv_passthrough,
    "reduce_precision": _iv_view,
    "scatter": _iv_join_all,
    "scatter-add": _iv_join_all,
    "scatter_add": _iv_join_all,
    "scatter_max": _iv_join_all,
    "scatter_min": _iv_join_all,
    "scatter_mul": _iv_join_all,
    "argmax": _iv_int_top,
    "argmin": _iv_int_top,
    "cumsum": _iv_cumulative,
    "cumlogsumexp": _iv_cumulative,
    "cumprod": _mk_unary(lambda self, x: _iv(-_INF, _INF, x.nf)),
    "cummax": _iv_passthrough,
    "cummin": _iv_passthrough,
    "threefry2x32": _iv_int_top,
    "random_seed": _iv_int_top,
    "random_wrap": _iv_int_top,
    "random_unwrap": _iv_int_top,
    "random_fold_in": _iv_int_top,
    "random_bits": _iv_int_top,
    "random_split": _iv_int_top,
    "random_clone": _iv_int_top,
    "random_gamma": _mk_unary(lambda self, x: _iv(0.0, _INF, True)),
    "shift_left": _iv_int_top,
    "shift_right_logical": _iv_int_top,
    "shift_right_arithmetic": _iv_int_top,
    "population_count": _iv_int_top,
    "clz": _iv_int_top,
    "device_put": _iv_passthrough,
}


# --------------------------------------------------------------------------
# Canonical flow cells
# --------------------------------------------------------------------------


_FLOW_PROBE_MEMO: Dict[bool, Tuple[Any, Any, int]] = {}


def _flow_probe_model(evidential: bool):
    """(apply_fn, unravel, dim) of the flow pass's probe model.  Unlike the
    IR pass's single plain-MLP probe, evidential rules get the evidential
    head here: the interval domain then SEES the softplus+1 alpha floor
    (alphas >= 1 => Dirichlet strength >= K), which is what proves the
    vacuity/entropy divisions in evidential_trust_metric zero-free — the
    paper-faithful configuration of that rule."""
    if evidential in _FLOW_PROBE_MEMO:
        return _FLOW_PROBE_MEMO[evidential]
    import jax
    from jax.flatten_util import ravel_pytree

    from murmura_tpu.models import make_mlp

    model = make_mlp(
        input_dim=_PROBE_IN, hidden_dims=(16,), num_classes=_PROBE_CLASSES,
        evidential=evidential,
    )
    flat0, unravel = ravel_pytree(model.init(jax.random.PRNGKey(0)))
    _FLOW_PROBE_MEMO[evidential] = (model.apply, unravel, int(flat0.size))
    return _FLOW_PROBE_MEMO[evidential]


_PROBE_RULES = frozenset({"ubar", "evidential_trust"})
_EVIDENTIAL_RULES = frozenset({"evidential_trust"})


@dataclasses.dataclass
class FlowCell:
    """One (rule, exchange mode) cell of the flow grid: a traceable
    ``fn(*args)`` plus which argument positions carry the per-neighbor
    exchange payload (taint-seeded along their leading node axis)."""

    name: str
    mode: str  # dense | circulant | sparse | compressed
    n: int
    fn: Callable
    args: Tuple
    bcast_args: Tuple[int, ...]  # arg indices seeded with row labels
    agg: Any
    _closed: Any = None

    def traced(self):
        """Memoized ClosedJaxpr of the cell — both flow domains (taint
        influence and interval denominators) analyze the same trace, so
        one sweep pays the jax.make_jaxpr cost."""
        if self._closed is None:
            import jax

            with _quiet_tracing():
                self._closed = jax.make_jaxpr(self.fn)(*self.args)
        return self._closed


# Default cells memoized per (rule, mode): check_influence and
# check_denominators sweep the same grid in one check_flow run — building
# each aggregator and probe model once is the difference between one trace
# per cell and two.
_CELL_MEMO: Dict[Tuple[str, str], "FlowCell"] = {}


def _flow_offsets(n: int) -> List[int]:
    from murmura_tpu.analysis.ir import canonical_offsets

    return canonical_offsets(n)


def build_flow_cell(
    name: str,
    mode: str,
    n: int = FLOW_N,
    agg_override: Any = None,
    params: Optional[Dict[str, Any]] = None,
    audit: bool = False,
) -> FlowCell:
    """Instantiate one rule over one flow-grid cell.

    Every mode is built over the SAME canonical k-regular(4) circulant
    graph (the dense mode takes its [N, N] matrix, the circulant/sparse/
    compressed modes its offsets), so the analyzed influence cardinality
    is comparable across modes — the MUR802 parity subject.

    ``audit`` builds the cell with ``ctx.audit`` on so the rule emits its
    per-node ``tap_*`` stats — the MUR1003 adaptive-feedback cells
    (analysis/adaptive.py) analyze the acceptance signal those taps feed.
    """
    import dataclasses as dc

    import jax.numpy as jnp

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.aggregation.base import AggContext
    from murmura_tpu.analysis.ir import AGG_CASES, _canonical_adj
    from murmura_tpu.ops.compress import Int8Blocks, quantize_int8

    if mode not in FLOW_MODES:
        raise ValueError(f"unknown flow mode {mode!r}")
    default_cell = (
        agg_override is None and params is None and n == FLOW_N
        and not audit
    )
    if default_cell and (name, mode) in _CELL_MEMO:
        return _CELL_MEMO[(name, mode)]
    offsets = _flow_offsets(n)
    k = len(offsets)
    evidential = name in _EVIDENTIAL_RULES
    if name in _PROBE_RULES:
        apply_fn, unravel, dim = _flow_probe_model(evidential)
    else:
        apply_fn = unravel = None
        dim = FLOW_DIM

    case = dict(AGG_CASES.get(name, {}) if params is None else params)
    if mode != "dense":
        case["exchange_offsets"] = list(offsets)
    if mode == "sparse":
        case["sparse_exchange"] = True
    if agg_override is not None:
        agg = agg_override
    else:
        agg = build_aggregator(name, case, model_dim=dim, total_rounds=10)

    rng = np.random.default_rng(0)
    own = jnp.asarray(rng.normal(size=(n, dim)) * 0.1, jnp.float32)
    bcast_f = jnp.asarray(rng.normal(size=(n, dim)) * 0.1, jnp.float32)
    if mode == "sparse":
        adj = jnp.ones((k, n), jnp.float32)
    else:
        # Dense mode takes the SAME circulant graph's [N, N] matrix.
        adj = jnp.asarray(_canonical_adj(n, circulant=True))
    ridx = jnp.asarray(0.0, jnp.float32)
    state = {k2: jnp.asarray(v) for k2, v in agg.init_state(n).items()}

    ctx = AggContext(
        apply_fn=apply_fn,
        unravel=unravel,
        evidential=evidential,
        num_classes=_PROBE_CLASSES,
        total_rounds=10,
        audit=audit,
    )
    if name in _PROBE_RULES:
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
        ctx = dc.replace(
            ctx, probe_x=probe["x"], probe_y=probe["y"],
            probe_mask=probe["mask"],
        )

    if mode == "compressed":
        if not agg.quantized_exchange:
            raise ValueError(
                f"rule '{name}' has no quantized exchange path — the "
                "compressed flow mode applies to quantized_exchange rules"
            )
        qb = quantize_int8(bcast_f, FLOW_BLOCK)

        def fn(own, q, scale, adj, ridx, state):  # murmura: traced
            payload = Int8Blocks(q, scale, FLOW_BLOCK, dim, jnp.float32)
            return agg.aggregate(own, payload, adj, ridx, state, ctx)

        args = (own, qb.q, qb.scale, adj, ridx, state)
        bcast_args = (1, 2)
    else:

        def fn(own, bcast, adj, ridx, state):  # murmura: traced
            return agg.aggregate(own, bcast, adj, ridx, state, ctx)

        args = (own, bcast_f, adj, ridx, state)
        bcast_args = (1,)

    cell = FlowCell(
        name=name, mode=mode, n=n, fn=fn, args=args, bcast_args=bcast_args,
        agg=agg,
    )
    if default_cell:
        _CELL_MEMO[(name, mode)] = cell
    return cell


def rule_flow_modes(name: str, agg=None) -> Tuple[str, ...]:
    """Exchange modes the flow grid sweeps for one rule.  ``compressed``
    only where the circulant kernels take the int8 payload itself —
    other rules consume the receiver-side dequantized tensor, which is
    taint-identical to their dense/circulant float path."""
    if agg is None:
        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.analysis.ir import AGG_CASES

        case = dict(AGG_CASES.get(name, {}))
        case["exchange_offsets"] = _flow_offsets(FLOW_N)
        agg = build_aggregator(name, case, model_dim=FLOW_DIM, total_rounds=10)
    modes = ["dense", "circulant", "sparse"]
    if agg.quantized_exchange:
        modes.append("compressed")
    return tuple(modes)


# --------------------------------------------------------------------------
# Influence analysis (Domain 1 drivers)
# --------------------------------------------------------------------------


def analyze_cell_influence(cell: FlowCell) -> Dict[str, Any]:
    """Run the taint interpreter over one cell and summarize the output
    [N, P] tensor's per-neighbor influence.

    Returns ``{"per_node": tuple[int], "max": int, "sets": [[labels]],
    "unknown_prims": [...]}`` where ``per_node[i]`` is the maximum number
    of distinct NON-SELF labels any single coordinate of output row i
    carries, and ``sets[i]`` the union of labels across row i's
    coordinates."""
    import jax

    closed = cell.traced()
    flat_args, _ = jax.tree_util.tree_flatten(cell.args)
    n = cell.n
    ev = TaintEval(n)
    pairs = []
    # Map flattened invars back to arg positions to seed the payload rows.
    # tree_flatten of the args tuple matches jaxpr invars order.
    arg_leaf_pos: List[int] = []
    for i, a in enumerate(cell.args):
        leaves = jax.tree_util.tree_leaves(a)
        arg_leaf_pos.extend([i] * len(leaves))
    assert len(arg_leaf_pos) == len(flat_args)
    for leaf, pos in zip(flat_args, arg_leaf_pos):
        v = np.asarray(leaf)
        t = _tz(n, v.shape)
        if pos in cell.bcast_args:
            if v.ndim == 0 or v.shape[0] != n:
                raise ValueError(
                    f"payload arg {pos} of cell {cell.name}/{cell.mode} has "
                    f"no leading node axis: {v.shape}"
                )
            for lbl in range(n):
                t[lbl, lbl] = True
        pairs.append((v, t))
    with _quiet_tracing():
        outs = ev.eval_closed(closed, pairs)
    out_val, out_t = outs[0]  # (new_flat, state, stats) flattens new_flat first
    if np.shape(out_val)[0] != n:
        raise AssertionError(
            f"cell {cell.name}/{cell.mode}: first output is not [N, P]"
        )
    self_t = out_t[np.arange(n), np.arange(n)]  # [N, P] self-label bits
    card = out_t.sum(axis=0) - self_t  # [N, P] non-self labels per coord
    per_node = card.max(axis=1).astype(int)
    sets = [
        sorted(int(l) for l in np.nonzero(out_t[:, i, :].any(axis=1))[0])
        for i in range(n)
    ]
    return {
        "per_node": tuple(int(c) for c in per_node),
        "max": int(per_node.max()),
        "sets": sets,
        "unknown_prims": sorted(ev.unknown),
    }


def rule_influence_summary(
    name: str,
    agg_overrides: Optional[Dict[str, Any]] = None,
    n: int = FLOW_N,
    modes: Optional[Sequence[str]] = None,
) -> Dict[str, Dict[str, Any]]:
    """Per-mode influence summaries for one rule (the `flow_summary` JSON
    payload and the MUR800/802 subject).  ``agg_overrides`` maps mode ->
    prebuilt AggregatorDef (tests inject leaky fakes this way)."""
    agg_overrides = agg_overrides or {}
    if modes is None:
        modes = rule_flow_modes(name, agg=agg_overrides.get("circulant"))
    out = {}
    for mode in modes:
        cell = build_flow_cell(
            name, mode, n=n, agg_override=agg_overrides.get(mode)
        )
        out[mode] = analyze_cell_influence(cell)
    return out


def _rule_anchor(name: str) -> Tuple[str, int]:
    from murmura_tpu.analysis.ir import _rule_anchor as ir_anchor

    return ir_anchor(name)


def influence_findings(
    name: str,
    summaries: Dict[str, Dict[str, Any]],
    influence,
    k: int,
    anchor: Optional[Tuple[str, int]] = None,
) -> List[Finding]:
    """MUR800 (bound) + MUR802 (mode parity) over one rule's analyzed
    summaries — factored out so tests drive it with fake rules."""
    path, line = anchor if anchor is not None else _rule_anchor(name)
    findings: List[Finding] = []
    for mode, s in summaries.items():
        if s.get("unknown_prims"):
            findings.append(Finding(
                "MUR800", path, line,
                f"aggregator '{name}' ({mode}) hit jaxpr primitives the "
                f"taint interpreter does not model: {s['unknown_prims']} — "
                "their coarse fallback taints everything, so the influence "
                "result is vacuous; teach analysis/flow.py the primitive",
                data={"rule": name, "mode": mode,
                      "unknown_prims": s["unknown_prims"]},
            ))
    if influence is None:
        findings.append(Finding(
            "MUR801", path, line,
            f"aggregator '{name}' declares no influence contract — set "
            "AggregatorDef.influence (aggregation/base.py InfluenceDecl) "
            "so the bounded-influence claim is machine-checked (MUR800) "
            "instead of folklore",
            data={"rule": name},
        ))
    elif influence.kind == "bounded":
        bound = influence.bound(k)
        for mode, s in summaries.items():
            if s["max"] > bound:
                findings.append(Finding(
                    "MUR800", path, line,
                    f"aggregator '{name}' ({mode}) leaks influence: some "
                    f"output coordinate mixes values from {s['max']} "
                    f"neighbors but the rule declares a bound of {bound} "
                    f"(degree {k}) — either the rule regressed or its "
                    "InfluenceDecl is wrong",
                    data={
                        "rule": name, "mode": mode, "analyzed": s["max"],
                        "declared_bound": bound, "degree": k,
                        "per_node": list(s["per_node"]),
                        "taint_sets": s["sets"],
                    },
                ))
    # MUR802: per-node cardinality parity across every supported mode —
    # for BOUNDED rules, where the cardinality IS the contract (krum must
    # stay 1 in compressed mode too).  Unbounded rules' benign-input
    # cardinality is data/precision-dependent: the dense Gram path centers
    # on the mean of ALL rows (a cancellation — ||(a-c)-(b-c)|| == ||a-b||
    # — the taint domain cannot see), so e.g. the dense geometric median
    # analyzes to "every row" while its circulant direct-norm twin
    # analyzes to the true neighborhood.  Their summaries are still
    # emitted for `check --json`.
    if influence is not None and influence.kind == "bounded":
        vectors = {m: s["per_node"] for m, s in summaries.items()}
    else:
        vectors = {}
    if len(set(vectors.values())) > 1:
        findings.append(Finding(
            "MUR802", path, line,
            f"aggregator '{name}' analyzes to different per-node influence "
            f"across exchange modes: "
            + "; ".join(f"{m}={list(v)}" for m, v in sorted(vectors.items()))
            + " — the same rule's math must bound influence identically in "
            "every mode (dense/circulant/sparse/compressed parity)",
            data={"rule": name,
                  "per_node": {m: list(v) for m, v in vectors.items()}},
        ))
    return findings


# Most recent flow sweep's per-rule/mode summaries, as `check --json`
# records ({"kind": "flow_summary", ...}); populated by check_influence.
_FLOW_SUMMARIES: List[Dict[str, Any]] = []


def flow_summaries() -> List[Dict[str, Any]]:
    return list(_FLOW_SUMMARIES)


@_family
def check_influence() -> List[Finding]:
    """MUR800/801/802: analyzed per-neighbor influence vs the declared
    contract, declaration coverage, and cross-mode parity."""
    from murmura_tpu.aggregation import AGGREGATORS

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES

    findings: List[Finding] = []
    _FLOW_SUMMARIES.clear()
    offsets = _flow_offsets(FLOW_N)
    k = len(offsets)
    for name in sorted(AGGREGATORS):
        path, line = _rule_anchor(name)
        try:
            # One circulant build answers both "which modes" and "what is
            # declared" — the per-mode cells are built by the summary sweep.
            case = dict(AGG_CASES.get(name, {}))
            case["exchange_offsets"] = list(offsets)
            agg_circ = build_aggregator(
                name, case, model_dim=FLOW_DIM, total_rounds=10
            )
            modes = rule_flow_modes(name, agg=agg_circ)
            summaries = rule_influence_summary(name, modes=modes)
            influence = agg_circ.influence
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR800", path, line,
                f"aggregator '{name}' crashed the influence sweep: "
                f"{type(e).__name__}: {e}",
            ))
            continue
        findings.extend(
            influence_findings(name, summaries, influence, k)
        )
        declared = (
            None if influence is None
            else {"kind": influence.kind,
                  "bound": (influence.bound(k)
                            if influence.kind == "bounded" else None),
                  "note": influence.note}
        )
        for mode, s in summaries.items():
            _FLOW_SUMMARIES.append({
                "kind": "flow_summary",
                "rule": name,
                "mode": mode,
                "degree": k,
                "max_influence": s["max"],
                "per_node": list(s["per_node"]),
                "taint_sets": s["sets"],
                "declared": declared,
            })
    return findings


# --------------------------------------------------------------------------
# Scrub dominance (MUR803) and denominators (MUR804)
# --------------------------------------------------------------------------


def _seed_round_ivals(
    args_tree, overrides: Optional[Dict[int, IVal]] = None
) -> List[IVal]:
    """Abstract seeds for a round program's flattened inputs: everything
    finite but arbitrary, ``overrides`` pinning specific top-level arg
    positions (adjacency/compromised/alive masks to [0, 1]), mask-named
    data leaves in [0, 1], integers bounded — contamination must be
    CREATED by the program's own math (diverging training, attack noise)
    and contained by its scrubs."""
    import jax

    overrides = overrides or {}
    paths = jax.tree_util.tree_flatten_with_path(args_tree)[0]
    ivals = []
    for (path, leaf) in paths:
        top = getattr(path[0], "idx", None) if path else None
        key = jax.tree_util.keystr(path)
        a = np.asarray(leaf)
        if top is not None and top in overrides:
            ivals.append(overrides[top])
        elif a.dtype == bool:
            ivals.append(BOOL_IV)
        elif np.issubdtype(a.dtype, np.integer) or np.issubdtype(
            a.dtype, np.unsignedinteger
        ):
            ivals.append(TOP_F)
        elif "mask" in key:
            ivals.append(_iv(0.0, 1.0))
        else:
            ivals.append(_iv(-_INF, _INF))
    return ivals


def scrub_dominance_report(
    fn,
    args_tree,
    check_leading: int = 2,
    seed_overrides: Optional[Dict[int, IVal]] = None,
):
    """Interval-analyze ``fn(*args_tree)`` with divergence-capable seeds;
    returns ``(contaminated_paths, events, unknown)`` where
    ``contaminated_paths`` are the output leaves among the first
    ``check_leading`` top-level outputs (params', agg_state') whose
    abstract value may carry input-originated non-finiteness.  The core of
    MUR803, factored out so tests drive it on hand-built programs."""
    import jax

    with _quiet_tracing():
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args_tree)
    ev = IntervalEval()
    ev.record_denominators = False  # MUR804's job, over rule cells
    outs = ev.eval_closed(
        closed, _seed_round_ivals(args_tree, seed_overrides)
    )
    flat_paths = jax.tree_util.tree_flatten_with_path(out_shape)[0]
    assert len(flat_paths) == len(outs)
    contaminated = []
    for (path, _shape), iv_out in zip(flat_paths, outs):
        keys = jax.tree_util.keystr(path)
        idx = getattr(path[0], "idx", None) if path else None
        if idx is not None and idx >= check_leading:
            continue  # metrics/stats may carry loss-derived non-finites
        if iv_out.nf:
            contaminated.append(keys)
    return contaminated, ev.events, sorted(ev.unknown)


# The rule set the scrub-dominance contract is traced over.  The sentinel
# lives in core/rounds.py UPSTREAM of every rule, so one representative per
# rule family keeps the sweep fast while still proving each rule's own math
# cannot resurrect contamination the scrub removed.
SCRUB_RULES: Tuple[str, ...] = (
    "fedavg", "krum", "median", "trimmed_mean", "geometric_median",
    "balance", "sketchguard", "ubar", "evidential_trust",
)


@_family
def check_scrub_dominance() -> List[Finding]:
    """MUR803: the NaN/attack scrub dominates all rule math.

    Each SCRUB_RULES faulted round program (NaN sentinel + gaussian attack
    armed — the configuration whose contract is 'non-finite data cannot
    reach parameters') is interval-analyzed with divergence-capable seeds:
    training math may abstractly diverge (log/exp/grad chains), the attack
    perturbation is contaminated by construction (RNG bitcasts), and the
    check fails if any output PARAMETER or carried aggregation-state leaf
    can still be non-finite — i.e. the where-style sentinel replacements
    in core/rounds.py no longer dominate every path to the output.  A mask
    applied multiplicatively (0 * nan == nan) keeps the contamination flag
    set, so the exact regression class PR 3 fixed by hand fails here
    statically."""
    import jax
    import jax.numpy as jnp

    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.analysis.ir import AGG_CASES, _canonical_adj
    from murmura_tpu.attacks.gaussian import make_gaussian_attack
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
    dim = _flow_probe_model(False)[2]
    attack = make_gaussian_attack(n, attack_percentage=0.25, noise_std=10.0)

    for rule in SCRUB_RULES:
        try:
            agg = build_aggregator(
                rule, dict(AGG_CASES.get(rule, {})), model_dim=dim,
                total_rounds=5,
            )
            prog = build_round_program(
                model, agg, data, total_rounds=5, batch_size=8,
                faults=FaultSpec(), attack=attack,
            )
            args = (
                prog.init_params,
                {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
                jax.random.PRNGKey(0),
                jnp.asarray(_canonical_adj(n, circulant=False)),
                jnp.asarray(attack.compromised, jnp.float32),
                jnp.ones((n,), jnp.float32),
                jnp.asarray(0.0, jnp.float32),
                {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
            )
            # Positions 3/4/5 of the faulted signature are the adjacency /
            # compromised / alive masks — [0, 1] by contract (the host-side
            # folds), which is what proves degree-style denominators like
            # fedavg's 1/(1+degree) nonzero.
            contaminated, events, unknown = scrub_dominance_report(
                prog.train_step, args,
                seed_overrides={
                    3: _iv(0.0, 1.0), 4: _iv(0.0, 1.0), 5: _iv(0.0, 1.0),
                },
            )
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR803", anchor, 1,
                f"the scrub-dominance sweep crashed for rule '{rule}': "
                f"{type(e).__name__}: {e}",
            ))
            continue
        if contaminated:
            entry_points = [
                e for e in events if e["kind"] in ("mask-mul",)
            ][:6]
            findings.append(Finding(
                "MUR803", anchor, 1,
                f"rule '{rule}': input-originated non-finiteness can reach "
                f"the round output at {contaminated[:6]} — the NaN/attack "
                "scrub (where-style replacement in core/rounds.py) no "
                "longer dominates every path; suspect multiplicative "
                "masking (0*inf == nan) or a bypassed sentinel"
                + (f"; mask-multiply sites: {entry_points}"
                   if entry_points else ""),
                data={"rule": rule, "contaminated": contaminated,
                      "mask_mul_events": entry_points,
                      "unknown_prims": unknown},
            ))
        elif unknown:
            findings.append(Finding(
                "MUR803", anchor, 1,
                f"rule '{rule}': the interval interpreter hit unmodeled "
                f"primitives {unknown} — their contaminated fallback makes "
                "the scrub-dominance verdict vacuous; teach "
                "analysis/flow.py the primitive",
                data={"rule": rule, "unknown_prims": unknown},
            ))
    return findings


def denominator_events(
    fn, args, seed_fn=None, closed=None
) -> List[Dict[str, Any]]:
    """Interval-analyze ``fn(*args)`` with post-scrub seeds and return the
    zero-denominator events (div/rsqrt/x**-k/rem whose denominator
    interval contains 0).  The MUR804 core, factored out for tests."""
    import jax

    if closed is None:
        with _quiet_tracing():
            closed = jax.make_jaxpr(fn)(*args)
    leaves = jax.tree_util.tree_leaves(args)
    ev = IntervalEval()
    if seed_fn is None:
        ivals = []
        for leaf in leaves:
            a = np.asarray(leaf)
            if a.dtype == bool:
                ivals.append(BOOL_IV)
            elif not np.issubdtype(a.dtype, np.floating):
                ivals.append(TOP_F)
            else:
                ivals.append(_iv(-_INF, _INF))
    else:
        ivals = seed_fn(leaves)
    ev.eval_closed(closed, ivals)
    return [e for e in ev.events if e["kind"] == "zero-denominator"]


def _cell_seeds(cell: FlowCell):
    """Post-scrub seeds for one cell's flattened args: broadcast/own are
    finite-but-arbitrary (MUR803 guarantees finiteness), the adjacency /
    edge-mask entries are [0, 1], carried state finite, round index within
    the horizon, int8 payloads within their code range."""
    import jax

    adj_pos = 3 if cell.mode == "compressed" else 2
    scale_pos = 2 if cell.mode == "compressed" else None

    def seed(leaves):
        out = []
        arg_leaf_pos: List[int] = []
        for i, a in enumerate(cell.args):
            arg_leaf_pos.extend([i] * len(jax.tree_util.tree_leaves(a)))
        for leaf, pos in zip(leaves, arg_leaf_pos):
            a = np.asarray(leaf)
            if a.dtype == bool:
                out.append(BOOL_IV)
            elif np.issubdtype(a.dtype, np.integer):
                # int8 payload codes are clipped to [-127, 127] by the
                # symmetric codec; other integers stay unbounded.
                out.append(
                    _iv(-127.0, 127.0) if a.dtype == np.int8 else TOP_F
                )
            elif pos == adj_pos:
                out.append(_iv(0.0, 1.0))  # adjacency / [k, N] edge mask
            elif pos == scale_pos:
                out.append(_iv(0.0, _INF))  # symmetric scales: max|x|/127
            else:
                out.append(_iv(-_INF, _INF))
        return out

    return seed


@_family
def check_denominators() -> List[Finding]:
    """MUR804: no reachable division/rsqrt sees a zero-capable denominator.

    Every rule cell in every supported mode, plus the compression codec
    (quantize_int8's guarded symmetric-scale division and compress_exchange
    end to end), is interval-analyzed under post-scrub seeds (finite but
    arbitrary exchange values, [0, 1] adjacency, the codec's scale
    invariants).  Guards — ``jnp.maximum(x, eps)`` floors, the codec's
    ``where(scale > 0, 1/max(scale, tiny), 0)`` — make denominators
    provably nonzero; any denominator whose interval still contains zero
    is a finding anchored at its source line."""
    from murmura_tpu.aggregation import AGGREGATORS

    findings: List[Finding] = []
    for name in sorted(AGGREGATORS):
        path, line = _rule_anchor(name)
        for mode in rule_flow_modes(name):
            try:
                cell = build_flow_cell(name, mode)
                events = denominator_events(
                    cell.fn, cell.args, seed_fn=_cell_seeds(cell),
                    closed=cell.traced(),
                )
            except Exception as e:  # noqa: BLE001 — a crash IS the finding
                findings.append(Finding(
                    "MUR804", path, line,
                    f"aggregator '{name}' ({mode}) crashed the denominator "
                    f"sweep: {type(e).__name__}: {e}",
                ))
                continue
            for e in events:
                e_path = e["path"] or path
                e_line = e["line"] or line
                findings.append(Finding(
                    "MUR804", e_path, e_line,
                    f"aggregator '{name}' ({mode}): {e['prim']} "
                    f"{e['detail']} (given post-scrub finite inputs and "
                    "[0, 1] masks) — a Byzantine-steerable zero denominator "
                    "is inf/NaN injection past the sentinel",
                    data={"rule": name, "mode": mode, **e},
                ))
    findings.extend(_codec_denominator_findings())
    return findings


def _codec_denominator_findings() -> List[Finding]:
    import jax.numpy as jnp

    from murmura_tpu.ops.compress import (
        RESIDUAL_KEY,
        CompressionSpec,
        compress_exchange,
        quantize_int8,
    )

    findings: List[Finding] = []
    pkg = Path(__file__).resolve().parent.parent
    anchor = (str(pkg / "ops" / "compress.py"), 1)
    n, p = FLOW_N, FLOW_DIM
    bcast = jnp.zeros((n, p), jnp.float32)
    resid = jnp.zeros((n, p), jnp.float32)
    spec = CompressionSpec("int8", block=FLOW_BLOCK, error_feedback=True)

    subjects = [
        ("quantize_int8", lambda b: quantize_int8(b, FLOW_BLOCK), (bcast,)),
        (
            "compress_exchange[int8+ef]",
            lambda b, r: compress_exchange(
                spec, b, {RESIDUAL_KEY: r}, True
            ),
            (bcast, resid),
        ),
    ]
    for label, fn, args in subjects:
        try:
            events = denominator_events(fn, args)
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR804", anchor[0], anchor[1],
                f"codec subject '{label}' crashed the denominator sweep: "
                f"{type(e).__name__}: {e}",
            ))
            continue
        for e in events:
            findings.append(Finding(
                "MUR804", e["path"] or anchor[0], e["line"] or anchor[1],
                f"codec '{label}': {e['prim']} {e['detail']} — an all-zero "
                "block's scale is exactly 0; the symmetric codec must keep "
                "its guarded-inverse form",
                data={"subject": label, **e},
            ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

_FLOW_MEMO: Optional[List[Finding]] = None


def check_flow(force: bool = False) -> List[Finding]:
    """Run MUR800-804 over the flow grid; returns findings (empty = every
    dataflow contract holds).  Memoized per process — the tier-1 gate and
    the CLI share one sweep.  Trace-level only:
    nothing compiles, nothing needs a multi-device platform."""
    global _FLOW_MEMO
    if _FLOW_MEMO is not None and not force:
        return list(_FLOW_MEMO)

    from murmura_tpu.analysis.ir import _apply_suppressions

    findings: List[Finding] = []
    for fam_name, fam in FLOW_CHECK_FAMILIES.items():
        try:
            findings.extend(fam())
        except Exception as e:  # noqa: BLE001 — a crash IS the finding
            findings.append(Finding(
                "MUR800", str(Path(__file__).resolve()), 1,
                f"flow check family '{fam_name}' crashed: "
                f"{type(e).__name__}: {e}",
            ))
    findings = _apply_suppressions(list(dict.fromkeys(findings)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _FLOW_MEMO = list(findings)
    return findings
