"""Pallas TPU kernels for the aggregation hot loop: fused distance
accumulation and candidate selection (docs/PERFORMANCE.md).

The round is exchange/aggregation-bound, not FLOP-bound.  The
aggregation hot loop's HBM traffic is dominated by
re-reading the [N, P] broadcast tensor: the circulant distance pass reads
it once per offset (k rolled passes), and the candidate-stack rules
materialize rolled copies before sorting.  These kernels stream the
parameter axis through VMEM once and fuse everything downstream of the
read:

``circulant_sq_distances``
    [k, N] squared neighbor distances in ONE pass over own/bcast: each
    [N, C] chunk is loaded once and all k rolled subtract-square-reduce
    chains run in VMEM — 2·N·P HBM reads instead of (k+1)·N·P.

``pairwise_sq_distances``
    The dense [N, M] distance matrix (krum/ubar/balance stage 1) with the
    Gram matmul, the squared norms, and the final combination fused in one
    streamed pass; the MXU does the per-chunk dot.

``fused_candidate_select``
    The static circulant median/trimmed-mean: per P-chunk, the [m, N, C]
    candidate stack is built from rolls in VMEM, sorted along the small
    static m axis with an odd-even transposition network, and reduced to
    the median / trimmed mean — the [N, m, P]-class intermediate the lax
    path sorts over never exists.

Deployment contract (mirrors ``ops/pallas_sketch.py``):

- ``interpret=True`` exactly when the default backend is not a TPU — the
  tier-1 suite (pinned to CPU) runs every kernel through the Pallas
  interpreter, so parity with the lax reference path is tested everywhere
  (tests/test_pallas_agg.py); on a TPU the kernels are always compiled
  (``chip_smoke.py`` asserts ``tpu_custom_call`` in the compiled round,
  tests/test_chip_compile.py compiles them for a described v5e).
- Opt-in via ``tpu.pallas_agg: true`` (or ``MURMURA_PALLAS_AGG=1``), wired
  by the factories as an aggregator param; off by default.  Sharded-axis
  policy (precise, per entry point): a sharded **nodes** axis is refused
  (in-kernel rolls are node-axis wrap-arounds; pallas_call does not
  decompose under GSPMD) — the entry points return ``None`` and callers
  keep the lax kernels.  A sharded **param** axis is accepted with
  SHARD-LOCAL grids: the kernel runs under ``shard_map`` over the mesh's
  ``"param"`` axis on each device's own column block, and the distance
  kernels finish with one small ``psum`` of the [k, N]/[N, M] scalars —
  exactly the sharded-P collective contract (MUR1300).  Anything else
  (both axes sharded, a width the shard count does not divide) falls back
  to lax by returning ``None``.
- Each entry point returns ``None`` when the shapes fall outside the
  kernel's support envelope — compiled mode needs the resident node dim
  ``N % 128 == 0`` (in-kernel rolls wrap at the block's row count and N
  is the lane dim of the [k, N]/[N, M] outputs), plus the VMEM budget;
  callers (aggregation/base.py) fall back to the lax path, so enabling the
  toggle is always safe.  Every such refusal raises a
  :class:`PallasEnvelopeWarning` naming the kernel and the shape that fell
  out, so an armed toggle never runs lax without a word.
- Parity is to documented tolerance, not bit-exact: the kernels accumulate
  chunk sums in float32 like the lax kernels but group them differently,
  and candidate stacks are compared/summed in f32 before the final cast.

Budget cells for the kernels land in ``analysis/BUDGETS.json`` under the
``pallas`` mode (analysis/budgets.py), so the FLOP/bytes delta of the
fused formulation is committed, reviewable perf history.
"""

import functools
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Per-input-block VMEM budget (bytes).  The distance kernels hold two
# [N, C] f32 blocks plus the [k, N]/[N, M] accumulator; the candidate
# kernel holds an m-high stack.  ~16 MB VMEM/core; stay well under.
_VMEM_BLOCK_BYTES = 4 * 1024 * 1024

# Hard cap on the resident accumulator (pairwise kernel holds [N, M] f32
# in VMEM for the whole sweep).
_MAX_PAIRWISE_CELLS = 1024 * 1024


class PallasEnvelopeWarning(UserWarning):
    """A requested aggregation kernel fell outside its envelope and the
    caller runs the lax path instead."""


def _refuse(kernel: str, shapes, reason: str) -> None:
    """Report an out-of-envelope request (trace time) and return ``None``
    for the caller's lax fallback."""
    warnings.warn(
        f"pallas_agg.{kernel}: {reason}; shapes "
        f"{[tuple(s) for s in shapes]} run the lax path",
        PallasEnvelopeWarning,
        stacklevel=3,
    )
    return None


def _sharded_axis_mode():
    """(mode, mesh) of the active param-axis trace scope
    (parallel/mesh.py): ``("nodes", mesh)`` = a sharded node axis — every
    entry point must REFUSE (return None; in-kernel rolls wrap at the
    resident row count, which is wrong on a split node axis);
    ``("param", mesh)`` = param-only sharding — run with shard-local
    grids via :func:`_param_shard_map`; ``(None, None)`` = no sharded
    scope (plain single-device call, or both axes size 1)."""
    from murmura_tpu.parallel.mesh import (
        active_param_scope,
        mesh_node_axis,
        mesh_param_shards,
    )

    scope = active_param_scope()
    if scope is None:
        return None, None
    mesh = scope[0]
    if mesh_node_axis(mesh) > 1:
        return "nodes", mesh
    if mesh_param_shards(mesh) > 1:
        return "param", mesh
    return None, None


def _param_shard_map(fn, mesh, n_in: int, reduce_out: bool):
    """Wrap a per-column-block kernel call for a param-sharded mesh:
    inputs split their LAST axis over ``"param"`` (shard-local grids —
    each device streams only its own columns), and the output either
    ``psum``s over the param groups (distance accumulations: the one
    small scalar collective of the sharded-P contract) or stays a
    column-sharded map (candidate selection)."""
    from jax.sharding import PartitionSpec as P

    col = P(None, "param")

    def local(*blocks):
        out = fn(*blocks)
        if reduce_out:
            out = jax.lax.psum(out, "param")
        return out

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(col,) * n_in,
        out_specs=P() if reduce_out else col,
        check_vma=False,
    )


def _param_shards_of(mesh) -> int:
    from murmura_tpu.parallel.mesh import mesh_param_shards

    return mesh_param_shards(mesh)


def _interpret_default() -> bool:
    """Interpreter mode exactly when there is no TPU (the test-suite
    path): nothing can put a TPU run into interpret mode unnoticed."""
    return jax.default_backend() != "tpu"


def _chunk_cols(n_rows: int, p: int, copies: int) -> int:
    """Lane-aligned chunk width so ``copies`` [n_rows, C] f32 blocks fit
    the VMEM budget."""
    c = _VMEM_BLOCK_BYTES // max(1, 4 * n_rows * copies)
    c = max(128, (c // 128) * 128)
    return min(c, max(128, (-(-p // 128)) * 128))


def _mask_tail(blk, i, chunk: int, p: int):
    """Zero the columns of grid step ``i``'s [rows, chunk] block that lie
    past the true width ``p``.  The grid is ``cdiv(p, chunk)`` over the
    UNPADDED inputs (padding them would copy both full [N, P] operands in
    HBM), so the last block's out-of-bounds lanes hold unspecified values;
    a select (not arithmetic) discards them, NaNs included."""
    if p % chunk == 0:
        return blk
    col = i * chunk + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.where(col < p, blk, jnp.zeros_like(blk))


# ---------------------------------------------------------------------------
# circulant fused distances
# ---------------------------------------------------------------------------


def _circ_dist_kernel(own_ref, b_ref, out_ref, *, offsets, k_pad, chunk, p):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Masked tail columns contribute (0 - 0)^2 to every distance.
    o_blk = _mask_tail(own_ref[:].astype(jnp.float32), i, chunk, p)
    b_blk = _mask_tail(b_ref[:].astype(jnp.float32), i, chunk, p)
    rows = []
    for off in offsets:
        d = o_blk - jnp.roll(b_blk, -off, axis=0)
        rows.append(jnp.sum(d * d, axis=1))
    acc = jnp.stack(rows)
    if k_pad > len(offsets):
        acc = jnp.pad(acc, ((0, k_pad - len(offsets)), (0, 0)))
    out_ref[:] += acc


@functools.partial(
    jax.jit, static_argnames=("offsets", "interpret")
)
def _circ_dist_call(own, bcast, offsets, interpret):
    n, p = bcast.shape
    k = len(offsets)
    chunk = _chunk_cols(n, p, 2)
    k_pad = k if interpret else -(-k // 8) * 8
    if not interpret and n % 128:
        # Row padding would corrupt the wrap-around of in-kernel rolls;
        # the caller falls back (see circulant_sq_distances).
        raise ValueError("unaligned n reached the kernel")
    out = pl.pallas_call(
        functools.partial(
            _circ_dist_kernel, offsets=tuple(offsets), k_pad=k_pad,
            chunk=chunk, p=p,
        ),
        grid=(pl.cdiv(p, chunk),),
        in_specs=[
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k_pad, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, n), jnp.float32),
        interpret=interpret,
    )(own, bcast)
    return out[:k]


def circulant_sq_distances(
    own: jnp.ndarray,
    bcast: jnp.ndarray,
    offsets: Sequence[int],
    interpret: Optional[bool] = None,
) -> Optional[jnp.ndarray]:
    """[k, N] squared distances D2[o, i] = ||own_i - bcast[(i+o) % N]||^2
    in one fused streaming pass, or ``None`` when the shapes fall outside
    the kernel envelope (caller falls back to the lax path)."""
    if interpret is None:
        interpret = _interpret_default()
    n, p = bcast.shape
    refuse = functools.partial(
        _refuse, "circulant_sq_distances", (own.shape, bcast.shape)
    )
    if not offsets or own.shape != bcast.shape:
        return refuse("no offsets or own/bcast shapes differ")
    # Compiled mode: in-kernel rolls wrap at the block's row count, so the
    # node dim must be exactly resident (no row padding) and lane-aligned
    # for the [k, N] output.
    if not interpret and n % 128 != 0:
        return refuse("compiled mode needs N % 128 == 0")
    mode, mesh = _sharded_axis_mode()
    if mode == "nodes":
        # rolls wrap at the resident row count — lax path
        return refuse("the node axis is sharded")
    if mode == "param":
        if p % _param_shards_of(mesh):
            return refuse("the param shard count does not divide P")
        return _param_shard_map(
            lambda o_l, b_l: _circ_dist_call(
                o_l, b_l, tuple(int(o) for o in offsets), interpret
            ),
            mesh, n_in=2, reduce_out=True,
        )(own, bcast)
    return _circ_dist_call(own, bcast, tuple(int(o) for o in offsets), interpret)


# ---------------------------------------------------------------------------
# dense fused pairwise distances
# ---------------------------------------------------------------------------


def _pairwise_kernel(
    a_ref, b_ref, out_ref, g_ref, sa_ref, sb_ref, *, chunk, p
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        g_ref[:] = jnp.zeros_like(g_ref)
        sa_ref[:] = jnp.zeros_like(sa_ref)
        sb_ref[:] = jnp.zeros_like(sb_ref)

    a = _mask_tail(a_ref[:].astype(jnp.float32), i, chunk, p)
    b = _mask_tail(b_ref[:].astype(jnp.float32), i, chunk, p)
    g_ref[:] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    sa_ref[:] += jnp.sum(a * a, axis=1)[None, :]
    sb_ref[:] += jnp.sum(b * b, axis=1)[None, :]

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = (
            sa_ref[0, :][:, None] + sb_ref[0, :][None, :] - 2.0 * g_ref[:]
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pairwise_call(a, b, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, p = a.shape
    m = b.shape[0]
    chunk = _chunk_cols(max(n, m), p, 2)
    scratch = [
        pltpu.VMEM((n, m), jnp.float32),
        pltpu.VMEM((1, n), jnp.float32),
        pltpu.VMEM((1, m), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_pairwise_kernel, chunk=chunk, p=p),
        grid=(pl.cdiv(p, chunk),),
        in_specs=[
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
            pl.BlockSpec((m, chunk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(a, b)


def pairwise_sq_distances(
    a: jnp.ndarray,
    b: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> Optional[jnp.ndarray]:
    """[N, M] squared distances with the Gram matmul and norm combination
    fused into one streamed pass.  Inputs are expected pre-centered (the
    caller owns the cancellation guard — aggregation/base.py); returns
    ``None`` outside the kernel envelope."""
    if interpret is None:
        interpret = _interpret_default()
    n, p = a.shape
    m = b.shape[0]
    refuse = functools.partial(
        _refuse, "pairwise_sq_distances", (a.shape, b.shape)
    )
    if b.shape[1] != p:
        return refuse("a/b widths differ")
    if n * m > _MAX_PAIRWISE_CELLS:
        # the [N, M] accumulator must stay VMEM-resident
        return refuse(f"N*M exceeds {_MAX_PAIRWISE_CELLS} accumulator cells")
    if not interpret and (n % 8 != 0 or m % 128 != 0):
        return refuse("compiled mode needs N % 8 == 0 and M % 128 == 0")
    mode, mesh = _sharded_axis_mode()
    if mode == "nodes":
        # the [N, M] accumulator spans the split node axis
        return refuse("the node axis is sharded")
    if mode == "param":
        if p % _param_shards_of(mesh):
            return refuse("the param shard count does not divide P")
        # Shard-local Gram/norm partials over each device's columns, one
        # [N, M] psum at the end: d2 = sum over shards of local d2.
        return _param_shard_map(
            lambda a_l, b_l: _pairwise_call(a_l, b_l, interpret),
            mesh, n_in=2, reduce_out=True,
        )(a, b)
    return _pairwise_call(a, b, interpret)


# ---------------------------------------------------------------------------
# fused candidate selection (static circulant median / trimmed mean)
# ---------------------------------------------------------------------------


def _candidate_kernel(own_ref, b_ref, out_ref, *, offsets, trim, median):
    o_blk = own_ref[:].astype(jnp.float32)
    b_blk = b_ref[:].astype(jnp.float32)
    cand = [o_blk] + [jnp.roll(b_blk, -off, axis=0) for off in offsets]
    m = len(cand)
    # Odd-even transposition network: m passes of compare-exchange sort the
    # m-candidate stack coordinate-wise (exact — same sorted values as
    # jnp.sort over the stacked axis).
    for sweep in range(m):
        for j in range(sweep % 2, m - 1, 2):
            lo = jnp.minimum(cand[j], cand[j + 1])
            hi = jnp.maximum(cand[j], cand[j + 1])
            cand[j], cand[j + 1] = lo, hi
    if median:
        res = 0.5 * (cand[(m - 1) // 2] + cand[m // 2])
    else:
        kept = cand[trim : m - trim]
        acc = kept[0]
        # Static unroll over a Python list of tracers (len is the static
        # candidate count) — not traced control flow.
        for c in kept[1:]:  # murmura: ignore[MUR001]
            acc = acc + c
        res = acc / float(len(kept))
    out_ref[:] = res.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("offsets", "trim", "median", "interpret")
)
def _candidate_call(own, bcast, offsets, trim, median, interpret):
    n, p = bcast.shape
    m = len(offsets) + 1
    chunk = _chunk_cols(n, p, m + 2)
    # Coordinate-wise along P: the last block's out-of-bounds lanes are
    # computed on unspecified values and dropped on the write-back, so the
    # unpadded [N, P] operands stream through with no HBM copy.
    return pl.pallas_call(
        functools.partial(
            _candidate_kernel,
            offsets=tuple(offsets),
            trim=trim,
            median=median,
        ),
        grid=(pl.cdiv(p, chunk),),
        in_specs=[
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, p), own.dtype),
        interpret=interpret,
    )(own, bcast)


def candidate_select_supported(
    own,
    bcast,
    offsets: Sequence[int],
    trim: int = 0,
    interpret: Optional[bool] = None,
) -> bool:
    """Static envelope predicate for :func:`fused_candidate_select` — lets
    rules pick the kernel vs the lax path with a plain Python branch (no
    traced operand, MUR001-clean) at trace time."""
    if interpret is None:
        interpret = _interpret_default()

    def refuse(reason: str) -> bool:
        _refuse("fused_candidate_select", (own.shape, bcast.shape), reason)
        return False

    if not offsets or tuple(own.shape) != tuple(bcast.shape):
        return refuse("no offsets or own/bcast shapes differ")
    m = len(offsets) + 1
    if trim < 0 or m - 2 * trim < 1:
        return refuse(f"trim {trim} leaves no candidate of {m}")
    if not interpret and bcast.shape[0] % 128 != 0:
        # in-kernel rolls wrap at the resident row count
        return refuse("compiled mode needs N % 128 == 0")
    mode, mesh = _sharded_axis_mode()
    if mode == "nodes":
        # rolls wrap at the resident row count — lax path
        return refuse("the node axis is sharded")
    if mode == "param" and bcast.shape[1] % _param_shards_of(mesh):
        # columns must split evenly into shard-local grids
        return refuse("the param shard count does not divide P")
    return True


def fused_candidate_select(
    own: jnp.ndarray,
    bcast: jnp.ndarray,
    offsets: Sequence[int],
    trim: int = 0,
    median: bool = False,
    interpret: Optional[bool] = None,
) -> Optional[jnp.ndarray]:
    """[N, P] coordinate-wise median (``median=True``) or ``trim``-trimmed
    mean over the static circulant candidate stack {own} ∪ {k rolled
    broadcasts}, fused with the streaming read.  ``None`` outside the
    envelope (masked/sparse candidate sets keep the lax path — their
    per-node counts are traced)."""
    if interpret is None:
        interpret = _interpret_default()
    if not candidate_select_supported(
        own, bcast, offsets, trim=0 if median else trim, interpret=interpret
    ):
        return None
    mode, mesh = _sharded_axis_mode()
    if mode == "param":
        # Coordinate-wise along P: a pure shard-local map over each
        # device's column block, no collective at all.
        return _param_shard_map(
            lambda o_l, b_l: _candidate_call(
                o_l, b_l, tuple(int(o) for o in offsets), int(trim),
                bool(median), interpret,
            ),
            mesh, n_in=2, reduce_out=False,
        )(own, bcast)
    return _candidate_call(
        own, bcast, tuple(int(o) for o in offsets), int(trim), bool(median),
        interpret,
    )
