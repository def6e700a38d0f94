"""Aggregation rule interface and shared kernels.

The reference's ``Aggregator.aggregate(node_id, own_state, neighbor_states,
round_num)`` (murmura/aggregation/base.py:20-49) runs once per node per round
over Python dicts.  Here a rule is one pure function over the whole network:

    aggregate(own[N, P], bcast[N, P], adj[N, N], round_idx, state, ctx)
        -> (new_flat[N, P], new_state, stats)

- ``own`` holds each node's true state; ``bcast`` holds the states as
  broadcast (post-attack).  The two differ only on compromised rows — the
  reference aggregates with the node's own true state while neighbors see
  the attacked snapshot (murmura/core/network.py:108-135, node.py:214-252);
- ``adj`` is the 0/1 adjacency mask of the gathered neighbor tensor;
- ``state`` carries cross-round per-rule memory (EMA trust, acceptance
  windows) that the reference keeps as Python attributes
  (e.g. evidential_trust.py:112-113, sketchguard.py:61-64);
- ``stats`` are per-node arrays replacing ``get_statistics()`` scalars.

Everything is traced — the rule compiles into the jitted round step.
"""

from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Collection,
    Dict,
    FrozenSet,
    Mapping,
    Optional,
    Tuple,
)

import jax
import jax.numpy as jnp

from murmura_tpu.ops.compress import Int8Blocks

Stats = Dict[str, jnp.ndarray]
AggState = Dict[str, jnp.ndarray]

# Canonical names of the communication primitives a lowered aggregation
# program may contain (the vocabulary of ``AggregatorDef.collectives`` and
# of the MUR202 collective-inventory check, analysis/ir.py).  They mirror
# the XLA HLO ops GSPMD emits when the node axis is sharded: the dense
# rules' gathered [N, P] reads become ``all_gather``/``all_reduce``; the
# circulant rules' ``jnp.roll`` becomes boundary ``ppermute``
# (collective-permute); vmapped probe sweeps may add ``all_to_all``.
COLLECTIVE_NAMES = frozenset(
    {"all_gather", "all_reduce", "ppermute", "all_to_all", "reduce_scatter"}
)


@dataclass(frozen=True)
class AggContext:
    """Per-round context handed to aggregation rules.

    Attributes:
        apply_fn: single-model forward (params, x, key, train) -> outputs.
        unravel: flat [P] -> params pytree.
        probe_x/probe_y/probe_mask: per-node probe batches [N, B, ...] used by
            loss-probe rules (UBAR stage 2 — ubar.py:152-202) and trust
            evaluation (evidential_trust.py:214-316).
        evidential: whether apply_fn outputs Dirichlet alphas.
        num_classes: output arity (for losses).
        total_rounds: T for threshold schedules.
        probe_cross: optional precomputed [N, N] cross-eval metric dict
            (probe.combined_probe_metric output) — set when another consumer
            in the same round step (DMTT) already paid for the N x N forward
            sweep, so probe-based rules reuse instead of recompute.
    """

    apply_fn: Callable = None
    unravel: Callable = None
    probe_x: Optional[jnp.ndarray] = None
    probe_y: Optional[jnp.ndarray] = None
    probe_mask: Optional[jnp.ndarray] = None
    evidential: bool = False
    num_classes: int = 0
    total_rounds: int = 1
    probe_cross: Optional[Dict[str, jnp.ndarray]] = None
    # True when the round step runs with the node axis sharded over a mesh
    # (tpu.num_devices > 1): circulant shift lowerings differ — jnp.roll
    # becomes boundary collective-permutes (O(degree) communication, the
    # point of tpu.exchange: ppermute) while a static-index gather would
    # lower to an all-gather; on ONE device the roles reverse (roll's
    # wrap-around slice pads up to 128x, a gather pads nothing).
    node_axis_sharded: bool = False
    # telemetry.audit_taps: rules additionally surface per-node decision
    # tensors (tap_* stats — who selected/accepted whom this round) riding
    # the normal stats/history output path.  Trace-time static; the tapped
    # program must add NO collectives beyond the rule's declared inventory
    # (circulant taps use rolls, dense taps use axis reductions already in
    # the declared set) and NO recompiles across rounds — both are
    # machine-checked contracts (`murmura check --ir` MUR400/MUR402).
    audit: bool = False


@dataclass(frozen=True)
class InfluenceDecl:
    """Declared Byzantine influence contract of a rule (``murmura check
    --flow``, MUR800-802 — analysis/flow.py).

    The flow analyzer seeds each exchanged broadcast row with a distinct
    taint label and propagates *value* dataflow through the rule's jaxpr
    (selection dataflow — comparisons, sort permutations, gather indices,
    ``where`` predicates — is excluded by construction: it decides WHICH
    finite values are chosen, and the finiteness precondition is
    discharged separately by the MUR803 scrub-dominance check).  The
    resulting per-output-coordinate taint cardinality is the number of
    distinct neighbors whose broadcast VALUES can enter that coordinate.

    ``kind="bounded"`` declares a cap: ``bound(k)`` maps the per-node
    neighbor count ``k`` (non-self candidates; self is always excluded
    from the cardinality) to the maximum labels any single output
    coordinate may carry — e.g. Krum's single winner (1), the
    coordinate-wise median's middle pair, the trimmed mean's kept
    interior.  MUR800 fails when the analyzed cardinality exceeds it.

    ``kind="unbounded"`` is an explicit admission that every neighbor's
    value can reach the output (fedavg's mean) or that the cap is
    data-dependent and vanishes on benign inputs (BALANCE/UBAR-style
    accept-filters admit everything when nothing looks hostile; the
    geometric median downweights but never excludes).  ``note`` says why
    — it doubles as runtime documentation (``murmura report`` prints it
    next to the observed audit-tap rejection counts).

    Declaring nothing is itself a finding (MUR801): every registered rule
    must state its influence contract, exactly as it must state its
    collective inventory.
    """

    kind: str  # "bounded" | "unbounded"
    bound: Optional[Callable[[int], int]] = None
    note: str = ""

    def __post_init__(self):
        if self.kind not in ("bounded", "unbounded"):
            raise ValueError(
                f"influence kind must be 'bounded' or 'unbounded', got "
                f"{self.kind!r}"
            )
        if self.kind == "bounded" and self.bound is None:
            raise ValueError("bounded influence declarations need a bound()")
        if self.kind == "unbounded" and self.bound is not None:
            raise ValueError(
                "unbounded influence declarations must not carry a bound()"
            )

    def describe(self, k: Optional[int] = None) -> str:
        """Human-readable contract line (the `murmura report` rendering)."""
        if self.kind == "unbounded":
            base = "unbounded"
        elif k is None:
            base = "bounded"
        else:
            base = f"bounded: <= {self.bound(k)} of {k} neighbors per coordinate"
        return f"{base} — {self.note}" if self.note else base


@dataclass(frozen=True)
class AggregatorDef:
    """A named aggregation rule with optional carried state.

    ``state_kind`` maps each carried-state key to its indexing scheme:
    'node' = leading axis is the node id (e.g. acceptance windows), 'edge' =
    [N, N] directed-edge matrix (e.g. smoothed trust).  The ZMQ distributed
    backend uses this to project the stacked state onto one process's view.

    ``collectives`` declares the rule's communication contract: for each
    exchange mode ('dense' = gathered [N, P] adjacency masking, 'circulant'
    = tpu.exchange: ppermute rolls) the set of :data:`COLLECTIVE_NAMES`
    the lowered SPMD program is allowed to contain.  ``murmura check --ir``
    (MUR202, analysis/ir.py) compiles each rule over a sharded node axis
    and fails on any collective outside the declaration — a stray
    ``all_gather`` on the circulant path is a finding at check time, not a
    silent O(N) ICI regression on the chip.  ``None`` means undeclared,
    itself a finding for registered rules.
    """

    name: str
    aggregate: Callable[
        [jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, AggState, AggContext],
        Tuple[jnp.ndarray, AggState, Stats],
    ]
    init_state: Callable[[int], AggState] = field(default=lambda num_nodes: {})
    needs_probe: bool = False
    state_kind: Dict[str, str] = field(default_factory=dict)
    collectives: Optional[Mapping[str, Collection[str]]] = None
    # True when this rule's exchange consumes the broadcast exclusively
    # through the shared circulant kernels below, which accept the int8
    # compressed payload (ops/compress.Int8Blocks) in place of the float
    # tensor — the rolls then move int8 + per-block scales through the
    # boundary ppermutes instead of a dequantized [*, P] float operand
    # (compressed exchange, MUR700).  Rules that run arbitrary math over
    # the broadcast (probe forwards, sketch tables) keep False and receive
    # the receiver-side dequantized tensor from core/rounds.py.
    quantized_exchange: bool = False
    # Declared Byzantine influence contract (see :class:`InfluenceDecl`):
    # how many distinct neighbors' broadcast VALUES may enter any single
    # output coordinate.  ``murmura check --flow`` verifies the analyzed
    # taint cardinality against it per exchange mode (MUR800), requires
    # every registered rule to declare one (MUR801), and pins the analyzed
    # result's parity across dense/circulant/sparse/compressed modes
    # (MUR802).  None = undeclared, itself a finding for registered rules.
    influence: Optional[InfluenceDecl] = None
    # The rule mixes the rows with weights that no row's values decide and
    # treats every column alike, so applied to one leaf's columns it gives
    # those columns of what it gives on the whole [N, P] row (FedAvg's
    # dense mean), and it takes ``own``/``bcast`` of any rank [N, ...].
    # core/rounds.py then hands a state of a GiB or more over leaf by
    # leaf, each stacked leaf as it lies, and never builds its [N, P] row
    # (``build_round_program``, "by leaf").  False: the rule needs the row.
    leafwise: bool = False

    def declared_collectives(self, circulant) -> Optional[FrozenSet[str]]:
        """Allowed collective set for one exchange mode (``None`` =
        undeclared).  The hook the IR analyzer calls; values must be drawn
        from :data:`COLLECTIVE_NAMES`.  ``circulant`` is the legacy bool
        (dense/circulant) or a mode string; mode ``"sparse"`` (the [k, N]
        edge-mask engine) inherits the circulant declaration unless a rule
        declares a tighter ``"sparse"`` set — the sparse path IS the
        circulant machinery with mask weights (MUR601)."""
        if self.collectives is None:
            return None
        if isinstance(circulant, str):
            mode = circulant
        else:
            mode = "circulant" if circulant else "dense"
        if mode == "sparse" and "sparse" not in self.collectives:
            mode = "circulant"
        return frozenset(self.collectives.get(mode, ()))


# ---------------------------------------------------------------------------
# Shared kernels
# ---------------------------------------------------------------------------


def pairwise_l2_distances(
    a: jnp.ndarray, b: Optional[jnp.ndarray] = None, pallas: bool = False
) -> jnp.ndarray:
    """L2 distance matrix D[i, j] = ||a_i - b_j|| via one Gram matmul.

    With ``b=None`` this is the all-pairs matrix over one tensor. The
    reference instead recomputes per-pair distances inside each node's
    Python loop (krum.py:54-62, balance.py:99-106).

    Numerics: the rows are centered on the mean of ``a`` before the Gram
    identity.  Late in training all nodes' parameter vectors cluster around
    a common point with norms orders of magnitude larger than their pairwise
    distances; without centering, sq_a + sq_b - 2ab cancels catastrophically
    in float32 and Krum's small-distance ranking degrades to rounding noise.
    Centering leaves distances unchanged and shrinks the norms to the
    cluster scale.
    """
    same = b is None
    in_dtype = a.dtype
    a32 = a.astype(jnp.float32)
    b32 = a32 if same else b.astype(jnp.float32)
    center = jnp.mean(a32, axis=0, keepdims=True)
    a32 = a32 - center
    b32 = a32 if same else b32 - center
    if pallas:
        # Fused streamed kernel (ops/pallas_agg.py): Gram matmul + norms +
        # combination in one pass over the centered operands.  None =
        # shapes outside the kernel envelope; fall through to the lax path.
        from murmura_tpu.ops import pallas_agg

        d2p = pallas_agg.pairwise_sq_distances(a32, b32)
        if d2p is not None:
            return jnp.sqrt(jnp.maximum(d2p, 0.0))
    # Squared norms and the final combination accumulate in f32 regardless
    # of input dtype: with bf16 params (tpu.param_dtype) a bf16 reduction
    # would quantize the small post-centering distances the selection ranks
    # on.  The Gram matmul itself keeps bf16 *inputs* with f32 accumulation
    # (preferred_element_type) — the MXU-native mode — rather than f32
    # operands, which would double the memory-bound matmul's HBM reads.
    sq_a = jnp.sum(a32 * a32, axis=-1)
    sq_b = sq_a if same else jnp.sum(b32 * b32, axis=-1)
    if in_dtype == jnp.bfloat16:
        da, db = a32.astype(in_dtype), b32.astype(in_dtype)
    else:
        da, db = a32, b32
    # The Gram runs at the MXU's default precision whatever the ambient
    # jax.default_matmul_precision: the identity cancels the dot against
    # the f32 VPU norms above, and on a v5e a "high"/"highest" [N, 6.6M]
    # dot disagrees with those norms by 5e-4 of their value (measured, PR
    # 22) — under an attack whose noise inflates the centred norms that is
    # several times d2 itself, every d2 clamps to 0 and every score with it.
    # The default-precision dot agrees with the norms to 3e-6.
    d2 = (
        sq_a[:, None]
        + sq_b[None, :]
        - 2.0 * jnp.dot(
            da, db.T, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
    )
    return jnp.sqrt(jnp.maximum(d2, 0.0))


# Per-rolled-copy HBM budget for the circulant kernels.  A full-width
# jnp.roll of the stacked [N, P] states materializes ~k copies at once
# (XLA schedules the Python-unrolled offsets concurrently), and the
# wrap-around slices ([1..k, P]) pick up a 32-128x tile-padding expansion
# at large N — the 25 GB OOM the 256-node north-star program hit on a
# 15.75 GB v5e chip.  Chunking the parameter axis caps the rolled working
# set at this budget while leaving small-N programs (one chunk) with the
# exact unchunked computation.  The P axis is never sharded (the node
# axis is the mesh axis — parallel/mesh.py), so dynamic-slicing it is
# GSPMD-safe and rolls on axis 0 still lower to collective-permutes.
_CIRCULANT_CHUNK_BYTES = 256 * 1024 * 1024


def _p_chunk_len(n: int, p: int, itemsize: int, floor: int = 4) -> int:
    """Chunk length along P so one [N, chunk] rolled copy stays in budget.

    The default budget floor is the f32 itemsize even for bf16 inputs:
    every circulant kernel accumulates its chunk in float32 (distance
    reduces, weighted sums), and XLA materializes the per-copy f32 upcast
    of a rolled *float* operand — sizing by itemsize=2 would double the
    chunk and hand back the OOM headroom the 256-node north-star run
    depends on.

    Compressed-exchange callers pass ``floor=1``: the rolled copies of an
    int8 payload stay int8 (the dequantizing convert feeds straight into
    the subtract/FMA chain — there is no standalone f32 copy per roll), so
    sizing the exchange chunk by the ≥4-byte float assumption would cut
    the chunk 4x and quadruple the ppermute count for no memory benefit.

    Param-axis sharding (parallel/mesh.py ``param_axis_scope``): under an
    active param-sharded trace scope the budget is SHARD-LOCAL — a
    [N, chunk] rolled copy is resident at chunk/shards columns per
    device, so the admissible chunk scales UP by the shard count.  That
    keeps programs the sharded budget can hold entirely UNCHUNKED, which
    matters more than it reads: a chunk loop's traced-start
    dynamic-slices on the column axis cannot be proven shard-aligned by
    GSPMD, so any chunking under a sharded P degrades to column
    all-gathers (MUR1300's subject).  Programs still too large for the
    scaled budget keep the loop with chunks aligned to whole shard-local
    widths — documented degradation; add shards (or use the dense Gram
    rules) instead.  ``p`` values the shard count does not divide fall
    back to the unsharded accounting via ``active_param_shards(p)``.
    """
    from murmura_tpu.parallel.mesh import active_param_shards

    shards = active_param_shards(p)
    cap = _CIRCULANT_CHUNK_BYTES // max(1, n * max(itemsize, floor))
    chunk = max(1, min(p, cap * shards))
    if shards > 1 and chunk < p:
        # Align the (rare) still-chunked case to whole shard-local
        # widths: nchunks = ceil(p/chunk) grows until it divides the
        # shard count's column grid (bounded scan, trace-time only).
        p_local = p // shards
        chunk = max(p_local, (chunk // p_local) * p_local)
    return chunk


def _p_chunked_accumulate(arrays, chunk_fn, acc_init, p: int, chunk: int):
    """Reduce ``chunk_fn`` over [*, c]-slices of ``arrays`` along axis 1.

    Runs floor(p/chunk) full chunks under a fori_loop (one buffer of
    rolled temps live at a time; the carry is the small accumulator) and
    one statically-shaped tail outside it, so no padding of P is needed.
    """
    nfull = p // chunk

    def body(i, acc):
        cs = [
            jax.lax.dynamic_slice(a, (0, i * chunk), (a.shape[0], chunk))
            for a in arrays
        ]
        return acc + chunk_fn(*cs)

    acc = acc_init
    if nfull:
        acc = jax.lax.fori_loop(0, nfull, body, acc)
    if p - nfull * chunk:
        acc = acc + chunk_fn(*[a[:, nfull * chunk :] for a in arrays])
    return acc


def _p_chunked_map(arrays, chunk_fn, out_dtype, p: int, chunk: int):
    """Assemble ``chunk_fn`` over [*, c]-slices of ``arrays`` into [N, p].

    The map-flavored sibling of :func:`_p_chunked_accumulate`: full chunks
    run under a fori_loop whose carry is the output buffer (XLA aliases
    while-loop carries in place, so the only full-size array is the output
    itself), and the remainder is a statically-shaped tail update.

    A statically-unrolled formulation (chunks barrier-chained, output via
    one concatenate) was measured WORSE on the 256-node program: XLA's
    buffer assignment kept every chunk's slice + rolled temps in distinct
    live allocations (40.4 GB vs this formulation's 17.2 GB).  The while
    carry costs {0,1}-layout conversion copies at the loop boundary, but
    that is the cheaper failure mode.  On a single device, very large
    N*P circulant programs should prefer the dense allgather rules
    anyway — see the geometric-median Gram path and PERFORMANCE.md.
    """
    n = arrays[0].shape[0]
    nfull = p // chunk

    def body(i, out):
        cs = [
            jax.lax.dynamic_slice(a, (0, i * chunk), (a.shape[0], chunk))
            for a in arrays
        ]
        return jax.lax.dynamic_update_slice(
            out, chunk_fn(*cs).astype(out_dtype), (0, i * chunk)
        )

    out = jnp.zeros((n, p), out_dtype)
    if nfull:
        out = jax.lax.fori_loop(0, nfull, body, out)
    if p - nfull * chunk:
        tail = nfull * chunk
        out = jax.lax.dynamic_update_slice(
            out,
            chunk_fn(*[a[:, tail:] for a in arrays]).astype(out_dtype),
            (0, tail),
        )
    return out


def _quantized_pad_own(own, p_pad: int) -> jnp.ndarray:
    """Float own-side operand padded (with exact zeros) to the payload's
    block-padded width — the int8 codec's zero padding dequantizes to
    exact zeros, so both sides' padded columns are inert."""
    own32 = own.astype(jnp.float32)
    if own32.shape[1] == p_pad:
        return own32
    return jnp.pad(own32, ((0, 0), (0, p_pad - own32.shape[1])))


def _quantized_circulant_d2(own, qb: Int8Blocks, offsets) -> jnp.ndarray:
    """[k, N] squared neighbor distances over a compressed broadcast.

    Each roll moves the int8 payload + the [*, C] scale rows (boundary
    ppermutes of the COMPRESSED representation on a sharded node axis —
    MUR700); dequantization fuses into the subtract/square/reduce chain,
    so HBM serves int8 too.  Chunking runs in whole quant blocks so the
    scales slice consistently with the payload, sized with ``floor=1``
    (the compressed-itemsize rationale on :func:`_p_chunk_len`).
    """
    n = qb.num_nodes
    blk, nblocks, p_pad = qb.block, qb.num_blocks, qb.padded_p
    own_is_q = isinstance(own, Int8Blocks)
    own_f = None if own_is_q else _quantized_pad_own(own, p_pad)

    def chunk_d2(b0, nb):
        qc = qb.slice_blocks(b0, nb)
        if own_is_q:
            oc = own.slice_blocks(b0, nb).dequantize_f32()
        else:
            oc = jax.lax.dynamic_slice(own_f, (0, b0 * blk), (n, nb * blk))
        return jnp.stack(
            [
                jnp.sum(
                    jnp.square(oc - qc.roll(-o).dequantize_f32()), axis=-1
                )
                for o in offsets
            ]
        )

    bpc = max(1, _p_chunk_len(n, p_pad, 1, floor=1) // blk)
    if bpc >= nblocks:
        return chunk_d2(0, nblocks)
    nfull = nblocks // bpc

    def body(i, acc):
        return acc + chunk_d2(i * bpc, bpc)

    acc = jax.lax.fori_loop(
        0, nfull, body, jnp.zeros((len(offsets), n), jnp.float32)
    )
    if nblocks - nfull * bpc:
        acc = acc + chunk_d2(nfull * bpc, nblocks - nfull * bpc)
    return acc


def _quantized_circulant_weighted_sum(
    qb: Int8Blocks, w_k: jnp.ndarray, offsets, out_dtype
) -> jnp.ndarray:
    """Compressed twin of :func:`circulant_weighted_sum`: the rolled
    operands are the int8 payload + scales, the f32 weight products
    accumulate per chunk, and only the [N, p] output materializes in
    ``out_dtype``."""
    n = qb.num_nodes
    blk, nblocks, p_pad = qb.block, qb.num_blocks, qb.padded_p
    out_dtype = qb.out_dtype if out_dtype is None else out_dtype

    def chunk_sum(b0, nb):
        qc = qb.slice_blocks(b0, nb)
        acc = jnp.zeros((n, nb * blk), jnp.float32)
        for idx, o in enumerate(offsets):
            acc = acc + w_k[idx][:, None] * qc.roll(-o).dequantize_f32()
        return acc

    bpc = max(1, _p_chunk_len(n, p_pad, 1, floor=1) // blk)
    if bpc >= nblocks:
        return chunk_sum(0, nblocks)[:, : qb.p].astype(out_dtype)
    nfull = nblocks // bpc
    out = jnp.zeros((n, p_pad), out_dtype)

    def body(i, out):
        return jax.lax.dynamic_update_slice(
            out, chunk_sum(i * bpc, bpc).astype(out_dtype), (0, i * bpc * blk)
        )

    out = jax.lax.fori_loop(0, nfull, body, out)
    if nblocks - nfull * bpc:
        out = jax.lax.dynamic_update_slice(
            out,
            chunk_sum(nfull * bpc, nblocks - nfull * bpc).astype(out_dtype),
            (0, nfull * bpc * blk),
        )
    return out[:, : qb.p]


def _quantized_circulant_candidate_map(
    own, qb: Int8Blocks, offsets, fn
) -> jnp.ndarray:
    """Compressed twin of :func:`circulant_candidate_map`: the candidate
    stack is assembled from rolled int8 payloads dequantized per chunk
    (the stack itself is f32 in registers/VMEM — only the reads are
    compressed), with the budget scaled by the stack height."""
    n = qb.num_nodes
    blk, nblocks, p_pad = qb.block, qb.num_blocks, qb.padded_p
    own_f = _quantized_pad_own(own, p_pad)
    out_dtype = qb.out_dtype

    def chunk_apply(b0, nb):
        qc = qb.slice_blocks(b0, nb)
        oc = jax.lax.dynamic_slice(own_f, (0, b0 * blk), (n, nb * blk))
        return fn(
            jnp.stack(
                [oc] + [qc.roll(-o).dequantize_f32() for o in offsets]
            )
        )

    # The f32 stack dominates the working set, so size by the float
    # accounting (floor=4) scaled by the stack height, in whole blocks.
    stack = len(offsets) + 1
    bpc = max(1, _p_chunk_len(n * stack, p_pad, 4) // blk)
    if bpc >= nblocks:
        return chunk_apply(0, nblocks)[:, : qb.p].astype(out_dtype)
    nfull = nblocks // bpc
    out = jnp.zeros((n, p_pad), out_dtype)

    def body(i, out):
        return jax.lax.dynamic_update_slice(
            out,
            chunk_apply(i * bpc, bpc).astype(out_dtype),
            (0, i * bpc * blk),
        )

    out = jax.lax.fori_loop(0, nfull, body, out)
    if nblocks - nfull * bpc:
        out = jax.lax.dynamic_update_slice(
            out,
            chunk_apply(nfull * bpc, nblocks - nfull * bpc).astype(out_dtype),
            (0, nfull * bpc * blk),
        )
    return out[:, : qb.p]


def circulant_neighbor_distances(
    own: jnp.ndarray, bcast: jnp.ndarray, offsets, pallas: bool = False
) -> jnp.ndarray:
    """[k, N] distances D[o, i] = ||own_i - bcast[(i+o) % N]|| via circular
    shifts — the O(degree) counterpart of the [N, N] pairwise matrix for
    circulant graphs (tpu.exchange: ppermute). Each roll lowers to
    boundary-slice collective-permutes on a sharded node axis, and the
    direct elementwise norm avoids the Gram-identity cancellation the dense
    path has to center against.  The squared-diff reduction runs in f32
    regardless of input dtype (XLA fuses the upcast into the reduce, no
    extra HBM pass): a bf16 accumulation over millions of terms would
    quantize the small distances the Byzantine selections rank on, same
    hazard :func:`pairwise_l2_distances` guards against.

    Large N*P runs P-chunked (see ``_CIRCULANT_CHUNK_BYTES``): the sum over
    P is associative, so partial sums over chunks accumulate in the same
    f32 precision and only the final sqrt changes position — identical up
    to f32 summation order.

    Compressed exchange (``bcast`` — or both operands — an
    :class:`Int8Blocks` payload) dispatches to the quantized twin so the
    rolls move the compressed representation (MUR700); ``pallas=True``
    routes plain float operands through the fused Pallas streaming kernel
    (ops/pallas_agg.py) when the shapes fit its envelope.
    """
    if isinstance(bcast, Int8Blocks):
        # own may be float (node-local, uncompressed) or Int8Blocks (the
        # krum delta-distance call passes the payload on both sides).
        return jnp.sqrt(_quantized_circulant_d2(own, bcast, offsets))
    if isinstance(own, Int8Blocks):
        raise TypeError(
            "circulant_neighbor_distances got a compressed own-side "
            "operand with an uncompressed broadcast — the quantized twin "
            "needs the rolled (broadcast) side compressed; quantize both "
            "or neither"
        )
    if pallas:
        from murmura_tpu.ops import pallas_agg

        d2p = pallas_agg.circulant_sq_distances(own, bcast, offsets)
        if d2p is not None:
            return jnp.sqrt(jnp.maximum(d2p, 0.0))
    n, p = bcast.shape

    def chunk_d2(oc, bc):
        return jnp.stack(
            [
                jnp.sum(
                    jnp.square(
                        (oc - jnp.roll(bc, -o, axis=0)).astype(jnp.float32)
                    ),
                    axis=-1,
                )
                for o in offsets
            ]
        )

    chunk = _p_chunk_len(n, p, bcast.dtype.itemsize)
    if chunk >= p:
        return jnp.sqrt(chunk_d2(own, bcast))
    d2 = _p_chunked_accumulate(
        [own, bcast],
        chunk_d2,
        jnp.zeros((len(offsets), n), jnp.float32),
        p,
        chunk,
    )
    return jnp.sqrt(d2)


def circulant_weighted_sum(
    bcast: jnp.ndarray, w_k: jnp.ndarray, offsets, out_dtype=None
) -> jnp.ndarray:
    """[N, P] per-offset weighted neighbor sum: sum_o w_k[o, i] * bcast[(i+o) % N].

    The shared memory-safe kernel behind the circulant masked mean, the
    fedavg roll path, evidential trust's weighted blend and the Weiszfeld
    recursion.  Large N*P runs P-chunked with the output assembled via
    dynamic_update_slice on the fori_loop carry (XLA aliases while-loop
    carries in place, so the only full-size buffers are ``bcast`` and the
    output).

    ``out_dtype`` narrows the OUTPUT buffer only — per-chunk accumulation
    still runs at the promoted precision (f32 for f32 weights over bf16
    states) and the cast happens once per chunk.  Callers that iterate on
    the result (geometric median) pass the resident param dtype here so a
    bf16 256-node program does not materialize f32 [N, P] buffers — the
    6.3 GB-per-copy OOM class.

    A compressed broadcast (:class:`Int8Blocks`) dispatches to the
    quantized twin: the rolls move int8 + scales (MUR700).
    """
    if isinstance(bcast, Int8Blocks):
        return _quantized_circulant_weighted_sum(bcast, w_k, offsets, out_dtype)
    n, p = bcast.shape
    acc_dtype = jnp.result_type(bcast.dtype, w_k.dtype)
    if out_dtype is None:
        out_dtype = acc_dtype

    def chunk_sum(bc):
        acc = jnp.zeros(bc.shape, acc_dtype)
        for idx, o in enumerate(offsets):
            acc = acc + w_k[idx][:, None] * jnp.roll(bc, -o, axis=0)
        return acc

    chunk = _p_chunk_len(n, p, bcast.dtype.itemsize)
    if chunk >= p:
        return chunk_sum(bcast).astype(out_dtype)
    return _p_chunked_map([bcast], chunk_sum, out_dtype, p, chunk)


def candidate_chunk_dispatch(own, bcast, chunk_apply, stack_height: int):
    """Shared P-chunking dispatch for candidate-stack reductions.

    ``chunk_apply(own_chunk, bcast_chunk) -> [N, c]`` must be
    coordinate-wise along the last axis.  The budget is scaled by
    ``stack_height`` (how many [N, c]-sized copies the stack materializes
    per chunk); small N*P runs the exact single-chunk computation.  Both
    the circulant and the dense candidate maps dispatch through here so
    the OOM-budget logic lives in one place.
    """
    n, p = bcast.shape
    chunk = _p_chunk_len(n * stack_height, p, bcast.dtype.itemsize)
    if chunk >= p:
        return chunk_apply(own, bcast)
    out_dtype = jax.eval_shape(
        chunk_apply,
        jax.ShapeDtypeStruct((n, 1), own.dtype),
        jax.ShapeDtypeStruct((n, 1), bcast.dtype),
    ).dtype
    return _p_chunked_map([own, bcast], chunk_apply, out_dtype, p, chunk)


def circulant_candidate_map(own, bcast, offsets, fn) -> jnp.ndarray:
    """Apply a coordinate-wise reduction over the circulant candidate stack.

    ``fn`` maps the stacked candidates ``[m, N, c]`` (own + one rolled
    broadcast per offset, any chunk width c) to ``[N, c]`` and must be
    coordinate-wise along the last axis (sorts/means over the candidate
    axis are; anything mixing P columns is not).  Large N*P runs P-chunked
    with the budget scaled by the stack height m, so the median and
    trimmed-mean circulant paths never materialize the full [m, N, P]
    tensor (the same OOM class ``_CIRCULANT_CHUNK_BYTES`` exists for).

    A compressed broadcast (:class:`Int8Blocks`) dispatches to the
    quantized twin: the stack is assembled from rolled int8 payloads.
    """
    if isinstance(bcast, Int8Blocks):
        return _quantized_circulant_candidate_map(own, bcast, offsets, fn)

    def chunk_apply(oc, bc):
        return fn(jnp.stack([oc] + [jnp.roll(bc, -o, axis=0) for o in offsets]))

    return candidate_chunk_dispatch(own, bcast, chunk_apply, len(offsets) + 1)


def circulant_masked_mean(
    bcast: jnp.ndarray, accept_k: jnp.ndarray, offsets
) -> jnp.ndarray:
    """Weighted neighbor mean from per-offset acceptance.

    Args:
        bcast: [N, P] broadcast states.
        accept_k: [k, N] accept weight for node i's neighbor at offset o.
    """
    # Normalize the small [k, N] weights up front (full f32 precision) and
    # pin out_dtype to the resident param dtype: per-chunk accumulation
    # still runs at the promoted f32 precision inside the shared kernel,
    # but no full-size f32 [N, P] accumulator or quotient is ever
    # materialized (the OOM class out_dtype exists for) and the exchanged
    # tensor never upcasts (MUR201).
    cnt = accept_k.sum(axis=0)
    w_norm = accept_k / jnp.maximum(cnt, 1e-12)[None, :]
    return circulant_weighted_sum(bcast, w_norm, offsets, out_dtype=bcast.dtype)


def circulant_in_degree(edge_k: jnp.ndarray, offsets) -> jnp.ndarray:
    """[N] sender in-degree under a [k, N] edge mask, via rolls only.

    ``edge_k[j, i]`` says receiver ``i`` reads sender ``(i + offsets[j])
    % N``, so sender ``s`` is read by receiver ``(s - o) % N`` — each term
    is one roll of a [N] row, which lowers to boundary ppermutes on a
    sharded node axis (the tap/degree helper of the sparse exchange mode;
    keeps MUR400/MUR601 inventories ppermute-only).
    """
    return sum(
        jnp.roll(edge_k[j].astype(jnp.float32), o)
        for j, o in enumerate(offsets)
    )


def candidate_indices(adj: jnp.ndarray, m_cap: int):
    """Per-node candidate ordering shared by the candidate-block rules.

    Rank self first (2), neighbors next (1), non-candidates last; argsort
    is stable so neighbor indices come out ascending and truncation at
    ``m_cap`` is deterministic (krum.py candidate blocks; robust_stats.py).

    Returns:
        (cand_idx [N, m], valid [N, m] bool).
    """
    n = adj.shape[0]
    rank = adj + 2.0 * jnp.eye(n, dtype=adj.dtype)
    cand_idx = jnp.argsort(-rank, axis=1)[:, :m_cap]
    valid = jnp.take_along_axis(rank, cand_idx, axis=1) > 0.0
    return cand_idx, valid


def masked_neighbor_mean(bcast: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted neighbor mean per node: (W @ bcast) / row-sum, safe on empty rows.

    Dtype-stable by contract (MUR201): with bf16 resident params the matmul
    runs bf16-in/f32-accumulate (the MXU-native mode — f32 *operands* would
    double the memory-bound matmul's HBM reads) and the mean is cast back to
    the resident dtype, so the exchanged [N, P] tensor never upcasts.  Row
    totals are summed (in f32) from the SAME cast weights the matmul uses:
    normalizing a bf16-quantized numerator by the unquantized f32 total
    would scale every row by sum(w)/sum(bf16(w)) != 1 — a systematic bias
    applied to the parameters each round.
    """
    w = weights.astype(bcast.dtype)
    totals = w.sum(axis=1, keepdims=True, dtype=jnp.float32)
    acc = jnp.dot(w, bcast, preferred_element_type=jnp.float32)
    return (acc / jnp.maximum(totals, 1e-12)).astype(bcast.dtype)


def blend_with_own(
    own: jnp.ndarray,
    neighbor_avg: jnp.ndarray,
    has_neighbors: jnp.ndarray,
    alpha: float,
) -> jnp.ndarray:
    """alpha*own + (1-alpha)*neighbor_avg where any neighbor was accepted,
    else own (the BALANCE/Sketchguard/UBAR output form — balance.py:140-175)."""
    blended = alpha * own + (1.0 - alpha) * neighbor_avg
    return jnp.where(has_neighbors[:, None], blended, own)


def rank_mask(values: jnp.ndarray, valid: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of the k smallest valid entries per row.

    Args:
        values: [..., M] scores (smaller = better).
        valid: [..., M] candidate mask.
        k: [...] per-row number to keep.
    """
    masked = jnp.where(valid, values, jnp.inf)
    order = jnp.argsort(masked, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    return valid & (ranks < k[..., None])


def self_probe_metrics(
    own: jnp.ndarray, ctx: AggContext, metric_fn: Callable
) -> Dict[str, jnp.ndarray]:
    """Evaluate each node's own params on its own probe batch (diagonal of the
    cross-eval), e.g. UBAR's own-loss baseline (ubar.py:174-176)."""

    def one(flat_i, x_i, y_i, m_i):
        params = ctx.unravel(flat_i)
        outputs = ctx.apply_fn(params, x_i, None, False)
        return metric_fn(outputs, y_i, m_i)

    n = own.shape[0]
    # A leading probe dim of 1 means "one shared evaluator batch" (the ZMQ
    # LocalNode mini-network) — broadcast it across the node axis.
    px, py, pm = (
        jnp.broadcast_to(a, (n,) + a.shape[1:]) if a.shape[0] == 1 and n != 1 else a
        for a in (ctx.probe_x, ctx.probe_y, ctx.probe_mask)
    )
    return jax.vmap(one)(own, px, py, pm)
