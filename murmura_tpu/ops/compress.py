"""Compressed neighbor exchange: int8 block quantization and top-k
sparsification with error feedback (docs/PERFORMANCE.md).

The round's exchanged tensor — the post-attack broadcast [N, P] — is the
dominant mover of bytes once the model is non-trivial: every edge of the
graph reads a full [P] row per round.  Quantized decentralized SGD
(PAPERS.md: arXiv:1910.12308) shows that compressing the exchanged
representation to int8 (or a top-k sparse slice) converges like
full-precision as long as the quantization residual is fed back into the
next round's transmission (error feedback), and it composes multiplicatively
with the degree-O(log N) sparse exponential graphs (docs/SCALING.md): fewer
edges x fewer bytes per edge.

Two codecs:

``int8`` — per-block symmetric scale.  The [P] row is split into
``block``-sized chunks; each chunk is quantized as ``q = round(x / scale)``
with ``scale = max|x| / 127`` per chunk.  Symmetric (no zero-point) by
design: exact zeros stay exact zeros through the codec, which is what the
padded-tail algebra and the masked-edge semantics (0-weighted neighbors
contribute nothing) rely on; the asymmetry loss is absorbed by error
feedback.  The compressed representation is ``(q int8 [N, P], scale f32
[N, P/block])`` — 8 bits + 32/block bits per element instead of 16/32.

``topk`` — sparse delta against a carried reference estimate.  Raw
parameter states are dense (top-k of a *state* would zero most of the
model); what is sparse is the round-over-round *change*.  The round
program carries a reference estimate ``x̂`` [N, P] in ``agg_state`` —
initialized from the (protocol-known) initial broadcast and updated to
exactly what receivers reconstruct — and transmits the k largest-magnitude
coordinates of ``x - x̂`` as (values f32, indices int32) pairs; receivers
apply the sparse delta to their copy of ``x̂``.  This is the CHOCO-SGD
memory-vector construction; with error feedback the untransmitted mass is
retried next round instead of lost.

The in-jit wiring lives in ``core/rounds.py`` (the ``compression=`` spec of
``build_round_program``); the int8 payload additionally rides the circulant
exchange kernels as an :class:`Int8Blocks` pytree so the ppermutes that
realize ``jnp.roll`` on a sharded node axis move the int8 payload, not a
dequantized float tensor (``murmura check --ir`` MUR700).
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Reserved round-program-level agg_state keys (the DMTT_STATE_KEYS pattern,
# core/rounds.py): carried by the round step but never handed to the
# aggregation rule's state dict.
RESIDUAL_KEY = "compress_residual"
REF_KEY = "compress_ref"
COMPRESS_STATE_KEYS = (RESIDUAL_KEY, REF_KEY)


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Trace-time compressed-exchange spec (config: ``compression:``).

    Static under trace — the codec choice and its shape parameters are
    program structure; everything data-dependent (scales, residuals, the
    reference estimate) is traced values, so rounds never recompile
    (MUR701).
    """

    algorithm: str  # "int8" | "topk"
    block: int = 256
    topk_ratio: float = 0.05
    error_feedback: bool = False

    def __post_init__(self):
        if self.algorithm not in ("int8", "topk"):
            raise ValueError(
                f"compression algorithm must be 'int8' or 'topk', got "
                f"{self.algorithm!r}"
            )
        if self.block < 1:
            raise ValueError(f"compression block must be >= 1, got {self.block}")
        if not 0.0 < self.topk_ratio <= 1.0:
            raise ValueError(
                f"topk_ratio must be in (0, 1], got {self.topk_ratio}"
            )

    def topk_k(self, p: int) -> int:
        """Static number of transmitted coordinates for a [P] row."""
        return max(1, min(p, int(round(self.topk_ratio * p))))

    def state_keys(self) -> Tuple[str, ...]:
        """agg_state keys this spec carries across rounds."""
        keys = []
        if self.error_feedback:
            keys.append(RESIDUAL_KEY)
        if self.algorithm == "topk":
            keys.append(REF_KEY)
        return tuple(keys)

    def payload_bytes(self, p: int, uncompressed_itemsize: int) -> int:
        """Analytic bytes of one node's exchanged representation for a [P]
        row — what actually crosses an edge (core/network.py reports it as
        ``payload_bytes_per_edge``)."""
        if self.algorithm == "int8":
            nblocks = -(-p // self.block)
            return p * 1 + nblocks * 4  # int8 payload + f32 scale per block
        k = self.topk_k(p)
        return k * (4 + 4)  # f32 value + int32 index per coordinate


# ---------------------------------------------------------------------------
# int8 per-block symmetric quantization
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Int8Blocks:
    """The int8 compressed exchange representation as a pytree.

    ``q`` is the int8 payload [N, C*B] (P zero-padded up to whole blocks —
    symmetric quantization maps the zero padding to exact zero codes, so
    padded columns are inert in every consumer); ``scale`` is the per-block
    f32 scale [N, C].  ``p`` is the true parameter length and ``out_dtype``
    the dtype ``dequantize`` restores (the resident param dtype, MUR201).

    The circulant exchange kernels (aggregation/base.py) accept this in
    place of the float broadcast tensor and roll ``q``/``scale`` along the
    node axis *before* dequantizing, so on a sharded node mesh the boundary
    collective-permutes move int8 + the tiny scale rows — never a full-size
    float [*, P] operand (the MUR700 contract).
    """

    def __init__(self, q, scale, block: int, p: int, out_dtype):
        self.q = q
        self.scale = scale
        self.block = int(block)
        self.p = int(p)
        self.out_dtype = jnp.dtype(out_dtype)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), (self.block, self.p, str(self.out_dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        block, p, out_dtype = aux
        q, scale = children
        return cls(q, scale, block, p, out_dtype)

    # -- views --------------------------------------------------------------
    @property
    def dtype(self):
        """The dequantized dtype — lets value-dtype consumers (e.g.
        ``circulant_masked_mean``'s ``out_dtype=bcast.dtype``) treat the
        payload like the float tensor it stands in for."""
        return self.out_dtype

    @property
    def num_nodes(self) -> int:
        return self.q.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.scale.shape[1]

    @property
    def padded_p(self) -> int:
        return self.q.shape[1]

    def roll(self, shift: int) -> "Int8Blocks":  # murmura: traced
        """Roll along the node axis — the circulant neighbor exchange.  On
        a sharded node axis each roll lowers to boundary collective-permutes
        of the int8 payload and the [*, C] scale rows."""
        return Int8Blocks(
            jnp.roll(self.q, shift, axis=0),
            jnp.roll(self.scale, shift, axis=0),
            self.block,
            self.p,
            self.out_dtype,
        )

    def slice_blocks(self, start_block, nblocks: int) -> "Int8Blocks":  # murmura: traced
        """Static-width slice of ``nblocks`` whole quant blocks starting at
        (possibly traced) block index ``start_block`` — the P-chunking hook
        the exchange kernels use (chunk widths are whole blocks, so scales
        slice consistently with the payload)."""
        n = self.num_nodes
        q = jax.lax.dynamic_slice(
            self.q, (0, start_block * self.block), (n, nblocks * self.block)
        )
        s = jax.lax.dynamic_slice(self.scale, (0, start_block), (n, nblocks))
        return Int8Blocks(q, s, self.block, nblocks * self.block, self.out_dtype)

    def dequantize_f32(self) -> jnp.ndarray:  # murmura: traced
        """[N, padded_p] float32 values (the fused-consumer form: XLA folds
        the convert+scale into whatever elementwise chain reads it, so the
        int8 payload is what HBM serves)."""
        n = self.num_nodes
        qf = self.q.astype(jnp.float32).reshape(n, self.num_blocks, self.block)
        return (qf * self.scale[:, :, None]).reshape(n, self.padded_p)

    def dequantize(self) -> jnp.ndarray:  # murmura: traced
        """[N, p] values in ``out_dtype`` (padding stripped) — the
        receiver-side tensor rules that do arbitrary math get."""
        return self.dequantize_f32()[:, : self.p].astype(self.out_dtype)


def quantize_int8(  # murmura: traced
    x: jnp.ndarray, block: int, out_dtype=None
) -> Int8Blocks:
    """Per-block symmetric int8 quantization of a [N, P] tensor.

    ``scale = max|x| / 127`` per ``block``-wide chunk of the parameter
    axis; ``q = round(x / scale)`` clipped to [-127, 127].  All-zero blocks
    quantize to zero codes with zero scale (dequantizing to exact zeros),
    and the zero padding up to whole blocks is likewise exact — no masking
    is ever needed downstream.
    """
    n, p = x.shape
    out_dtype = x.dtype if out_dtype is None else jnp.dtype(out_dtype)
    pad = (-p) % block
    xf = x.astype(jnp.float32)
    # Static shape math: p is x.shape[1] and block is a trace-time int —
    # the name-based taint pass cannot see through the int param.
    if pad:  # murmura: ignore[MUR001]
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
    nblocks = xf.shape[1] // block
    xb = xf.reshape(n, nblocks, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)  # [N, C]
    scale = amax / 127.0
    inv = jnp.where(scale > 0.0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xb * inv[:, :, None]), -127.0, 127.0).astype(
        jnp.int8
    )
    return Int8Blocks(
        q.reshape(n, nblocks * block), scale, block, p, out_dtype
    )


# ---------------------------------------------------------------------------
# top-k sparse delta codec
# ---------------------------------------------------------------------------


def topk_encode(delta: jnp.ndarray, k: int):  # murmura: traced
    """(values f32 [N, k], indices int32 [N, k]) of the k largest-magnitude
    coordinates per row — the transmitted representation."""
    mag = jnp.abs(delta.astype(jnp.float32))
    _, idx = jax.lax.top_k(mag, k)
    idx = idx.astype(jnp.int32)
    values = jnp.take_along_axis(delta.astype(jnp.float32), idx, axis=1)
    return values, idx


def topk_decode(  # murmura: traced
    values: jnp.ndarray, idx: jnp.ndarray, p: int
) -> jnp.ndarray:
    """Dense [N, p] float32 reconstruction of the sparse delta (zeros off
    the transmitted support)."""
    n = values.shape[0]
    rows = jnp.arange(n)[:, None]
    return jnp.zeros((n, p), jnp.float32).at[rows, idx].set(values)


# ---------------------------------------------------------------------------
# The round-step codec: one entry point for core/rounds.py
# ---------------------------------------------------------------------------


def compress_exchange(
    spec: CompressionSpec,
    bcast: jnp.ndarray,
    agg_state,
    quantized_exchange: bool,
):  # murmura: traced
    """Apply the compressed-exchange codec to the round's broadcast.

    Returns ``(exchanged, decoded, state_updates, stats)``:

    - ``exchanged`` is what the aggregation rule receives as its broadcast
      operand — an :class:`Int8Blocks` payload when the rule's exchange
      kernels can move compressed data (``AggregatorDef.quantized_exchange``
      and int8), else the dense ``decoded`` tensor;
    - ``decoded`` is the receiver-side dequantized [N, P] tensor (resident
      dtype) — what every receiver's rule math sees;
    - ``state_updates`` carries the error-feedback residual and/or the
      top-k reference estimate for the next round (``agg_state`` keys in
      :data:`COMPRESS_STATE_KEYS`);
    - ``stats`` are per-node history metrics (``agg_compress_*``).

    Error feedback: the residual ``e`` rides ``agg_state``; the round
    transmits ``Q(bcast + e)`` and carries ``e' = (bcast + e) - Q(bcast +
    e)`` forward, so quantization error telescopes instead of accumulating
    (tests/test_compression.py pins the telescoping identity).
    """
    state_updates = {}
    outgoing = bcast.astype(jnp.float32)
    if spec.error_feedback:
        outgoing = outgoing + agg_state[RESIDUAL_KEY].astype(jnp.float32)

    if spec.algorithm == "int8":
        qb = quantize_int8(outgoing, spec.block, out_dtype=bcast.dtype)
        decoded = qb.dequantize()
        exchanged = qb if quantized_exchange else decoded
    else:  # topk: sparse delta against the carried reference estimate
        ref = agg_state[REF_KEY].astype(jnp.float32)
        values, idx = topk_encode(outgoing - ref, spec.topk_k(bcast.shape[1]))
        decoded32 = ref + topk_decode(values, idx, bcast.shape[1])
        decoded = decoded32.astype(bcast.dtype)
        # The reference advances to exactly what receivers reconstructed —
        # stored in the resident dtype so both ends of next round's delta
        # agree bit-for-bit with what the rules actually consumed.
        state_updates[REF_KEY] = decoded
        exchanged = decoded

    err = outgoing - decoded.astype(jnp.float32)
    if spec.error_feedback:
        state_updates[RESIDUAL_KEY] = err.astype(
            agg_state[RESIDUAL_KEY].dtype
        )
    stats = {
        # Per-node L2 of what this round's codec did NOT deliver (before
        # feedback): the drift bound the error-feedback property test rides.
        "compress_error": jnp.sqrt(jnp.sum(err * err, axis=1)),
    }
    if spec.error_feedback:
        stats["compress_residual_norm"] = jnp.sqrt(
            jnp.sum(
                state_updates[RESIDUAL_KEY].astype(jnp.float32) ** 2, axis=1
            )
        )
    return exchanged, decoded, state_updates, stats


def init_compress_state(
    spec: Optional[CompressionSpec], init_flat, dtype
):
    """Initial ``agg_state`` entries for a compressed program.

    ``init_flat`` is the raveled [N, P] initial broadcast — the
    protocol-known starting point the top-k reference estimate adopts (a
    real deployment broadcasts full states once at setup), killing the
    cold-start round where a zero reference would make every delta dense.
    """
    import numpy as np

    if spec is None:
        return {}
    out = {}
    if spec.error_feedback:
        out[RESIDUAL_KEY] = np.zeros(init_flat.shape, dtype)
    if spec.algorithm == "topk":
        out[REF_KEY] = np.asarray(init_flat, dtype)
    return out


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="compression",
    module="murmura_tpu.ops.compress",
    state_keys_group="COMPRESS_STATE_KEYS",
    stage="murmura.compress",
    verdicts={
        # The codec quantizes whatever broadcast the attack produced —
        # the adaptation loop observes acceptance, not payload bytes.
        "adaptive": composes(),
    },
)
