"""Causal softmax attention over one sequence, for every decoder of the repo:
``o[h] = softmax(scale_h q[h] k[g(h)]^T, causal) v[g(h)]``, ``Hq`` query
heads over ``Hkv`` key/value heads, ``g(h) = h // (Hq / Hkv)``.

The products take operands in the compute ``dtype`` and accumulate in
float32; the scores are scaled in float32 after their product (a head's
``scale_h``: ``1/sqrt(d)`` for a plain softmax, a learned temperature for
ZAYA1's); the softmax and its statistics are float32, and the
probabilities are cast to the compute dtype for their product with the
values.  The result is float32, and so are the gradients of ``q``, ``k``,
``v`` and ``scale`` (each in its operand's own dtype: a float32 operand
takes a float32 gradient that was never rounded to the compute dtype).

- *On a TPU*, where the sequence tiles into blocks (``kernel_block``): one
  Pallas flash kernel each way, scores and softmax in VMEM, only the
  (query block, key block) pairs on or below the diagonal visited (a grid
  over the pairs themselves: a block above it is neither loaded nor
  multiplied).  Forward (``causal_attention_fwd``): a query block meets its
  key blocks in order with a running maximum and sum, and leaves the
  result and the log-sum-exp of its rows.  Backward
  (``causal_attention_bwd``): a key block meets the query blocks from the
  diagonal on; its key and value gradients accumulate in its output
  blocks, the query gradient of the whole head in a float32 block that
  stays in VMEM for the head, so that each pair's probabilities are
  computed once.  The key and value gradients leave it a query head at a
  time and are summed over a key/value head's group outside.
- *Elsewhere* (the CPU tests), or at a sequence the kernel cannot tile, the
  same sums in blocks of ``ATTENTION_BLOCK`` queries that meet the keys up
  to their own end, in ``jnp``.  ``use_pallas=True`` off a TPU runs the
  kernels interpreted.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ATTENTION_BLOCK = 512  # queries a block of the jnp path
# The names of the forward's residuals that the backward pass needs beside
# q, k and v: a layer recomputed under ``KEEP_RESIDUALS`` keeps them from
# its forward pass and does not run the forward again.
RESULT, LOG_SUM_EXP = "causal_attention.result", "causal_attention.lse"
KEEP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(RESULT, LOG_SUM_EXP)
_NT = (((1,), (1,)), ((), ()))  # a [m, d] . b [n, d]^T
_TN = (((0,), (0,)), ((), ()))  # a [m, n]^T . b [m, d]
# Scores outside the causal triangle: finite, so that no exp meets inf - inf.
_MASKED = -0.7 * float(np.finfo(np.float32).max)
_LANES, _SUBLANES = 128, 8
# The backward kernel keeps a head's float32 query gradient in VMEM (twice:
# the pipeline's two buffers); a sequence whose column would pass this
# takes the jnp path.
_RESIDENT_BYTES = 24 * 2**20
_VMEM_LIMIT_BYTES = 64 * 2**20


def kernel_block(t: int, dqk: int):
    """The kernel's block of queries and of keys for a sequence of ``t``
    positions and heads of ``dqk``: the largest of 1024, 512, 256 and 128
    that tiles ``t`` into two blocks or more (one block has no pair above
    the diagonal to skip); None where none does, or where a head's query
    gradient would not stay in VMEM.  (On a v5e at 4,096 positions a pair
    of 1024 blocks costs less than its four of 512, though a diagonal
    block multiplies half its scores for nothing: PERF.md §6.)"""
    lanes = -(-dqk // _LANES) * _LANES
    if 2 * t * lanes * 4 > _RESIDENT_BYTES:
        return None
    return next((b for b in (1024, 512, 256, 128) if t % b == 0 and t >= 2 * b), None)


def _pairs(n: int, by_query: bool):
    """(query block, key block) of every pair on or below the diagonal of
    ``n`` blocks: a query block's keys in order (``by_query``), else a key
    block's queries in order."""
    if by_query:
        pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    else:
        pairs = [(i, j) for j in range(n) for i in range(j, n)]
    qi, kj = (np.asarray(side, np.int32) for side in zip(*pairs))
    return jnp.asarray(qi), jnp.asarray(kj)


# A block's scores as the kernels mask them: below the diagonal none; on
# it a key after its query, queries on rows (forward) or keys (backward).
def _unmasked(s):
    return s


def _places(s):
    return (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))


def _queries_on_rows(s):
    rows, cols = _places(s)
    return jnp.where(cols <= rows, s, _MASKED)


def _keys_on_rows(s):
    rows, cols = _places(s)
    return jnp.where(rows <= cols, s, _MASKED)


# ---- the kernels ------------------------------------------------------------


def _forward_kernel(qi_ref, kj_ref, scale_ref, q_ref, k_ref, v_ref,
                    o_ref, lse_ref, m_ref, l_ref, acc_ref):
    h, p = pl.program_id(0), pl.program_id(1)
    i, j = qi_ref[p], kj_ref[p]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def meet(mask):
        s = mask(jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                     preferred_element_type=jnp.float32) * scale_ref[h])
        m_prev = m_ref[...]  # [block, lanes], every lane the row's
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        e = jnp.exp(s - m_next[:, :1])
        l_ref[...] = alpha * l_ref[...] + e.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
            e.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    pl.when(j < i)(lambda: meet(_unmasked))

    @pl.when(j == i)  # the diagonal block is a query block's last
    def _():
        meet(_queries_on_rows)
        l = l_ref[...]
        o_ref[...] = acc_ref[...] / l[:, :1]
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _backward_kernel(qi_ref, kj_ref, scale_ref, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, di_ref, dq_ref, dk_ref, dv_ref, *, block):
    h, p = pl.program_id(0), pl.program_id(1)
    i, j = qi_ref[p], kj_ref[p]

    @pl.when(p == 0)  # the head's first pair: its query gradient starts
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(i == j)  # a key block's first pair
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def meet(mask):
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        # Transposed: keys on rows, so that a query's statistics lie along
        # lanes and the key side's products need no transpose.
        s = mask(jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
                 * scale_ref[h])
        prob = jnp.exp(s - lse_ref[:1, :])
        dv_ref[...] += jax.lax.dot(prob.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dprob = jax.lax.dot_general(v_ref[...], do, _NT, preferred_element_type=jnp.float32)
        ds = (prob * (dprob - di_ref[:1, :])).astype(q.dtype)
        dk_ref[...] += jax.lax.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        dq_ref[rows, :] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

    pl.when(i > j)(lambda: meet(_unmasked))
    pl.when(i == j)(lambda: meet(_keys_on_rows))


def _forward_pallas(q, k, v, scale, *, block, interpret):
    hq, t, dqk = q.shape
    dv, group = v.shape[-1], hq // k.shape[0]
    qi, kj = _pairs(t // block, by_query=True)
    at_query = lambda h, p, qi, kj, sc: (h, qi[p], 0)
    at_key = lambda h, p, qi, kj, sc: (h // group, kj[p], 0)
    o, lse = pl.pallas_call(
        _forward_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(hq, qi.shape[0]),
            in_specs=[pl.BlockSpec((None, block, dqk), at_query),
                      pl.BlockSpec((None, block, dqk), at_key),
                      pl.BlockSpec((None, block, dv), at_key)],
            out_specs=[pl.BlockSpec((None, block, dv), at_query),
                       pl.BlockSpec((None, block, _LANES), at_query)],
            scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((hq, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((hq, t, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="causal_attention_fwd",
        interpret=interpret,
    )(qi, kj, scale, q, k, v)
    return o, lse[..., 0]


def _backward_pallas(q, k, v, scale, lse, do, di, *, block, interpret):
    hq, t, dqk = q.shape
    dv, group = v.shape[-1], hq // k.shape[0]
    qi, kj = _pairs(t // block, by_query=False)
    at_query = lambda h, p, qi, kj, sc: (h, qi[p], 0)
    at_key = lambda h, p, qi, kj, sc: (h // group, kj[p], 0)
    at_own_key = lambda h, p, qi, kj, sc: (h, kj[p], 0)
    along = lambda h, p, qi, kj, sc: (h, 0, qi[p])
    rows = lambda a: jnp.broadcast_to(a[:, None, :], (hq, _SUBLANES, t))
    return pl.pallas_call(
        partial(_backward_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(hq, qi.shape[0]),
            in_specs=[pl.BlockSpec((None, block, dqk), at_query),
                      pl.BlockSpec((None, block, dqk), at_key),
                      pl.BlockSpec((None, block, dv), at_key),
                      pl.BlockSpec((None, block, dv), at_query),
                      pl.BlockSpec((None, _SUBLANES, block), along),
                      pl.BlockSpec((None, _SUBLANES, block), along)],
            out_specs=[pl.BlockSpec((None, t, dqk), lambda h, p, qi, kj, sc: (h, 0, 0)),
                       pl.BlockSpec((None, block, dqk), at_own_key),
                       pl.BlockSpec((None, block, dv), at_own_key)],
        ),
        out_shape=[jax.ShapeDtypeStruct((hq, t, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((hq, t, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((hq, t, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="causal_attention_bwd",
        interpret=interpret,
    )(qi, kj, scale, q, k, v, do, rows(lse), rows(di))


# ---- the jnp path -----------------------------------------------------------


def _blocks(q, k, scale):
    """Per block of queries: its start and end, and its scaled causal scores
    ``[Hkv, G, block, end]`` against the keys up to its end."""
    hkv, t = k.shape[:2]
    qg = q.reshape(hkv, -1, t, q.shape[-1])
    sc = scale.reshape(hkv, -1, 1, 1)
    block = min(ATTENTION_BLOCK, t)
    for start in range(0, t, block):
        end = min(start + block, t)
        s = jnp.einsum("grqd,gkd->grqk", qg[:, :, start:end], k[:, :end],
                       preferred_element_type=jnp.float32) * sc
        causal = jnp.arange(start, end)[:, None] >= jnp.arange(end)[None, :]
        yield start, end, jnp.where(causal, s, -jnp.inf)


def _forward_blocked(q, k, v, scale):
    hq, t, _ = q.shape
    out, lse = [], []
    for _, end, s in _blocks(q, k, scale):
        lse.append(jax.nn.logsumexp(s, axis=-1))
        w = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("grqk,gkd->grqd", w.astype(v.dtype), v[:, :end],
                              preferred_element_type=jnp.float32))
    return (jnp.concatenate(out, axis=2).reshape(hq, t, -1),
            jnp.concatenate(lse, axis=2).reshape(hq, t))


def _backward_blocked(q, k, v, scale, lse, do, di):
    hq, t, dqk = q.shape
    hkv, dv = k.shape[0], v.shape[-1]
    grouped = lambda a: a.reshape((hkv, hq // hkv) + a.shape[1:])
    qg, lse, do, di = grouped(q), grouped(lse), grouped(do), grouped(di)
    dq, dk = [], jnp.zeros(qg.shape, jnp.float32)
    dvs = jnp.zeros(qg.shape[:3] + (dv,), jnp.float32)
    for start, end, s in _blocks(q, k, scale):
        prob = jnp.exp(s - lse[:, :, start:end, None])
        do_b = do[:, :, start:end]
        dvs = dvs.at[:, :, :end].add(jnp.einsum(
            "grqk,grqd->grkd", prob.astype(do.dtype), do_b,
            preferred_element_type=jnp.float32))
        dprob = jnp.einsum("grqd,gkd->grqk", do_b, v[:, :end],
                           preferred_element_type=jnp.float32)
        ds = (prob * (dprob - di[:, :, start:end, None])).astype(q.dtype)
        dq.append(jnp.einsum("grqk,gkd->grqd", ds, k[:, :end],
                             preferred_element_type=jnp.float32))
        dk = dk.at[:, :, :end].add(jnp.einsum(
            "grqk,grqd->grkd", ds, qg[:, :, start:end], preferred_element_type=jnp.float32))
    flat = lambda a: a.reshape((hq,) + a.shape[2:])
    return flat(jnp.concatenate(dq, axis=2)), flat(dk), flat(dvs)


# ---- the function both decoders call ---------------------------------------


def causal_attention(q, k, v, scale, dtype=jnp.bfloat16, use_pallas=None):
    """Causal attention of ``q`` [Hq, T, dqk] over ``k`` [Hkv, T, dqk] and
    ``v`` [Hkv, T, dv], ``Hq`` a multiple of ``Hkv``, scores scaled by
    ``scale`` [Hq] a query head; products in ``dtype`` (None: float32).
    Result ``[Hq, T, dv]`` float32.

    The kernels run on a TPU where ``kernel_block`` tiles ``T``;
    ``use_pallas=False`` takes the ``jnp`` path there too, ``use_pallas=True``
    runs the kernels interpreted off a TPU (where they tile)."""
    hq, t, dqk = q.shape
    hkv, dv = k.shape[0], v.shape[-1]
    if hq % hkv or k.shape != (hkv, t, dqk) or v.shape[:2] != (hkv, t):
        raise ValueError(f"no causal attention of q {q.shape} over k {k.shape}, v {v.shape}")
    on_tpu = jax.default_backend() == "tpu"
    block = kernel_block(t, dqk)
    if block is not None and (on_tpu if use_pallas is None else use_pallas):
        forward = partial(_forward_pallas, block=block, interpret=not on_tpu)
        backward = partial(_backward_pallas, block=block, interpret=not on_tpu)
    else:
        forward, backward = _forward_blocked, _backward_blocked
    cast = (lambda x: x.astype(jnp.float32)) if dtype is None else (lambda x: x.astype(dtype))
    like = q.dtype, k.dtype, v.dtype
    group = hq // hkv

    @jax.custom_vjp
    def attend(q, k, v, scale):
        return forward(cast(q), cast(k), cast(v), scale)[0]

    def attend_forward(q, k, v, scale):
        q, k, v = cast(q), cast(k), cast(v)
        out, lse = forward(q, k, v, scale)
        # The result is named where it leaves too: what follows (the output
        # projection's backward pass) takes it from the forward pass as well.
        out, lse = checkpoint_name(out, RESULT), checkpoint_name(lse, LOG_SUM_EXP)
        return out, (q, k, v, scale, out, lse)

    def attend_backward(saved, g):
        q, k, v, scale, out, lse = saved
        g = cast(g)
        di = jnp.sum(out * g.astype(jnp.float32), axis=-1)
        # A query head's sums of ds k, ds^T q and p^T do, ds the cotangent
        # of the scaled scores: the scale still to apply, and the key and
        # value sides still to sum over a group.
        gq, gk, gv = backward(q, k, v, scale, lse, g, di)
        by_head = scale[:, None, None]
        per_group = lambda a: a.reshape((hkv, group) + a.shape[1:]).sum(axis=1)
        dscale = jnp.sum(q.astype(jnp.float32) * gq, axis=(1, 2))
        return ((by_head * gq).astype(like[0]), per_group(by_head * gk).astype(like[1]),
                per_group(gv).astype(like[2]), dscale)

    attend.defvjp(attend_forward, attend_backward)
    return attend(q, k, v, scale.astype(jnp.float32))
