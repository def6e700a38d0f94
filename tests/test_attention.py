"""``ops/attention.py``: the flash kernels (interpreted on the CPU) against
the ``jnp`` path, and both against plain causal attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from murmura_tpu.ops import attention

SHAPES = pytest.mark.parametrize(
    "hq,hkv,dqk,dv",
    [(2, 2, 192, 128),  # latent attention's heads: 128 + 64 against values of 128
     (4, 2, 128, 128)],  # grouped queries: two query heads a key/value head
    ids=["mla", "gqa"],
)


def _operands(hq, hkv, dqk, dv, t, seed=0):
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (hq, t, dqk), jnp.float32)
    k = jax.random.normal(kk, (hkv, t, dqk), jnp.float32)
    v = jax.random.normal(kv, (hkv, t, dv), jnp.float32)
    scale = jax.random.uniform(ks, (hq,), jnp.float32, 0.5, 1.5) / np.sqrt(dqk)
    return q, k, v, scale


def _plain(q, k, v, scale):
    """Whole [T, T] scores a head, float32 throughout."""
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") * scale[:, None, None]
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, precision="highest")


def _value_and_grads(f, operands, seed=1):
    """The result, and the gradients of q, k, v and scale of a fixed
    random projection of it."""
    out = f(*operands)
    w = jax.random.normal(jax.random.PRNGKey(seed), out.shape, jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3))(*operands)
    return out, grads


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), np.abs(got - want).max()


def _runs_kernel(f, operands):
    return "pallas_call" in str(jax.make_jaxpr(f)(*operands))


def test_the_block_follows_the_sequence():
    assert attention.kernel_block(4096, 192) == 1024
    assert attention.kernel_block(1024, 128) == 512
    assert attention.kernel_block(256, 128) == 128  # two blocks, one above the diagonal
    assert attention.kernel_block(128, 128) is None  # one block: nothing to skip
    assert attention.kernel_block(320, 128) is None
    assert attention.kernel_block(65536, 192) is None  # a head's dq would not stay in VMEM


@SHAPES
def test_the_kernels_follow_the_jnp_path(hq, hkv, dqk, dv):
    operands = _operands(hq, hkv, dqk, dv, 256)
    kernel = lambda *a: attention.causal_attention(*a, use_pallas=True)
    blocked = lambda *a: attention.causal_attention(*a, use_pallas=False)
    assert _runs_kernel(kernel, operands) and not _runs_kernel(blocked, operands)
    (out, grads), (want, wants) = (_value_and_grads(f, operands) for f in (kernel, blocked))
    # bf16 operands: a probability or a cotangent rounded the other way is
    # 2^-8 of itself.
    _close(out, want, 1e-2)
    for got, w in zip(grads, wants):
        _close(got, w, 2e-2)


@SHAPES
@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernel", "jnp"])
def test_both_paths_are_causal_attention(hq, hkv, dqk, dv, use_pallas):
    operands = _operands(hq, hkv, dqk, dv, 256, seed=2)
    (out, grads), (want, wants) = (
        _value_and_grads(f, operands) for f in (
            lambda *a: attention.causal_attention(*a, dtype=None, use_pallas=use_pallas),
            _plain))
    _close(out, want, 1e-5)
    for got, w in zip(grads, wants):
        _close(got, w, 1e-4)


@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernel", "jnp"])
def test_the_gradients_leave_in_float32_unrounded(use_pallas):
    """bf16 products, float32 gradients: not rounded to bf16 on the way out
    (a float32 consumer takes them as the products accumulated them)."""
    operands = _operands(4, 2, 128, 128, 256, seed=3)
    f = lambda *a: attention.causal_attention(*a, use_pallas=use_pallas)
    for g in _value_and_grads(f, operands)[1]:
        assert g.dtype == jnp.float32
        g = np.asarray(g)
        assert (g != np.asarray(jnp.asarray(g).astype(jnp.bfloat16).astype(jnp.float32))).mean() > 0.5


def test_a_sequence_the_kernel_cannot_tile_takes_the_jnp_path():
    operands = _operands(4, 2, 128, 128, 320, seed=4)
    f = lambda *a: attention.causal_attention(*a, dtype=None, use_pallas=True)
    assert not _runs_kernel(f, operands)
    _close(f(*operands), _plain(*operands), 1e-5)


def test_a_later_position_changes_nothing_before_it():
    q, k, v, scale = _operands(2, 2, 192, 128, 256, seed=5)
    f = lambda *a: attention.causal_attention(*a, use_pallas=True)
    moved = f(q, k.at[:, 200:].multiply(3.0), v.at[:, 200:].add(1.0), scale)
    np.testing.assert_array_equal(np.asarray(moved[:, :200]), np.asarray(f(q, k, v, scale)[:, :200]))


def test_heads_that_do_not_group_are_refused():
    q, k, v, scale = _operands(3, 2, 128, 128, 256)
    with pytest.raises(ValueError, match="no causal attention"):
        attention.causal_attention(q, k, v, scale)
