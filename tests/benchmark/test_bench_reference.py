"""The plain reference's own parts against hand values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import precision, rule_sketchguard
from benchmark.reference.round import Job, batch_schedule


def test_a_product_rounds_its_operands_forward_and_backward():
    x = jnp.asarray([1.0 + 2.0**-10, 3.0])
    assert np.asarray(precision.round_operand(x, "bfloat16"))[0] == 1.0
    assert np.asarray(precision.round_operand(x, "float32"))[0] == 1.0 + 2.0**-10
    # Three mantissa bits after the scale puts 448 at the format's top:
    # steps of 1/8 of a power of two, 1.06 * 240 / 448 = 0.568 -> 0.5625.
    coarse = np.asarray(precision.round_operand(jnp.asarray([1.0, 1.06, 448.0]),
                                                "float8_e4m3fn"))
    assert coarse[1] == pytest.approx(1.05) and coarse[2] == 448.0
    # The gradient meets the rounded operand, not the exact one.
    a, b = jnp.ones((1, 1)), jnp.asarray([[1.0 + 2.0**-10]])
    exact = jax.grad(lambda v: precision.matmul(v, b, "float32").sum())(a)
    rounded = jax.grad(lambda v: precision.matmul(v, b, "bfloat16").sum())(a)
    assert float(exact[0, 0]) == 1.0 + 2.0**-10 and float(rounded[0, 0]) == 1.0


@pytest.mark.parametrize("fmt,host", [
    ("bfloat16", "bfloat16"), ("float8_e5m2", "float8_e5m2"),
])
def test_rounding_inside_a_jit_is_the_hosts_rounding(fmt, host):
    """``reduce_precision`` inside a jitted program against a cast by
    ml_dtypes on the host (the formats whose range the two share)."""
    import ml_dtypes

    x = np.random.default_rng(1).normal(size=4096).astype(np.float32)
    top = precision.FORMATS[fmt][2]
    scale = 1.0 if top is None else np.float32(top) / np.abs(x).max()
    want = (x * scale).astype(getattr(ml_dtypes, host)).astype(np.float32) / scale
    got = np.asarray(jax.jit(lambda v: precision.rounded(v, fmt))(jnp.asarray(x)))
    normal = np.abs(x * scale) >= 2.0 ** -14  # reduce_precision flushes below
    np.testing.assert_allclose(got[normal], want[normal], rtol=1e-6)


def test_the_rounding_probe_sees_every_format_round():
    probe = precision.rounding_probe(4096)
    assert set(probe) == {"bfloat16", "float8_e4m3fn", "float8_e5m2"}
    for fmt, seen in probe.items():
        assert seen["changed"] > 0.99, fmt
        assert 0.5 * seen["half_ulp"] < seen["widest_step"] <= seen["half_ulp"]


def test_sketchguard_rejects_a_far_broadcast_and_blends_the_rest():
    rng = np.random.default_rng(0)
    own = jnp.asarray(rng.normal(size=(4, 200)).astype(np.float32))
    bcast = own.at[3].add(1000.0)
    adj = np.ones((4, 4)) - np.eye(4)
    state = rule_sketchguard.init_state(4, {})
    new, state, stats = rule_sketchguard.aggregate(
        own, bcast, adj, 0.0, state, {"sketch_size": 50}, 10, {}
    )
    want0 = 0.5 * own[0] + 0.5 * (own[1] + own[2]) / 2
    assert np.allclose(np.asarray(new[0]), np.asarray(want0), atol=1e-5)
    assert np.asarray(stats["acceptance_rate"])[0] == pytest.approx(2 / 3)
    assert int(state["window_len"][0]) == 1


def test_batch_schedule_covers_every_sample_once_an_epoch():
    data = {
        "mask": np.ones((3, 12), np.float32), "eff_batch": np.full(3, 4),
        "steps": np.full(3, 3), "num_samples": np.full(3, 12),
    }
    job = Job(model="femnist_cnn", rule="sketchguard", rule_params={}, attack=None,
              attack_params={}, lr=0.1, batch_size=4, local_epochs=2,
              total_rounds=5)
    idx, bmask, live, _ = batch_schedule(5, 0, data, job)
    assert idx.shape == (2, 3, 3, 4) and bmask.shape == (3, 4)
    for epoch in range(2):
        for node in range(3):
            assert sorted(idx[epoch, :, node].ravel().tolist()) == list(range(12))
    again, _, _, _ = batch_schedule(5, 0, data, job)
    other, _, _, _ = batch_schedule(5, 1, data, job)
    assert np.array_equal(idx, again) and not np.array_equal(idx, other)
    half, hmask, _, _ = batch_schedule(
        5, 0, data, Job(**{**job.__dict__, "fault": "half_batch"})
    )
    assert hmask.sum() == bmask.sum() / 2
