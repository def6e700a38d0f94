"""simulation vs tpu backend equivalence: the same config, seed, and round
count must learn the same way whether the node axis is vmapped on one
device or sharded over the 8-virtual-device CPU mesh
(SURVEY.md §4 test plan items (b)/(c))."""

import numpy as np
import pytest

from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config


def _cfg(backend: str) -> Config:
    return Config.model_validate(
        {
            "experiment": {"name": f"eq-{backend}", "seed": 11, "rounds": 3},
            "topology": {"type": "ring", "num_nodes": 8},
            "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
            "attack": {"enabled": True, "type": "gaussian", "percentage": 0.25,
                        "params": {"noise_std": 5.0}},
            "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
            "data": {"adapter": "synthetic",
                     "params": {"num_samples": 800, "input_dim": 24,
                                "num_classes": 4}},
            "model": {"factory": "mlp",
                      "params": {"input_dim": 24, "hidden_dims": [32],
                                 "num_classes": 4}},
            "backend": backend,
            # Pin full precision so the two backends are numerically
            # comparable; the tpu backend defaults to bfloat16 matmuls.
            "tpu": {"compute_dtype": "float32"},
        }
    )


def test_simulation_and_tpu_backends_match():
    hist_sim = build_network_from_config(_cfg("simulation")).train(rounds=3)
    hist_tpu = build_network_from_config(_cfg("tpu")).train(rounds=3)

    assert hist_sim["round"] == hist_tpu["round"]
    np.testing.assert_allclose(
        hist_sim["mean_accuracy"], hist_tpu["mean_accuracy"], atol=1e-4
    )
    np.testing.assert_allclose(
        hist_sim["mean_loss"], hist_tpu["mean_loss"], rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        hist_sim["honest_accuracy"], hist_tpu["honest_accuracy"], atol=1e-4
    )


def test_tpu_backend_learns_under_attack():
    net = build_network_from_config(_cfg("tpu"))
    hist = net.train(rounds=3)
    assert hist["honest_accuracy"][-1] > 0.5  # Krum resists 25% gaussian


@pytest.mark.slow
def test_wearable_window_params_sync_model_input_dim():
    # Non-default window params change sample dimensionality; the model
    # input must follow without a hand-set input_dim.
    cfg = Config.model_validate(
        {
            "experiment": {"name": "win-sync", "seed": 0, "rounds": 1},
            "topology": {"type": "ring", "num_nodes": 4},
            "aggregation": {"algorithm": "fedavg", "params": {}},
            "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
            "data": {"adapter": "wearables.pamap2",
                     "params": {"window_size": 50,
                                "include_heart_rate": False,
                                "num_samples": 200,
                                "partition_method": "iid"}},
            "model": {"factory": "examples.wearables.pamap2", "params": {}},
            "backend": "simulation",
        }
    )
    hist = build_network_from_config(cfg).train(rounds=1)
    assert len(hist["round"]) == 1  # forward pass shape-consistent


def test_tpu_backend_bfloat16_learns():
    cfg = _cfg("tpu")
    cfg.tpu.compute_dtype = "bfloat16"
    hist = build_network_from_config(cfg).train(rounds=3)
    assert np.isfinite(hist["mean_loss"][-1])
    assert hist["honest_accuracy"][-1] > 0.5


def test_tpu_param_dtype_bfloat16():
    # tpu.param_dtype=bfloat16 stores the stacked node state (and the
    # exchanged [N, P] tensor) in bf16; it must actually take effect and
    # stay stable across rounds (attack noise must not promote it back).
    import jax.numpy as jnp
    from jax.tree_util import tree_leaves

    cfg = _cfg("tpu")
    cfg.tpu.param_dtype = "bfloat16"
    net = build_network_from_config(cfg)
    assert all(l.dtype == jnp.bfloat16 for l in tree_leaves(net.params))
    hist = net.train(rounds=2)
    assert all(l.dtype == jnp.bfloat16 for l in tree_leaves(net.params))
    assert np.isfinite(hist["mean_loss"][-1])
    assert hist["honest_accuracy"][-1] > 0.4


def test_ppermute_exchange_matches_allgather():
    # On a circulant graph, the roll-based O(degree) exchange must produce
    # exactly the adjacency-matmul result.
    def cfg(exchange):
        c = _cfg("tpu")
        c.topology.type = "k-regular"
        c.topology.k = 4
        c.aggregation.algorithm = "fedavg"
        c.aggregation.params = {}
        c.tpu.exchange = exchange
        return c

    hist_ag = build_network_from_config(cfg("allgather")).train(rounds=3)
    hist_pp = build_network_from_config(cfg("ppermute")).train(rounds=3)
    np.testing.assert_allclose(
        hist_ag["mean_accuracy"], hist_pp["mean_accuracy"], atol=1e-5
    )
    np.testing.assert_allclose(
        hist_ag["mean_loss"], hist_pp["mean_loss"], rtol=1e-4
    )


def test_ppermute_chunked_kernels_match_sharded_and_unsharded(monkeypatch):
    """Forcing the P-chunked circulant kernels (the 256-node OOM fix,
    base.py _CIRCULANT_CHUNK_BYTES) must not change training history —
    on one device and with the node axis sharded over the 8-device mesh."""
    from murmura_tpu.aggregation import base as agg_base

    def cfg(num_devices):
        c = _cfg("tpu")
        c.topology.type = "k-regular"
        c.topology.k = 4
        c.tpu.exchange = "ppermute"
        c.tpu.num_devices = num_devices
        return c

    ref = build_network_from_config(cfg(1)).train(rounds=3)
    # MLP 24->32->4 => P = 24*32+32+32*4+4 = 964 floats; chunk len
    # 1024 // (8 nodes * 4 bytes) = 32 -> 30 full chunks + tail.
    monkeypatch.setattr(agg_base, "_CIRCULANT_CHUNK_BYTES", 1024)
    chunked = build_network_from_config(cfg(1)).train(rounds=3)
    sharded = build_network_from_config(cfg(8)).train(rounds=3)
    for hist in (chunked, sharded):
        np.testing.assert_allclose(
            ref["mean_loss"], hist["mean_loss"], rtol=1e-4
        )
        np.testing.assert_allclose(
            ref["mean_accuracy"], hist["mean_accuracy"], atol=1e-5
        )


def test_node_axis_sharded_flag_resolution():
    """AggContext.node_axis_sharded selects circulant shift lowerings
    (probe.py): an explicit mesh is authoritative, else tpu.num_devices."""
    from murmura_tpu.utils.factories import _node_axis_sharded

    c1 = _cfg("tpu")
    c1.tpu.num_devices = 1
    assert _node_axis_sharded(c1) is False
    c8 = _cfg("tpu")
    c8.tpu.num_devices = 8
    assert _node_axis_sharded(c8) is True
    assert _node_axis_sharded(_cfg("simulation")) is False

    # Explicit mesh wins over config (a subset mesh on a multi-device host
    # must not pick the sharded lowering).
    import jax
    from jax.sharding import Mesh

    cnull = _cfg("tpu")
    cnull.tpu.num_devices = None
    single = Mesh(np.array(jax.devices()[:1]), ("nodes",))
    assert _node_axis_sharded(cnull, single) is False
    full = Mesh(np.array(jax.devices()), ("nodes",))
    assert _node_axis_sharded(cnull, full) is (len(jax.devices()) > 1)


def test_ppermute_exchange_rejects_noncirculant():
    import pytest as _pytest

    c = _cfg("tpu")
    c.topology.type = "erdos"
    c.topology.p = 0.5
    c.aggregation.algorithm = "fedavg"
    c.aggregation.params = {}
    c.tpu.exchange = "ppermute"
    with _pytest.raises(ValueError, match="circulant"):
        build_network_from_config(c)


import pytest


@pytest.mark.parametrize("algo,params", [
    ("balance", {"gamma": 1.5}),
    ("sketchguard", {"sketch_size": 64}),
    ("ubar", {"rho": 0.6}),
    ("evidential_trust", {"trust_threshold": 0.1}),
    ("median", {}),
    ("trimmed_mean", {"trim_ratio": 0.2}),
])
def test_ppermute_circulant_rule_matches_allgather(algo, params):
    def cfg(exchange):
        c = _cfg("tpu")
        c.topology.type = "ring"
        c.aggregation.algorithm = algo
        c.aggregation.params = dict(params)
        c.tpu.exchange = exchange
        return c

    hist_ag = build_network_from_config(cfg("allgather")).train(rounds=3)
    hist_pp = build_network_from_config(cfg("ppermute")).train(rounds=3)
    np.testing.assert_allclose(
        hist_ag["mean_loss"], hist_pp["mean_loss"], rtol=1e-3
    )
    np.testing.assert_allclose(
        hist_ag["mean_accuracy"], hist_pp["mean_accuracy"], atol=1e-3
    )


@pytest.mark.slow
def test_64node_rules_scale_smoke(monkeypatch):
    """Structural scale coverage on CPU: 64 nodes crosses the bf16
    auto-default boundary (factories.resolved_param_dtype) and, with the
    chunk budget forced down, exercises the P-chunked circulant/dense
    kernels inside a full round program — the code paths the 256-node
    chip runs take, minus the chip."""
    from murmura_tpu.aggregation import base as agg_base

    # Tiny model keeps this a smoke test; the forced budget still splits
    # its P into multiple chunks.
    monkeypatch.setattr(agg_base, "_CIRCULANT_CHUNK_BYTES", 64 * 1024)

    for algo, params, exchange in [
        ("krum", {"num_compromised": 1}, "ppermute"),
        ("geometric_median", {}, "allgather"),
        ("median", {}, "allgather"),
        ("trimmed_mean", {"trim_ratio": 0.2}, "ppermute"),
    ]:
        c = _cfg("tpu")
        c.topology.type = "k-regular"
        c.topology.k = 4
        c.topology.num_nodes = 64
        c.data.params["num_samples"] = 64 * 20
        c.aggregation.algorithm = algo
        c.aggregation.params = dict(params)
        c.tpu.exchange = exchange
        c.tpu.compute_dtype = "float32"  # CPU: bf16 matmuls are emulated
        hist = build_network_from_config(c).train(rounds=2)
        assert len(hist["round"]) == 2
        assert np.isfinite(hist["mean_loss"]).all(), (algo, exchange)
