"""Compressed neighbor exchange (ops/compress.py; ISSUE 7).

Covers: codec correctness (int8 block quantization, top-k delta), the
error-feedback telescoping property, `compression: none` byte-identity,
end-to-end compressed training on the dense / circulant / sparse paths,
the quantized-kernel payload parity, gang and fused-scan composition, the
analytic exchange-bytes accounting, and the schema fail-louds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from pydantic import ValidationError

from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.config import Config
from murmura_tpu.core.rounds import build_multi_round, build_round_program
from murmura_tpu.data.base import FederatedArrays
from murmura_tpu.models import make_mlp
from murmura_tpu.ops.compress import (
    COMPRESS_STATE_KEYS,
    REF_KEY,
    RESIDUAL_KEY,
    CompressionSpec,
    Int8Blocks,
    compress_exchange,
    quantize_int8,
    topk_decode,
    topk_encode,
)


def _data(n=8, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return FederatedArrays(
        x=rng.normal(size=(n, s, 8)).astype(np.float32),
        y=rng.integers(0, 4, size=(n, s)).astype(np.int32),
        mask=np.ones((n, s), np.float32),
        num_samples=np.full((n,), s),
        num_classes=4,
    )


def _model():
    return make_mlp(input_dim=8, hidden_dims=(16,), num_classes=4)


def _dense_adj(n):
    return (np.ones((n, n)) - np.eye(n)).astype(np.float32)


def _circ_adj(n, offsets):
    adj = np.zeros((n, n), np.float32)
    for o in offsets:
        adj[np.arange(n), (np.arange(n) + o) % n] = 1.0
    return adj


def _run_rounds(prog, adj, rounds=3, n=8, alive=None):
    step = jax.jit(prog.train_step)
    params = prog.init_params
    state = {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()}
    d = {k: jnp.asarray(v) for k, v in prog.data_arrays.items()}
    metrics = None
    for r in range(rounds):
        args = [
            params, state, jax.random.PRNGKey(r), jnp.asarray(adj),
            jnp.zeros((n,), jnp.float32),
        ]
        if prog.faulted:
            args.append(jnp.ones((n,), jnp.float32) if alive is None else alive)
        args += [jnp.asarray(float(r), jnp.float32), d]
        params, state, metrics = step(*args)
    return params, state, metrics


class TestInt8Codec:
    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(6, 300)).astype(np.float32))
        qb = quantize_int8(x, block=64)
        deq = qb.dequantize()
        # Per-block error bound: |x - deq| <= scale/2 everywhere.
        per_col_scale = np.repeat(np.asarray(qb.scale), 64, axis=1)[:, :300]
        assert np.all(
            np.abs(np.asarray(deq - x)) <= per_col_scale / 2 + 1e-7
        )

    def test_zeros_are_exact(self):
        x = jnp.zeros((3, 100), jnp.float32)
        qb = quantize_int8(x, block=32)
        assert np.all(np.asarray(qb.dequantize()) == 0.0)
        assert np.all(np.asarray(qb.scale) == 0.0)

    def test_padding_is_inert(self):
        # p not a multiple of block: padded tail quantizes to exact-zero
        # codes and never leaks into the dequantized view.
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 70)), jnp.float32)
        qb = quantize_int8(x, block=32)
        assert qb.padded_p == 96 and qb.p == 70
        assert np.all(np.asarray(qb.q)[:, 70:] == 0)
        assert qb.dequantize().shape == (4, 70)

    def test_out_dtype_restored(self):
        x = jnp.asarray(np.random.default_rng(2).normal(size=(4, 64)), jnp.float32)
        qb = quantize_int8(x, block=32, out_dtype=jnp.bfloat16)
        assert qb.dequantize().dtype == jnp.bfloat16

    def test_pytree_roundtrip(self):
        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 64)), jnp.float32)
        qb = quantize_int8(x, block=32)
        leaves, treedef = jax.tree_util.tree_flatten(qb)
        qb2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert np.array_equal(np.asarray(qb2.q), np.asarray(qb.q))
        assert qb2.block == qb.block and qb2.p == qb.p


class TestTopkCodec:
    def test_encode_decode_support(self):
        rng = np.random.default_rng(0)
        delta = jnp.asarray(rng.normal(size=(5, 40)), jnp.float32)
        values, idx = topk_encode(delta, 4)
        dec = topk_decode(values, idx, 40)
        # The transmitted support reproduces exactly; the rest is zero.
        dn, decn = np.asarray(delta), np.asarray(dec)
        for i in range(5):
            on = np.asarray(idx)[i]
            assert np.allclose(decn[i, on], dn[i, on])
            off = np.setdiff1d(np.arange(40), on)
            assert np.all(decn[i, off] == 0.0)
        # Top-k by magnitude: every transmitted |value| >= every dropped.
        for i in range(5):
            on = np.asarray(idx)[i]
            off = np.setdiff1d(np.arange(40), on)
            assert np.min(np.abs(dn[i, on])) >= np.max(np.abs(dn[i, off])) - 1e-7


class TestErrorFeedback:
    def test_telescoping_residual(self):
        """EF property: after T rounds, sum_t (x_t - decoded_t) == e_T —
        per-round codec error telescopes into the final residual instead
        of accumulating as drift (arXiv:1910.12308)."""
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        rng = np.random.default_rng(0)
        n, p = 4, 96
        state = {RESIDUAL_KEY: jnp.zeros((n, p), jnp.float32)}
        total_err = np.zeros((n, p), np.float32)
        for t in range(6):
            x = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
            _, decoded, updates, _ = compress_exchange(spec, x, state, False)
            total_err += np.asarray(x) - np.asarray(decoded)
            state = {**state, **updates}
        assert np.allclose(
            total_err, np.asarray(state[RESIDUAL_KEY]), atol=1e-5
        )

    def test_residual_bounds_quantization_drift(self):
        # The residual norm stays at one-round-quantization scale (it
        # never grows with T): the drift bound EF exists for.
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        rng = np.random.default_rng(1)
        n, p = 4, 96
        state = {RESIDUAL_KEY: jnp.zeros((n, p), jnp.float32)}
        one_round_scale = None
        for t in range(10):
            x = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
            _, _, updates, stats = compress_exchange(spec, x, state, False)
            state = {**state, **updates}
            if one_round_scale is None:
                one_round_scale = float(np.max(np.asarray(stats["compress_error"])))
        final = float(np.max(np.asarray(stats["compress_error"])))
        assert final <= 3.0 * one_round_scale

    def test_topk_ref_tracks_decoded(self):
        spec = CompressionSpec("topk", topk_ratio=0.25, error_feedback=True)
        rng = np.random.default_rng(2)
        n, p = 4, 40
        state = {
            RESIDUAL_KEY: jnp.zeros((n, p), jnp.float32),
            REF_KEY: jnp.zeros((n, p), jnp.float32),
        }
        for t in range(3):
            x = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
            _, decoded, updates, _ = compress_exchange(spec, x, state, False)
            # The reference advances to exactly what receivers decoded.
            assert np.array_equal(
                np.asarray(updates[REF_KEY]), np.asarray(decoded)
            )
            state = {**state, **updates}


class TestQuantizedKernelChunking:
    """The chunked (fori_loop + remainder) paths of the quantized
    circulant kernels: with the default 256 MB budget every test-sized
    program takes the single-chunk early return, so the chunk/remainder
    arithmetic would otherwise first run on a real >256 MB-per-copy model
    (the test_pallas_agg multi-chunk pattern, for the quantized twins)."""

    def test_chunked_paths_match_unchunked(self, monkeypatch):
        import murmura_tpu.aggregation.base as base
        from murmura_tpu.aggregation.base import (
            circulant_candidate_map,
            circulant_neighbor_distances,
            circulant_weighted_sum,
        )

        rng = np.random.default_rng(0)
        n, p, offs = 6, 300, [1, 2, 4]
        x = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
        own = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
        w = jnp.asarray(rng.uniform(size=(3, n)), jnp.float32)
        qb = quantize_int8(x, block=32)
        fn = lambda cand: jnp.sort(cand, axis=0)[1]  # noqa: E731

        d_1 = circulant_neighbor_distances(own, qb, offs)
        dqq_1 = circulant_neighbor_distances(qb, qb, offs)
        ws_1 = circulant_weighted_sum(qb, w, offs, out_dtype=jnp.float32)
        cm_1 = circulant_candidate_map(own, qb, offs, fn)

        # Small budget => several full chunks + a remainder chunk (the
        # padded width is 10 blocks; budget forces ~2 blocks per chunk).
        monkeypatch.setattr(base, "_CIRCULANT_CHUNK_BYTES", 32 * n * 2)
        d_k = circulant_neighbor_distances(own, qb, offs)
        dqq_k = circulant_neighbor_distances(qb, qb, offs)
        ws_k = circulant_weighted_sum(qb, w, offs, out_dtype=jnp.float32)
        cm_k = circulant_candidate_map(own, qb, offs, fn)

        np.testing.assert_allclose(d_k, d_1, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(dqq_k, dqq_1, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(ws_k, ws_1, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(cm_k, cm_1)

    def test_own_compressed_without_broadcast_rejected(self):
        from murmura_tpu.aggregation.base import circulant_neighbor_distances

        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 64)),
                        jnp.float32)
        qb = quantize_int8(x, block=32)
        with pytest.raises(TypeError, match="quantize both or neither"):
            circulant_neighbor_distances(qb, x, [1])


class TestRoundProgramComposition:
    def test_none_is_byte_identical(self):
        """compression=None programs and histories are untouched — the
        default-off contract (the faults:/telemetry:/population: pattern)."""
        n = 8
        agg = build_aggregator("fedavg", {}, model_dim=100, total_rounds=4)
        base = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8
        )
        again = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=None,
        )
        adj = _dense_adj(n)
        p1, s1, m1 = _run_rounds(base, adj, rounds=2)
        p2, s2, m2 = _run_rounds(again, adj, rounds=2)
        for a, b in zip(
            jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert sorted(m1) == sorted(m2)
        assert not any("compress" in k for k in m1)

    @pytest.mark.parametrize("algorithm", ["int8", "topk"])
    def test_dense_compressed_trains(self, algorithm):
        n = 8
        spec = CompressionSpec(
            algorithm, block=32, topk_ratio=0.2, error_feedback=True
        )
        agg = build_aggregator("fedavg", {}, model_dim=100, total_rounds=4)
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=spec,
        )
        assert prog.compression is spec
        params, state, metrics = _run_rounds(prog, _dense_adj(n))
        assert all(
            np.isfinite(np.asarray(v)).all()
            for v in jax.tree_util.tree_leaves(params)
        )
        assert "agg_compress_error" in metrics
        assert RESIDUAL_KEY in state
        if algorithm == "topk":
            assert REF_KEY in state

    def test_compress_state_hidden_from_rule(self):
        # The rule's state dict never sees the reserved keys (the
        # DMTT_STATE_KEYS pattern): balance carries its own state and
        # must receive exactly that.
        seen = {}
        inner = build_aggregator("balance", {}, model_dim=100, total_rounds=4)

        def spy(own, bcast, adj, round_idx, state, ctx):
            seen["keys"] = sorted(state)
            return inner.aggregate(own, bcast, adj, round_idx, state, ctx)

        agg = dataclasses.replace(inner, aggregate=spy)
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=spec,
        )
        _run_rounds(prog, _dense_adj(8), rounds=1)
        assert not set(seen["keys"]) & set(COMPRESS_STATE_KEYS)
        assert RESIDUAL_KEY in prog.init_agg_state

    def test_circulant_quantized_payload_close_to_dense_decode(self):
        """The quantized-kernel path (rules receive the Int8Blocks payload)
        computes the same aggregation as feeding the dequantized tensor
        through the plain kernels — pinned by comparing a krum circulant
        compressed run against a manual decode."""
        n, offsets = 8, [1, 2]
        spec = CompressionSpec("int8", block=32)
        agg = build_aggregator(
            "krum",
            {"num_compromised": 1, "exchange_offsets": offsets},
            model_dim=100, total_rounds=4,
        )
        assert agg.quantized_exchange
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=spec,
        )
        params, _, metrics = _run_rounds(prog, _circ_adj(n, offsets))
        assert all(
            np.isfinite(np.asarray(v)).all()
            for v in jax.tree_util.tree_leaves(params)
        )
        assert float(np.asarray(metrics["agg_compress_error"]).mean()) >= 0.0

    # One rule per distinct compressed-kernel path (tier-1 time budget):
    # krum = delta-distance rolls, median = candidate map, geomed =
    # Weiszfeld weighted sums, ubar = the materialized (probe) path.
    # fedavg/trimmed_mean/balance share these kernels and are covered by
    # the quantized-flag bijection test + tests/test_pallas_agg.py.
    @pytest.mark.parametrize(
        "rule,params",
        [
            ("krum", {"num_compromised": 1}),
            ("median", {}),
            ("geometric_median", {"max_iters": 2}),
            ("ubar", {}),  # materialized path (quantized_exchange=False)
        ],
    )
    def test_circulant_rules_run_compressed(self, rule, params):
        n, offsets = 8, [1, 2]
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        agg = build_aggregator(
            rule, dict(params, exchange_offsets=offsets),
            model_dim=100, total_rounds=4,
        )
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            probe_size=8, compression=spec,
        )
        params_o, _, metrics = _run_rounds(
            prog, _circ_adj(n, offsets), rounds=2
        )
        assert all(
            np.isfinite(np.asarray(v)).all()
            for v in jax.tree_util.tree_leaves(params_o)
        )
        assert "agg_compress_error" in metrics

    def test_fused_scan_carries_residual(self):
        n = 8
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        agg = build_aggregator("fedavg", {}, model_dim=100, total_rounds=4)
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=spec,
        )
        multi = jax.jit(build_multi_round(prog, chunk=3, eval_every=3))
        adj = jnp.asarray(np.stack([_dense_adj(n)] * 3))
        params, state, rows = multi(
            prog.init_params,
            {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
            jax.random.PRNGKey(0),
            adj,
            jnp.zeros((n,), jnp.float32),
            jnp.asarray(0, jnp.int32),
            {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
        )
        assert rows["agg_compress_error"].shape == (3, n)
        assert np.isfinite(np.asarray(state[RESIDUAL_KEY])).all()

    def test_faulted_compressed_round(self):
        from murmura_tpu.faults.schedule import FaultSpec

        n = 8
        spec = CompressionSpec("int8", block=32, error_feedback=True)
        agg = build_aggregator("fedavg", {}, model_dim=100, total_rounds=4)
        prog = build_round_program(
            _model(), agg, _data(), total_rounds=4, batch_size=8,
            compression=spec, faults=FaultSpec(),
        )
        alive = jnp.asarray(
            np.array([1, 1, 0, 1, 1, 1, 0, 1], np.float32)
        )
        params, _, metrics = _run_rounds(
            prog, _dense_adj(n), rounds=2, alive=alive
        )
        assert all(
            np.isfinite(np.asarray(v)).all()
            for v in jax.tree_util.tree_leaves(params)
        )
        assert float(np.asarray(metrics["agg_alive"])) == 6.0

    def test_dmtt_rejected(self):
        from murmura_tpu.dmtt.protocol import DMTTParams

        agg = build_aggregator("fedavg", {}, model_dim=100, total_rounds=4)
        with pytest.raises(ValueError, match="DMTT"):
            build_round_program(
                _model(), agg, _data(), total_rounds=4, batch_size=8,
                compression=CompressionSpec("int8"), dmtt=DMTTParams(),
            )


def _cfg(overrides=None, **compression):
    raw = {
        "experiment": {"name": "compress-test", "seed": 3, "rounds": 2},
        "topology": {"type": "k-regular", "num_nodes": 8, "k": 2},
        "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {
            "adapter": "synthetic",
            "params": {
                "num_samples": 64, "input_shape": [8], "num_classes": 4,
            },
        },
        "model": {
            "factory": "mlp",
            "params": {"input_dim": 8, "hidden_dims": [16], "num_classes": 4},
        },
        "backend": "simulation",
    }
    if compression:
        raw["compression"] = compression
    for k, v in (overrides or {}).items():
        raw[k] = v
    return Config.model_validate(raw)


class TestConfigWiring:
    def test_schema_defaults_off(self):
        cfg = _cfg()
        assert cfg.compression.algorithm == "none"
        from murmura_tpu.utils.factories import build_compression_spec

        assert build_compression_spec(cfg) is None

    def test_sparse_topology_composition(self):
        from murmura_tpu.utils.factories import build_network_from_config

        cfg = _cfg(
            overrides={
                "topology": {"type": "exponential", "num_nodes": 16},
                "aggregation": {"algorithm": "fedavg", "params": {}},
            },
            algorithm="int8", error_feedback=True, block=64,
        )
        net = build_network_from_config(cfg)
        assert net.program.sparse and net.program.compression is not None
        history = net.train(rounds=2, eval_every=1)
        assert all(np.isfinite(history["mean_accuracy"]))

    def test_gang_composition(self):
        from murmura_tpu.utils.factories import build_gang_from_config

        cfg = _cfg(
            overrides={"sweep": {"num_seeds": 2}},
            algorithm="int8", error_feedback=True, block=64,
        )
        gang = build_gang_from_config(cfg)
        histories = gang.train(rounds=2, eval_every=1)
        assert len(histories) == 2
        for h in histories:
            assert all(np.isfinite(h["mean_accuracy"]))
            assert "agg_compress_error" in h

    def test_int8_accuracy_tracks_uncompressed(self):
        """int8 + error feedback stays close to the uncompressed run on
        the attack scenario: final mean accuracy within a loose
        tolerance."""
        from murmura_tpu.utils.factories import build_network_from_config

        atk = {
            "attack": {
                "enabled": True, "type": "gaussian", "percentage": 0.25,
                "params": {"noise_std": 5.0},
            },
            "experiment": {"name": "compress-acc", "seed": 3, "rounds": 3},
        }
        h0 = build_network_from_config(_cfg(overrides=atk)).train(
            rounds=3, eval_every=3
        )
        net1 = build_network_from_config(
            _cfg(overrides=atk, algorithm="int8", error_feedback=True,
                 block=64)
        )
        assert net1.program.compression is not None
        h1 = net1.train(rounds=3, eval_every=3)
        assert abs(h1["mean_accuracy"][-1] - h0["mean_accuracy"][-1]) < 0.1
        assert all(np.isfinite(h1["mean_accuracy"]))
        assert "agg_compress_error" in h1
        cost = net1.exchange_cost_analysis()
        # int8 payload (1 byte + scale amortized) vs f32 rows: >= 3x — the
        # acceptance-criterion surface.
        assert cost["exchange_bytes_reduction"] >= 3.0
        assert cost["exchange_bytes_per_round"] < (
            cost["uncompressed_exchange_bytes_per_round"]
        )

    def test_fail_louds(self):
        with pytest.raises(ValidationError, match="error_feedback"):
            _cfg(error_feedback=True)  # no codec
        with pytest.raises(ValidationError, match="distributed"):
            _cfg(overrides={"backend": "distributed"}, algorithm="int8")
        with pytest.raises(ValidationError, match="population"):
            _cfg(
                overrides={
                    "population": {"enabled": True, "virtual_size": 100},
                },
                algorithm="topk",
            )
        with pytest.raises(ValueError, match="algorithm"):
            CompressionSpec("gzip")
        with pytest.raises(ValueError, match="topk_ratio"):
            CompressionSpec("topk", topk_ratio=0.0)


class TestAnalyticBytes:
    def test_payload_bytes(self):
        p = 1000
        int8 = CompressionSpec("int8", block=100)
        assert int8.payload_bytes(p, 4) == 1000 + 10 * 4
        topk = CompressionSpec("topk", topk_ratio=0.1)
        assert topk.payload_bytes(p, 4) == 100 * 8
        # int8 vs f32 rows: ~3.85x; topk(5%) vs f32: 10x.
        assert p * 4 / int8.payload_bytes(p, 4) > 3.0
