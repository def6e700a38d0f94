"""Closed-form aggregation rule tests (SURVEY.md §4 plan item (a);
reference semantics: murmura/aggregation/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.aggregation.base import AggContext, pairwise_l2_distances


def _ring_adj(n):
    adj = np.zeros((n, n), dtype=np.float32)
    for i in range(n):
        adj[i, (i + 1) % n] = adj[i, (i - 1) % n] = 1.0
    return jnp.asarray(adj)


def _full_adj(n):
    adj = np.ones((n, n), dtype=np.float32) - np.eye(n, dtype=np.float32)
    return jnp.asarray(adj)


def _ctx(total_rounds=10, **kw):
    return AggContext(total_rounds=total_rounds, **kw)


def _run(agg, own, adj, round_idx=0, bcast=None, ctx=None, state=None):
    own = jnp.asarray(own, jnp.float32)
    bcast = own if bcast is None else jnp.asarray(bcast, jnp.float32)
    state = state if state is not None else {
        k: jnp.asarray(v) for k, v in agg.init_state(own.shape[0]).items()
    }
    return agg.aggregate(own, bcast, adj, jnp.asarray(round_idx, jnp.float32),
                         state, ctx or _ctx())


class TestPairwiseDistances:
    def test_matches_direct(self):
        a = np.random.default_rng(0).normal(size=(5, 17)).astype(np.float32)
        d = np.asarray(pairwise_l2_distances(jnp.asarray(a)))
        direct = np.linalg.norm(a[:, None] - a[None, :], axis=-1)
        np.testing.assert_allclose(d, direct, atol=2e-3)

    def test_large_offset_cancellation(self):
        """Centering keeps small distances accurate under a huge common
        offset (the late-training regime Krum ranks in)."""
        rng = np.random.default_rng(1)
        base = rng.normal(size=(6, 100)).astype(np.float32) * 1e-3
        shifted = base + 300.0  # norm ~ 3e3, distances ~ 1e-2
        d = np.asarray(pairwise_l2_distances(jnp.asarray(shifted)))
        direct = np.linalg.norm(base[:, None] - base[None, :], axis=-1)
        np.testing.assert_allclose(d, direct, rtol=0.05, atol=1e-4)


    @pytest.mark.parametrize("ambient", ["highest", "high", None])
    def test_gram_dot_ignores_the_ambient_matmul_precision(self, ambient):
        """The identity cancels the dot against f32 VPU norms; on a v5e a
        "high"/"highest" [16, 6.6M] dot disagrees with them by 5e-4 of their
        value and every d2 clamps to 0 (chip_smoke's f32 runs found Krum
        scores of 0.0).  The dot is pinned to the default precision."""
        import contextlib

        import jax

        a = jnp.ones((4, 8), jnp.float32)
        scope = (
            jax.default_matmul_precision(ambient) if ambient
            else contextlib.nullcontext()
        )
        with scope:
            jaxpr = jax.make_jaxpr(pairwise_l2_distances)(a)
        dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
        assert len(dots) == 1
        assert dots[0].params["precision"] == (
            jax.lax.Precision.DEFAULT, jax.lax.Precision.DEFAULT
        )


class TestCirculantChunking:
    """The P-chunked circulant kernels (base.py _CIRCULANT_CHUNK_BYTES —
    the 256-node OOM fix) must reproduce the single-chunk computation."""

    def _force_chunk(self, monkeypatch, nbytes):
        from murmura_tpu.aggregation import base

        monkeypatch.setattr(base, "_CIRCULANT_CHUNK_BYTES", nbytes)

    def test_distances_match_unchunked(self, monkeypatch):
        from murmura_tpu.aggregation.base import circulant_neighbor_distances

        rng = np.random.default_rng(3)
        own = jnp.asarray(rng.normal(size=(6, 101)), jnp.float32)
        bcast = jnp.asarray(rng.normal(size=(6, 101)), jnp.float32)
        offsets = [1, 2, 5]
        ref = np.asarray(circulant_neighbor_distances(own, bcast, offsets))
        # 6 nodes * 4 bytes * 7 -> chunk len 7: 14 full chunks + tail of 3.
        self._force_chunk(monkeypatch, 6 * 4 * 7)
        chunked = np.asarray(circulant_neighbor_distances(own, bcast, offsets))
        np.testing.assert_allclose(chunked, ref, rtol=1e-6, atol=1e-6)

    def test_weighted_sum_matches_unchunked(self, monkeypatch):
        from murmura_tpu.aggregation.base import circulant_weighted_sum

        rng = np.random.default_rng(4)
        bcast = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        w_k = jnp.asarray(rng.uniform(size=(2, 5)), jnp.float32)
        offsets = [1, 4]
        ref = np.asarray(circulant_weighted_sum(bcast, w_k, offsets))
        self._force_chunk(monkeypatch, 5 * 4 * 9)  # chunk 9, tail 1
        chunked = np.asarray(circulant_weighted_sum(bcast, w_k, offsets))
        np.testing.assert_allclose(chunked, ref, rtol=1e-6, atol=1e-6)

    def test_exact_chunk_divisor_no_tail(self, monkeypatch):
        from murmura_tpu.aggregation.base import circulant_weighted_sum

        rng = np.random.default_rng(5)
        bcast = jnp.asarray(rng.normal(size=(4, 60)), jnp.float32)
        w_k = jnp.asarray(rng.uniform(size=(1, 4)), jnp.float32)
        ref = np.asarray(circulant_weighted_sum(bcast, w_k, [2]))
        self._force_chunk(monkeypatch, 4 * 4 * 15)  # chunk 15 divides 60
        chunked = np.asarray(circulant_weighted_sum(bcast, w_k, [2]))
        np.testing.assert_allclose(chunked, ref, rtol=1e-6, atol=1e-6)

    def test_dense_median_trimmed_match_unchunked(self, monkeypatch):
        """The P-chunked dense candidate map (_dense_candidate_map — the
        15.7 GB [N, m, P] gather fix) must reproduce the single-chunk
        result for both coordinate-wise rules on an irregular graph."""
        rng = np.random.default_rng(6)
        own = jnp.asarray(rng.normal(size=(6, 53)), jnp.float32)
        bcast = jnp.asarray(rng.normal(size=(6, 53)), jnp.float32)
        adj = _ring_adj(6)
        for algo, params in [("median", {}), ("trimmed_mean", {"trim_ratio": 0.34})]:
            agg = build_aggregator(algo, params)
            ref, _, ref_stats = _run(agg, own, adj, bcast=bcast)
            # m_cap defaults to n=6, so chunk = 720 // (6*6*4) = 5 -> 10
            # full chunks + tail 3 over P=53.
            self._force_chunk(monkeypatch, 6 * 3 * 4 * 10)
            chunked, _, ch_stats = _run(agg, own, adj, bcast=bcast)
            monkeypatch.undo()
            np.testing.assert_allclose(
                np.asarray(chunked), np.asarray(ref), rtol=1e-6, atol=1e-6
            )
            np.testing.assert_allclose(
                np.asarray(ch_stats["num_candidates"]),
                np.asarray(ref_stats["num_candidates"]),
            )

    def test_bf16_states_f32_weights_dtype(self, monkeypatch):
        from murmura_tpu.aggregation.base import circulant_weighted_sum

        bcast = jnp.ones((4, 40), jnp.bfloat16)
        w_k = jnp.ones((1, 4), jnp.float32) * 0.5
        self._force_chunk(monkeypatch, 4 * 2 * 16)
        out = circulant_weighted_sum(bcast, w_k, [1])
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), 0.5, atol=1e-6)


class TestFedAvg:
    def test_masked_mean(self):
        """Ring node averages itself + its two neighbors (fedavg.py:19-42)."""
        agg = build_aggregator("fedavg", {})
        own = np.arange(4, dtype=np.float32)[:, None] * np.ones((4, 3))
        new, _, stats = _run(agg, own, _ring_adj(4))
        # node 0: mean(own 0, neighbors 1 and 3) = 4/3
        np.testing.assert_allclose(np.asarray(new)[0], 4.0 / 3.0, atol=1e-6)
        assert np.asarray(stats["num_neighbors"]).tolist() == [2, 2, 2, 2]

    def test_own_state_vs_broadcast(self):
        """Aggregating node uses its own true state, neighbors' broadcasts
        (network.py:108-135)."""
        agg = build_aggregator("fedavg", {})
        own = np.zeros((3, 2), dtype=np.float32)
        bcast = np.ones((3, 2), dtype=np.float32) * 3.0
        new, _, _ = _run(agg, own, _full_adj(3), bcast=bcast)
        # each node: (0 + 3 + 3) / 3 = 2
        np.testing.assert_allclose(np.asarray(new), 2.0, atol=1e-6)


class TestKrum:
    def test_picks_planted_inlier(self):
        """Cluster of 4 near-identical states + 1 far outlier: Krum must
        select a cluster member for every honest node (krum.py:64-75)."""
        rng = np.random.default_rng(0)
        cluster = rng.normal(size=(1, 8)).astype(np.float32)
        own = np.repeat(cluster, 5, axis=0) + rng.normal(size=(5, 8)).astype(np.float32) * 0.01
        own[4] += 100.0  # outlier
        agg = build_aggregator("krum", {"num_compromised": 1})
        new, _, stats = _run(agg, own, _full_adj(5))
        winners = np.asarray(stats["selected_index"])
        assert all(w != 4 for w in winners[:4])
        for i in range(4):
            np.testing.assert_allclose(np.asarray(new)[i], own[winners[i]], atol=1e-5)

    def test_constraint_fallback_to_own(self):
        """c >= (m-2)/2 -> own state (krum.py:49-52). m=3, c=1: 1 >= 0.5."""
        own = np.random.default_rng(1).normal(size=(3, 4)).astype(np.float32)
        agg = build_aggregator("krum", {"num_compromised": 1})
        new, _, stats = _run(agg, own, _ring_adj(3))
        np.testing.assert_allclose(np.asarray(new), own, atol=1e-6)
        assert np.asarray(stats["selected_own"]).tolist() == [1.0, 1.0, 1.0]

    def test_selects_own_state_not_broadcast_of_self(self):
        """Candidate 'self' is the node's true state even when its broadcast
        differs (krum.py:45)."""
        own = np.zeros((4, 3), dtype=np.float32)
        own[1:] += np.random.default_rng(2).normal(size=(3, 3)) * 0.01
        bcast = own.copy()
        bcast[0] = 1000.0  # node 0 broadcasts garbage but keeps its true state
        agg = build_aggregator("krum", {"num_compromised": 0})
        new, _, stats = _run(agg, own, _full_adj(4), bcast=bcast)
        # node 0 should still be able to select among the close cluster
        # (its own true state is close to 1..3)
        assert np.abs(np.asarray(new)[0]).max() < 1.0

    def test_capped_candidates_match_dense(self):
        """The O(N·m²) gathered-candidate path (max_candidates = degree+1,
        injected by the factories for static graphs) must select exactly what
        the dense m = N path selects."""
        rng = np.random.default_rng(3)
        n = 12
        own = rng.normal(size=(n, 16)).astype(np.float32)
        bcast = own + rng.normal(size=(n, 16)).astype(np.float32) * 0.1
        bcast[5] += 50.0  # one Byzantine broadcast
        for adj in (_ring_adj(n), _full_adj(n)):
            max_deg = int(np.asarray(adj).sum(axis=1).max())
            dense = build_aggregator("krum", {"num_compromised": 1})
            capped = build_aggregator(
                "krum", {"num_compromised": 1, "max_candidates": max_deg + 1}
            )
            new_d, _, st_d = _run(dense, own, adj, bcast=bcast)
            new_c, _, st_c = _run(capped, own, adj, bcast=bcast)
            np.testing.assert_array_equal(
                np.asarray(st_d["selected_index"]), np.asarray(st_c["selected_index"])
            )
            np.testing.assert_allclose(np.asarray(new_d), np.asarray(new_c), atol=1e-6)

    def test_circulant_path_matches_dense(self):
        """The O(degree) delta-vector path (exchange_offsets, tpu.exchange:
        ppermute) must select exactly what the dense Gram path selects on
        the equivalent circulant adjacency."""
        rng = np.random.default_rng(7)
        n = 12
        own = rng.normal(size=(n, 16)).astype(np.float32)
        bcast = own + rng.normal(size=(n, 16)).astype(np.float32) * 0.1
        bcast[3] += 40.0
        bcast[8] -= 40.0
        # [1, 2, 10, 11] is the production form: circulant_offsets() returns
        # positive residues (np.flatnonzero of row 0), not symmetric +/-.
        for offsets in (
            [-1, 1],
            [-2, -1, 1, 2],
            [-3, -2, -1, 1, 2, 3],
            [1, 2, 10, 11],
        ):
            adj = np.zeros((n, n), dtype=np.float32)
            for i in range(n):
                for o in offsets:
                    adj[i, (i + o) % n] = 1.0
            dense = build_aggregator("krum", {"num_compromised": 1})
            circ = build_aggregator(
                "krum",
                {"num_compromised": 1, "exchange_offsets": offsets},
            )
            new_d, _, st_d = _run(dense, own, jnp.asarray(adj), bcast=bcast)
            new_c, _, st_c = _run(circ, own, jnp.asarray(adj), bcast=bcast)
            if len(offsets) == 2:
                # m=3, c=1 fails the Krum constraint: both paths keep own
                # but still report the computed argmin score (krum.py:73-75).
                np.testing.assert_allclose(np.asarray(new_c), own, atol=1e-6)
                np.testing.assert_allclose(np.asarray(new_d), own, atol=1e-6)
                np.testing.assert_allclose(
                    np.asarray(st_d["krum_score"]),
                    np.asarray(st_c["krum_score"]),
                    rtol=1e-4, atol=1e-4,
                )
                continue
            np.testing.assert_array_equal(
                np.asarray(st_d["selected_index"]),
                np.asarray(st_c["selected_index"]),
            )
            np.testing.assert_allclose(
                np.asarray(new_d), np.asarray(new_c), atol=1e-5
            )
            np.testing.assert_allclose(
                np.asarray(st_d["krum_score"]),
                np.asarray(st_c["krum_score"]),
                rtol=1e-4,
                atol=1e-4,
            )


class TestBalance:
    def test_threshold_filters_outlier(self):
        """Neighbor at distance > gamma*||own|| rejected; close neighbor
        accepted; output alpha*own + (1-alpha)*mean (balance.py:108-175)."""
        own = np.ones((3, 4), dtype=np.float32)  # ||own|| = 2
        bcast = np.stack([
            np.ones(4), np.ones(4) * 1.1, np.ones(4) * 100.0
        ]).astype(np.float32)
        adj = _full_adj(3)
        agg = build_aggregator("balance", {"gamma": 1.0, "kappa": 0.0,
                                            "alpha": 0.5, "min_neighbors": 0})
        new, _, stats = _run(agg, own, adj, bcast=bcast)
        # node 0: neighbor 1 at dist 0.2 <= 2 accepted; neighbor 2 at ~198 rejected
        np.testing.assert_allclose(np.asarray(new)[0], 0.5 * 1.0 + 0.5 * 1.1, atol=1e-5)
        assert np.asarray(stats["acceptance_rate"])[0] == pytest.approx(0.5)

    def test_fallback_accepts_closest(self):
        """No neighbor passes -> closest accepted when min_neighbors=1
        (balance.py:133-135)."""
        own = np.zeros((2, 4), dtype=np.float32)
        bcast = np.stack([np.zeros(4), np.ones(4) * 50.0]).astype(np.float32)
        agg = build_aggregator("balance", {"gamma": 0.001, "min_neighbors": 1,
                                            "alpha": 0.5})
        new, _, _ = _run(agg, own, _full_adj(2), bcast=bcast)
        # node 0's only neighbor (dist 100) fails threshold but is the
        # closest -> accepted: 0.5*0 + 0.5*50
        np.testing.assert_allclose(np.asarray(new)[0], 25.0, atol=1e-4)

    def test_threshold_tightens_over_rounds(self):
        agg = build_aggregator("balance", {"gamma": 2.0, "kappa": 1.0})
        own = np.ones((2, 4), dtype=np.float32)
        _, _, s0 = _run(agg, own, _full_adj(2), round_idx=0, ctx=_ctx(10))
        _, _, s9 = _run(agg, own, _full_adj(2), round_idx=9, ctx=_ctx(10))
        assert np.asarray(s9["threshold"])[0] < np.asarray(s0["threshold"])[0]


class TestSketchguard:
    def test_filters_outlier_via_sketches(self):
        dim = 64
        agg = build_aggregator(
            "sketchguard",
            {"sketch_size": 32, "gamma": 1.0, "kappa": 0.0, "alpha": 0.5,
             "min_neighbors": 0},
            model_dim=dim,
        )
        own = np.ones((3, dim), dtype=np.float32)
        bcast = own.copy()
        bcast[2] *= 100.0
        new, state, stats = _run(agg, own, _full_adj(3), bcast=bcast)
        # honest nodes 0,1 accept each other, reject inflated node 2
        assert np.asarray(stats["acceptance_rate"])[0] == pytest.approx(0.5)
        np.testing.assert_allclose(np.asarray(new)[0], 1.0, atol=1e-5)
        assert np.asarray(stats["compression_ratio"])[0] == pytest.approx(2.0)

    def test_attack_window_boosts_threshold(self):
        dim = 16
        agg = build_aggregator(
            "sketchguard",
            {"sketch_size": 8, "gamma": 1.0, "kappa": 0.0},
            model_dim=dim,
        )
        own = np.ones((2, dim), dtype=np.float32)
        # window full of low acceptance -> 1.5x threshold boost
        state = {
            "acc_window": jnp.zeros((2, 5), jnp.float32),
            "window_len": jnp.full((2,), 5, jnp.int32),
        }
        _, _, stats_boost = _run(agg, own, _full_adj(2), state=state)
        fresh = {k: jnp.asarray(v) for k, v in agg.init_state(2).items()}
        _, _, stats_plain = _run(agg, own, _full_adj(2), state=fresh)
        assert np.asarray(stats_boost["threshold"])[0] == pytest.approx(
            1.5 * np.asarray(stats_plain["threshold"])[0]
        )

    def test_window_state_rolls(self):
        dim = 16
        agg = build_aggregator("sketchguard", {"sketch_size": 8}, model_dim=dim)
        own = np.ones((2, dim), dtype=np.float32)
        _, state, _ = _run(agg, own, _full_adj(2))
        assert np.asarray(state["window_len"]).tolist() == [1, 1]
        assert np.asarray(state["acc_window"])[:, -1].tolist() == [1.0, 1.0]


def _probe_ctx(n, num_classes=4, batch=6):
    """Context whose apply_fn reads logits straight from the flat params:
    model j's logits on any sample = flat_j[:K].  Lets tests dictate each
    model's probe loss exactly."""
    probe_x = jnp.zeros((n, batch, 2), jnp.float32)
    probe_y = jnp.zeros((n, batch), jnp.int32)  # true class always 0
    probe_mask = jnp.ones((n, batch), jnp.float32)

    def apply_fn(params, x, key, train):
        return jnp.tile(params[:num_classes][None, :], (x.shape[0], 1))

    return AggContext(
        apply_fn=apply_fn,
        unravel=lambda flat: flat,
        probe_x=probe_x,
        probe_y=probe_y,
        probe_mask=probe_mask,
        num_classes=num_classes,
        total_rounds=10,
    )


class TestUBAR:
    def test_two_stage_selection(self):
        """Stage 1 shortlists closest rho*deg; stage 2 keeps loss <= own
        (ubar.py:114-202)."""
        n, k = 4, 4
        ctx = _probe_ctx(n, num_classes=k)
        # flat[:4] are the logits; class 0 is the target.
        good = np.array([5.0, 0.0, 0.0, 0.0] + [0.0] * 4, dtype=np.float32)
        bad = np.array([-5.0, 5.0, 0.0, 0.0] + [0.0] * 4, dtype=np.float32)
        own = np.stack([good, good * 0.9, bad, good * 1.1]).astype(np.float32)
        agg = build_aggregator("ubar", {"rho": 1.0, "alpha": 0.5})
        new, _, stats = _run(agg, own, _full_adj(n), ctx=ctx)
        # node 0: neighbor 3 (logits 1.1x -> lower CE loss than own) passes
        # stage 2; neighbor 1 (0.9x -> higher loss) and neighbor 2 (bad) are
        # rejected (accept iff loss <= own loss, ubar.py:191).
        expected = 0.5 * own[0] + 0.5 * own[3]
        np.testing.assert_allclose(np.asarray(new)[0], expected, atol=1e-5)

    def test_stage2_fallback_best_loss(self):
        """None pass stage 2 -> best-loss shortlisted accepted (ubar.py:195-197)."""
        n, k = 3, 4
        ctx = _probe_ctx(n, num_classes=k)
        best = np.array([9.0, 0, 0, 0, 0, 0, 0, 0], dtype=np.float32)
        mid = np.array([4.0, 0, 0, 0, 0, 0, 0, 0], dtype=np.float32)
        worst = np.array([0.0, 5.0, 0, 0, 0, 0, 0, 0], dtype=np.float32)
        own = np.stack([best, mid, worst]).astype(np.float32)
        agg = build_aggregator("ubar", {"rho": 1.0, "alpha": 0.5})
        new, _, _ = _run(agg, own, _full_adj(n), ctx=ctx)
        # node 0 has the lowest loss; no neighbor beats it -> fallback to
        # the best neighbor (node 1): 0.5*best + 0.5*mid
        np.testing.assert_allclose(
            np.asarray(new)[0], 0.5 * best + 0.5 * mid, atol=1e-5
        )

    def test_stage1_rank_count(self):
        n, k = 5, 4
        ctx = _probe_ctx(n, num_classes=k)
        own = np.random.default_rng(3).normal(size=(n, 8)).astype(np.float32)
        agg = build_aggregator("ubar", {"rho": 0.5, "min_neighbors": 1})
        _, _, stats = _run(agg, own, _full_adj(n), ctx=ctx)
        # deg = 4, rho*deg = 2 shortlisted of 4 -> stage1 rate 0.5
        np.testing.assert_allclose(np.asarray(stats["stage1_acceptance_rate"]), 0.5)


def _evidential_ctx(n, num_classes=4, batch=6):
    """apply_fn yields alphas = softplus(flat[:K]) + 1 so tests control
    evidence/vacuity/accuracy directly."""
    probe_x = jnp.zeros((n, batch, 2), jnp.float32)
    probe_y = jnp.zeros((n, batch), jnp.int32)
    probe_mask = jnp.ones((n, batch), jnp.float32)

    def apply_fn(params, x, key, train):
        alpha = jax.nn.softplus(params[:num_classes]) + 1.0
        return jnp.tile(alpha[None, :], (x.shape[0], 1))

    return AggContext(
        apply_fn=apply_fn,
        unravel=lambda flat: flat,
        probe_x=probe_x,
        probe_y=probe_y,
        probe_mask=probe_mask,
        evidential=True,
        num_classes=num_classes,
        total_rounds=10,
    )


class TestEvidentialTrust:
    def test_high_vacuity_neighbor_filtered(self):
        """Low-evidence (vacuous) neighbor scores below threshold and is
        excluded; confident accurate neighbor dominates
        (evidential_trust.py:289-305)."""
        n, k = 3, 4
        ctx = _evidential_ctx(n, num_classes=k)
        confident = np.array([20.0, -20, -20, -20] + [0.0] * 4, np.float32)
        vacuous = np.array([-20.0, -20, -20, -20] + [0.0] * 4, np.float32)
        own = np.stack([confident, confident * 1.01, vacuous]).astype(np.float32)
        agg = build_aggregator(
            "evidential_trust",
            {"trust_threshold": 0.3, "use_tightening_threshold": False,
             "use_adaptive_trust": False, "self_weight": 0.5,
             "strength_guard": False},
        )
        new, _, stats = _run(agg, own, _full_adj(n), ctx=ctx)
        # node 0 accepts only node 1 -> 0.5*own + 0.5*neighbor1
        np.testing.assert_allclose(
            np.asarray(new)[0], 0.5 * own[0] + 0.5 * own[1], atol=1e-4
        )
        assert np.asarray(stats["acceptance_rate"])[0] == pytest.approx(0.5)

    def test_none_accepted_returns_own(self):
        n, k = 2, 4
        ctx = _evidential_ctx(n, num_classes=k)
        vacuous = np.array([-20.0, -20, -20, -20, 0, 0, 0, 0], np.float32)
        own = np.stack([vacuous, vacuous * 1.1]).astype(np.float32)
        agg = build_aggregator(
            "evidential_trust",
            {"trust_threshold": 0.9, "use_tightening_threshold": False,
             "strength_guard": False},
        )
        new, _, _ = _run(agg, own, _full_adj(n), ctx=ctx)
        np.testing.assert_allclose(np.asarray(new), own, atol=1e-5)

    def test_ema_smoothing_state(self):
        """trust_t = momentum*new + (1-momentum)*old after first observation
        (evidential_trust.py:318-342)."""
        n, k = 2, 4
        ctx = _evidential_ctx(n, num_classes=k)
        confident = np.array([20.0, -20, -20, -20, 0, 0, 0, 0], np.float32)
        own = np.stack([confident, confident]).astype(np.float32)
        agg = build_aggregator(
            "evidential_trust",
            {"trust_momentum": 0.7, "use_tightening_threshold": False,
             "strength_guard": False},
        )
        _, state1, s1 = _run(agg, own, _full_adj(n), ctx=ctx)
        t1 = np.asarray(state1["smoothed_trust"])[0, 1]
        # second round, same inputs: smoothed = 0.7*t + 0.3*t = t (fixed point)
        _, state2, _ = _run(agg, own, _full_adj(n), state=state1, ctx=_evidential_ctx(n))
        t2 = np.asarray(state2["smoothed_trust"])[0, 1]
        assert t2 == pytest.approx(t1, abs=1e-5)
        assert np.asarray(state1["trust_seen"])[0, 1] == 1.0

    def test_strength_guard_rejects_inflated(self):
        """Neighbor with evidence >> median neighborhood strength gets zero
        trust (documented robustness extension)."""
        n, k = 4, 4
        ctx = _evidential_ctx(n, num_classes=k)
        normal = np.array([2.0, 1.0, 1.0, 1.0, 0, 0, 0, 0], np.float32)
        inflated = np.array([5000.0, 5000, 5000, 5000, 0, 0, 0, 0], np.float32)
        own = np.stack([normal, normal * 1.01, normal * 0.99, inflated]).astype(
            np.float32
        )
        agg = build_aggregator(
            "evidential_trust",
            {"trust_threshold": 0.05, "use_tightening_threshold": False,
             "use_adaptive_trust": False, "strength_guard": True,
             "strength_guard_factor": 10.0},
        )
        _, _, stats = _run(agg, own, _full_adj(n), ctx=ctx)
        # honest node 0: neighbors 1,2 accepted, 3 (inflated) rejected
        assert np.asarray(stats["acceptance_rate"])[0] == pytest.approx(2.0 / 3.0)


class TestUnknownAlgorithm:
    def test_raises(self):
        with pytest.raises(ValueError):
            build_aggregator("median_of_means", {})


class TestRobustStats:
    """Beyond-parity rules: coordinate-wise median / trimmed mean
    (robust_stats.py; no reference counterpart)."""

    def test_median_ignores_extreme_minority(self):
        # 4 nodes fully connected: candidates everywhere = all 4 states.
        # One Byzantine broadcast at +1000 cannot move the median of 4
        # values beyond the span of the honest 3.
        own = np.array([[1.0], [2.0], [3.0], [1000.0]], dtype=np.float32)
        agg = build_aggregator("median", {})
        new, _, stats = _run(agg, own, _full_adj(4))
        # median of {1,2,3,1000} = (2+3)/2 = 2.5 for every node
        np.testing.assert_allclose(np.asarray(new), 2.5, atol=1e-6)
        assert np.asarray(stats["num_candidates"]).tolist() == [4.0] * 4

    def test_median_respects_topology_and_own_state(self):
        # Ring of 4: node 0's candidates = {own_0, bcast_1, bcast_3}.
        own = np.array([[0.0], [10.0], [20.0], [30.0]], dtype=np.float32)
        bcast = own.copy()
        agg = build_aggregator("median", {})
        new, _, _ = _run(agg, own, _ring_adj(4), bcast=bcast)
        # node 0: median{0,10,30} = 10; node 1: median{10,0,20} = 10
        np.testing.assert_allclose(np.asarray(new)[:2, 0], [10.0, 10.0], atol=1e-6)

    def test_median_uses_own_true_state_not_broadcast(self):
        own = np.zeros((3, 2), dtype=np.float32)
        bcast = own.copy()
        bcast[0] = 500.0  # node 0 lies outward but keeps its true state
        agg = build_aggregator("median", {})
        new, _, _ = _run(agg, own, _full_adj(3), bcast=bcast)
        # node 0's own candidate is its true 0-state: median{0,0,0} = 0
        np.testing.assert_allclose(np.asarray(new)[0], 0.0, atol=1e-6)

    def test_trimmed_mean_drops_tails(self):
        own = np.array([[0.0], [1.0], [2.0], [3.0], [1000.0]], dtype=np.float32)
        # beta=0.2, cnt=5 -> trim 1 per side: mean{1,2,3} = 2 everywhere
        agg = build_aggregator("trimmed_mean", {"trim_ratio": 0.2})
        new, _, stats = _run(agg, own, _full_adj(5))
        np.testing.assert_allclose(np.asarray(new), 2.0, atol=1e-5)
        assert np.asarray(stats["trimmed_per_side"]).tolist() == [1.0] * 5

    def test_trimmed_mean_zero_trim_is_masked_mean(self):
        rng = np.random.default_rng(4)
        own = rng.normal(size=(5, 8)).astype(np.float32)
        agg = build_aggregator("trimmed_mean", {"trim_ratio": 0.0})
        new, _, _ = _run(agg, own, _ring_adj(5))
        for i in range(5):
            expect = own[[i, (i - 1) % 5, (i + 1) % 5]].mean(axis=0)
            np.testing.assert_allclose(np.asarray(new)[i], expect, atol=1e-5)

    def test_capped_candidates_match_dense(self):
        rng = np.random.default_rng(5)
        n = 10
        own = rng.normal(size=(n, 6)).astype(np.float32)
        adj = _ring_adj(n)
        for algo in ("median", "trimmed_mean"):
            dense = build_aggregator(algo, {})
            capped = build_aggregator(algo, {"max_candidates": 3})
            new_d, _, _ = _run(dense, own, adj)
            new_c, _, _ = _run(capped, own, adj)
            np.testing.assert_allclose(
                np.asarray(new_d), np.asarray(new_c), atol=1e-6
            )


class TestGeometricMedian:
    """Beyond-parity rule #3: smoothed-Weiszfeld geometric median (RFA,
    robust_stats.py make_geometric_median; no reference counterpart)."""

    def test_outlier_minority_cannot_drag_the_median(self):
        # 5 nodes fully connected, one Byzantine at +1000: the geometric
        # median of {0,1,2,3,1000} stays inside the honest cluster's span.
        own = np.array([[0.0], [1.0], [2.0], [3.0], [1000.0]],
                        dtype=np.float32)
        agg = build_aggregator("geometric_median", {"max_iters": 32})
        new, _, stats = _run(agg, own, _full_adj(5))
        vals = np.asarray(new)[:, 0]
        assert (vals > 0.0).all() and (vals < 4.0).all(), vals
        assert np.asarray(stats["num_candidates"]).tolist() == [5.0] * 5

    def test_majority_cluster_wins_exactly(self):
        # 3 candidates, two identical: the geometric median of a
        # 2-vs-1 split is the majority point.
        own = np.zeros((3, 4), dtype=np.float32)
        bcast = own.copy()
        bcast[2] = 100.0  # single outlier broadcast
        agg = build_aggregator("geometric_median", {"max_iters": 64})
        new, _, _ = _run(agg, own, _full_adj(3), bcast=bcast)
        np.testing.assert_allclose(np.asarray(new)[0], 0.0, atol=1e-2)

    def test_rotation_invariance_vs_coordinate_median(self):
        # The property the coordinate-wise median lacks: rotating the
        # candidate cloud rotates the geometric median with it.
        rng = np.random.default_rng(6)
        own = rng.normal(size=(4, 2)).astype(np.float32)
        theta = 0.7
        rot = np.array([[np.cos(theta), -np.sin(theta)],
                         [np.sin(theta), np.cos(theta)]], dtype=np.float32)
        agg = build_aggregator("geometric_median", {"max_iters": 64})
        new, _, _ = _run(agg, own, _full_adj(4))
        new_rot, _, _ = _run(agg, own @ rot.T, _full_adj(4))
        np.testing.assert_allclose(
            np.asarray(new) @ rot.T, np.asarray(new_rot), atol=1e-3
        )

    def test_respects_topology_and_own_true_state(self):
        own = np.zeros((3, 2), dtype=np.float32)
        bcast = own.copy()
        bcast[0] = 500.0  # node 0 lies outward but keeps its true state
        agg = build_aggregator("geometric_median", {"max_iters": 32})
        new, _, _ = _run(agg, own, _full_adj(3), bcast=bcast)
        # node 0's own candidate is its true 0-state: gm{0,0,0} = 0
        np.testing.assert_allclose(np.asarray(new)[0], 0.0, atol=1e-4)

    def test_capped_candidates_match_dense(self):
        rng = np.random.default_rng(7)
        n = 10
        own = rng.normal(size=(n, 6)).astype(np.float32)
        adj = _ring_adj(n)
        dense = build_aggregator("geometric_median", {})
        capped = build_aggregator("geometric_median", {"max_candidates": 3})
        new_d, _, _ = _run(dense, own, adj)
        new_c, _, _ = _run(capped, own, adj)
        np.testing.assert_allclose(
            np.asarray(new_d), np.asarray(new_c), atol=1e-5
        )

    def test_weight_concentration_telemetry(self):
        # Under a huge outlier the final Weiszfeld weights concentrate on
        # the honest cluster: max share rises well above the uniform 1/cnt.
        own = np.zeros((4, 3), dtype=np.float32)
        bcast = own.copy()
        bcast[3] = 1000.0
        agg = build_aggregator("geometric_median", {"max_iters": 32})
        _, _, stats = _run(agg, own, _full_adj(4), bcast=bcast)
        share = np.asarray(stats["max_weight_share"])
        assert (share[:3] > 0.3).all(), share  # honest nodes: ~1/3 each over 3 near-identical

    def test_bf16_matches_f32_within_tolerance(self):
        """tpu.param_dtype auto-default: >= 64 nodes store bf16 resident
        states, but the Weiszfeld iterate (robust_stats.py dense Gram path)
        accumulates distances and weighted means in f32 regardless of input
        dtype.  The bf16 result must therefore land within bf16
        quantization of the f32 result: rtol 1/128 (8-bit mantissa -> one
        part in 2^8, taken x2 for the final-store rounding of inputs AND
        output) plus a matching atol for near-zero coordinates.  Future
        nu/iters changes that break f32 accumulation show up here as a
        gross (not 1-ulp) divergence."""
        rng = np.random.default_rng(11)
        n, p = 8, 96
        own = rng.normal(size=(n, p)).astype(np.float32)
        bcast = own + 0.1 * rng.normal(size=(n, p)).astype(np.float32)
        bcast[2] += 50.0  # one outlier so the reweighting actually ranks
        adj = _full_adj(n)
        agg = build_aggregator("geometric_median", {"max_iters": 16})
        z32, _, _ = agg.aggregate(
            jnp.asarray(own), jnp.asarray(bcast), adj,
            jnp.asarray(0.0), {}, _ctx(),
        )
        z16, _, _ = agg.aggregate(
            jnp.asarray(own, jnp.bfloat16), jnp.asarray(bcast, jnp.bfloat16),
            adj, jnp.asarray(0.0), {}, _ctx(),
        )
        assert z16.dtype == jnp.bfloat16  # stored in the resident dtype
        np.testing.assert_allclose(
            np.asarray(z16, dtype=np.float32), np.asarray(z32),
            rtol=2 / 128, atol=2 / 128,
        )

    def test_self_edges_in_adjacency_are_ignored(self):
        """The uncapped Gram path zeroes the adjacency diagonal locally
        (ISSUE-1 satellite): a stray self-edge must not double-count the
        node's own state, so diag-1 and diag-0 adjacencies agree."""
        rng = np.random.default_rng(12)
        own = rng.normal(size=(5, 7)).astype(np.float32)
        bcast = own + rng.normal(size=(5, 7)).astype(np.float32)
        adj_clean = _full_adj(5)
        adj_selfy = jnp.asarray(np.asarray(adj_clean) + np.eye(5, dtype=np.float32))
        agg = build_aggregator("geometric_median", {"max_iters": 16})
        z_clean, _, _ = _run(agg, own, adj_clean, bcast=bcast)
        z_selfy, _, _ = _run(agg, own, adj_selfy, bcast=bcast)
        np.testing.assert_allclose(
            np.asarray(z_selfy), np.asarray(z_clean), atol=1e-5
        )

    def test_config_wiring_learns_under_attack(self):
        # Full config -> factories -> network path: schema accepts the
        # algorithm, factories inject max_candidates on static graphs, and
        # the network keeps learning with 25% gaussian Byzantine nodes.
        from murmura_tpu.config import Config
        from murmura_tpu.utils.factories import build_network_from_config

        cfg = Config.model_validate(
            {
                "experiment": {"name": "gm", "seed": 3, "rounds": 3},
                "topology": {"type": "ring", "num_nodes": 8},
                "aggregation": {"algorithm": "geometric_median",
                                 "params": {"max_iters": 8}},
                "attack": {"enabled": True, "type": "gaussian",
                            "percentage": 0.25,
                            "params": {"noise_std": 10.0}},
                "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.1},
                "data": {"adapter": "synthetic",
                          "params": {"num_samples": 640, "input_dim": 24,
                                     "num_classes": 4}},
                "model": {"factory": "mlp",
                           "params": {"input_dim": 24, "hidden_dims": [32],
                                      "num_classes": 4}},
                "backend": "simulation",
                "tpu": {"compute_dtype": "float32"},
            }
        )
        hist = build_network_from_config(cfg).train(rounds=3)
        assert hist["honest_accuracy"][-1] > 0.5, hist["honest_accuracy"]

    def test_zero_smoothing_rejected_at_build_time(self):
        import pytest

        with pytest.raises(ValueError, match="smoothing"):
            build_aggregator("geometric_median", {"smoothing": 0.0})

    def test_circulant_path_matches_dense_on_ring(self):
        # tpu.exchange: ppermute serves geometric_median too: the rolled
        # Weiszfeld recursion must agree with the dense candidate-tensor
        # path on the same circulant graph.
        rng = np.random.default_rng(8)
        n = 8
        own = rng.normal(size=(n, 6)).astype(np.float32)
        bcast = own + rng.normal(size=(n, 6)).astype(np.float32) * 0.1
        dense = build_aggregator("geometric_median", {"max_iters": 16})
        circ = build_aggregator(
            "geometric_median",
            {"max_iters": 16, "exchange_offsets": [-1, 1]},
        )
        new_d, _, stats_d = _run(dense, own, _ring_adj(n), bcast=bcast)
        new_c, _, stats_c = _run(circ, own, _ring_adj(n), bcast=bcast)
        np.testing.assert_allclose(
            np.asarray(new_d), np.asarray(new_c), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(stats_d["max_weight_share"]),
            np.asarray(stats_c["max_weight_share"]), atol=1e-5,
        )


class TestSatelliteGuards:
    """ISSUE-1 satellite regressions: explicit probe-offset guard and the
    f32-floored circulant chunk budget."""

    def test_circulant_probe_eval_rejects_empty_offsets(self):
        from murmura_tpu.aggregation.probe import circulant_probe_eval

        with pytest.raises(ValueError, match="at least one offset"):
            circulant_probe_eval(
                jnp.zeros((4, 8)), [], _ctx(), lambda o, y, m: {"loss": 0.0}
            )

    def test_p_chunk_len_budgets_f32_for_bf16(self):
        """bf16 programs accumulate chunks in f32, so the chunk budget must
        use the f32 itemsize — bf16 and f32 inputs get the same chunk."""
        from murmura_tpu.aggregation.base import (
            _CIRCULANT_CHUNK_BYTES,
            _p_chunk_len,
        )

        n, p = 256, 10_000_000
        assert _p_chunk_len(n, p, 2) == _p_chunk_len(n, p, 4)
        assert _p_chunk_len(n, p, 4) == _CIRCULANT_CHUNK_BYTES // (n * 4)
        # f64 (itemsize 8) still scales down, and tiny programs still get
        # the single-chunk exact path.
        assert _p_chunk_len(n, p, 8) == _CIRCULANT_CHUNK_BYTES // (n * 8)
        assert _p_chunk_len(4, 128, 2) == 128
