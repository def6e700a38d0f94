"""Bounded-staleness gossip (murmura_tpu/core/stale.py; ISSUE 13).

Covers the acceptance surface of docs/ROBUSTNESS.md "Bounded staleness":

- default-off byte-identity: a config without an ``exchange`` block and
  one with ``max_staleness: 0`` produce byte-identical traced programs
  AND histories;
- schema fail-louds (discount without bound, staleness without faults,
  the distributed/dmtt/mobility/one_peer/population rejections);
- fold semantics (unit-level, dense AND sparse): disrupted senders are
  served from cache with the discounted weight, fresh payloads pass
  through and update the cache, ages expire to drop-the-edge, the scrub
  gate withholds a caught row's cached copy, link-dropped edges of a
  delivering sender stay dropped, and the sparse fold bit-matches the
  dense fold on the same circulant graph;
- end-to-end runs: stale edges actually served under a straggler/link
  schedule, zero-probability faults leave stale-on == stale-off
  byte-identical, fused == per-round, int8+EF x sparse-exponential
  composition;
- durability: the MUR901/902 ``stale`` grid cell (save -> restore ->
  replay byte-equality with a populated cache; the crash matrix lives in
  tests/test_durability.py);
- MUR1100-1103 representative cells clean + negatives proving each
  probe can fire (broken registry, a fold that leaks the replay hole).
"""

import numpy as np
import pytest

from murmura_tpu.config import Config
from murmura_tpu.core.stale import (
    AGE_KEY,
    CACHE_KEY,
    STALE_STATE_KEYS,
    StalenessSpec,
    init_stale_state,
    make_stale_fold,
)
from murmura_tpu.utils.factories import build_network_from_config


def _raw(**over):
    raw = {
        "experiment": {"name": "stale", "seed": 3, "rounds": 8},
        "topology": {"type": "k-regular", "num_nodes": 8, "k": 4},
        "aggregation": {"algorithm": "krum"},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {
            "adapter": "synthetic",
            "params": {"num_samples": 320, "input_dim": 16,
                       "num_classes": 4},
        },
        "model": {
            "factory": "mlp",
            "params": {"input_dim": 16, "hidden_dims": [16],
                       "num_classes": 4},
        },
        "backend": "simulation",
    }
    for k, v in over.items():
        raw[k] = v
    return raw


def _cfg(**over):
    return Config.model_validate(_raw(**over))


FAULTS = {"enabled": True, "straggler_prob": 0.4, "link_drop_prob": 0.1,
          "seed": 11}


# ---------------------------------------------------------------------------
# Default-off byte-identity
# ---------------------------------------------------------------------------


class TestDefaultOffByteIdentity:
    def test_history_identical_without_and_with_default_block(self):
        h0 = build_network_from_config(_cfg(faults=FAULTS)).train(rounds=4)
        h1 = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 0})
        ).train(rounds=4)
        assert h0 == h1

    def test_traced_program_identical(self):
        """The acceptance bar is PROGRAM identity, not just history
        identity: with the block absent the jaxpr (and therefore the
        compiled executable) must be byte-identical to main."""
        import jax
        import jax.numpy as jnp

        def jaxpr_of(cfg):
            net = build_network_from_config(cfg)
            prog = net.program
            n = prog.num_nodes
            args = [
                prog.init_params,
                {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
                jax.random.PRNGKey(0),
                jnp.asarray(net._adjacency_for_round(0)),
                jnp.asarray(net.compromised),
                jnp.ones((n,), jnp.float32),
                jnp.asarray(0.0, jnp.float32),
                {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
            ]
            import re

            # Function reprs embed memory addresses (``at 0x...``) that
            # differ between builds of the same program; the structural
            # text is the identity subject.
            return re.sub(
                r"0x[0-9a-f]+", "0x",
                str(jax.make_jaxpr(prog.train_step)(*args)),
            )

        assert jaxpr_of(_cfg(faults=FAULTS)) == jaxpr_of(
            _cfg(faults=FAULTS, exchange={"max_staleness": 0})
        )


# ---------------------------------------------------------------------------
# Schema fail-louds
# ---------------------------------------------------------------------------


class TestExchangeConfig:
    def test_discount_without_bound_rejected(self):
        with pytest.raises(Exception, match="staleness_discount"):
            _cfg(exchange={"max_staleness": 0, "staleness_discount": 0.5})

    def test_requires_faults(self):
        with pytest.raises(Exception, match="faults.enabled"):
            _cfg(exchange={"max_staleness": 2})

    def test_distributed_rejected(self):
        with pytest.raises(Exception, match="distributed"):
            _cfg(backend="distributed", faults=FAULTS,
                 exchange={"max_staleness": 2})

    def test_mobility_rejected(self):
        with pytest.raises(Exception, match="mobility"):
            _cfg(faults=FAULTS, exchange={"max_staleness": 2},
                 mobility={"comm_range": 40.0})

    def test_one_peer_rejected(self):
        with pytest.raises(Exception, match="one_peer"):
            _cfg(faults=FAULTS, exchange={"max_staleness": 2},
                 topology={"type": "one_peer", "num_nodes": 8})

    def test_population_rejected(self):
        with pytest.raises(Exception, match="population"):
            _cfg(faults=FAULTS, exchange={"max_staleness": 2},
                 population={"enabled": True, "virtual_size": 64})

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="max_staleness"):
            StalenessSpec(max_staleness=0)
        with pytest.raises(ValueError, match="staleness_discount"):
            StalenessSpec(max_staleness=1, discount=1.5)


# ---------------------------------------------------------------------------
# Fold semantics (unit level)
# ---------------------------------------------------------------------------


def _ring4(n=6):
    """k-regular(2) circulant via offsets {1, n-1} as a dense mask."""
    base = np.zeros((n, n), np.float32)
    for i in range(n):
        base[i, (i + 1) % n] = 1.0
        base[i, (i - 1) % n] = 1.0
    return base


class TestFoldSemantics:
    def _fold(self, base, max_staleness=2, discount=0.5, offsets=()):
        spec = StalenessSpec(
            max_staleness=max_staleness, discount=discount, base_mask=base
        )
        return spec, make_stale_fold(spec, sparse_offsets=offsets)

    def test_disrupted_sender_served_from_cache_with_discount(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        spec, fold = self._fold(base)
        bcast = jnp.asarray(np.arange(n * p, dtype=np.float32).reshape(n, p))
        cache = jnp.asarray(-np.ones((n, p), np.float32))
        age = jnp.zeros((n,), jnp.float32)
        adj = base.copy()
        adj[:, 2] = 0.0  # sender 2 straggles: column dark
        ones = jnp.ones((n,), jnp.float32)
        b_eff, a_eff, upd, stats = fold(
            bcast, jnp.asarray(adj), {CACHE_KEY: cache, AGE_KEY: age},
            ones, ones,
        )
        b_eff, a_eff = np.asarray(b_eff), np.asarray(a_eff)
        # Sender 2's row substituted by its cache; everyone else fresh.
        np.testing.assert_array_equal(b_eff[2], -np.ones(p))
        np.testing.assert_array_equal(
            np.delete(b_eff, 2, axis=0), np.delete(np.asarray(bcast), 2, 0)
        )
        # Its base in-edges re-added at discount**1.
        receivers = np.nonzero(base[:, 2])[0]
        np.testing.assert_allclose(a_eff[receivers, 2], 0.5)
        # Cache advances: fresh rows adopted, stale row kept; ages track.
        upd_cache = np.asarray(upd[CACHE_KEY])
        np.testing.assert_array_equal(upd_cache[2], -np.ones(p))
        np.testing.assert_array_equal(upd_cache[0], np.asarray(bcast)[0])
        np.testing.assert_array_equal(
            np.asarray(upd[AGE_KEY]),
            np.asarray([0, 0, 1, 0, 0, 0], np.float32),
        )
        assert float(stats["stale_used"]) == len(receivers)
        assert float(stats["stale_expired"]) == 0.0

    def test_age_past_bound_degrades_to_drop(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        spec, fold = self._fold(base, max_staleness=1)
        adj = base.copy()
        adj[:, 2] = 0.0
        age = np.zeros((n,), np.float32)
        age[2] = 1.0  # already 1 round old -> age_new = 2 > bound
        ones = jnp.ones((n,), jnp.float32)
        _, a_eff, upd, stats = fold(
            jnp.zeros((n, p)), jnp.asarray(adj),
            {CACHE_KEY: jnp.ones((n, p)), AGE_KEY: jnp.asarray(age)},
            ones, ones,
        )
        assert np.asarray(a_eff)[:, 2].sum() == 0.0  # edge stays dropped
        assert float(stats["stale_used"]) == 0.0
        assert float(stats["stale_expired"]) == float(base[:, 2].sum())
        # Age saturates at the cap (exact small ints forever).
        assert np.asarray(upd[AGE_KEY])[2] == spec.age_cap

    def test_scrub_gate_withholds_cache_and_blocks_adoption(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        _, fold = self._fold(base)
        adj = base.copy()
        adj[:, 2] = 0.0  # the sentinel zeroed the scrubbed column
        scrub = np.ones((n,), np.float32)
        scrub[2] = 0.0
        poisoned = jnp.full((n, p), 7.0)
        old_cache = jnp.full((n, p), -3.0)
        ones = jnp.ones((n,), jnp.float32)
        _, a_eff, upd, stats = fold(
            poisoned, jnp.asarray(adj),
            {CACHE_KEY: old_cache, AGE_KEY: jnp.zeros((n,))},
            ones, jnp.asarray(scrub),
        )
        # Neither served (the replay hole) ...
        assert np.asarray(a_eff)[:, 2].sum() == 0.0
        # ... nor adopted into the cache (the poisoned broadcast).
        np.testing.assert_array_equal(
            np.asarray(upd[CACHE_KEY])[2], np.full(p, -3.0)
        )
        # A scrub-withheld sender is NOT "expired": its cache is fresh
        # enough, just quarantined for the round — the expiry counter is
        # the AGE signal, not a catch-all.
        assert float(stats["stale_expired"]) == 0.0

    def test_round0_empty_cache_not_served(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        spec, fold = self._fold(base)
        adj = base.copy()
        adj[:, 4] = 0.0
        init = init_stale_state(spec, n, p, np.float32)
        ones = jnp.ones((n,), jnp.float32)
        _, a_eff, _, stats = fold(
            jnp.zeros((n, p)), jnp.asarray(adj),
            {k: jnp.asarray(v) for k, v in init.items()}, ones, ones,
        )
        assert np.asarray(a_eff)[:, 4].sum() == 0.0
        assert float(stats["stale_used"]) == 0.0

    def test_link_dropped_edge_of_delivering_sender_stays_dropped(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        _, fold = self._fold(base)
        adj = base.copy()
        adj[0, 1] = 0.0  # one link drop; sender 1 still delivers to 2
        ones = jnp.ones((n,), jnp.float32)
        b_eff, a_eff, _, stats = fold(
            jnp.ones((n, p)), jnp.asarray(adj),
            {CACHE_KEY: jnp.zeros((n, p)), AGE_KEY: jnp.zeros((n,))},
            ones, ones,
        )
        # One payload version per sender: the fresh version did not
        # cross this edge, so the edge stays dropped for the round.
        assert np.asarray(a_eff)[0, 1] == 0.0
        assert float(stats["stale_used"]) == 0.0

    def test_dead_receiver_gets_no_readded_edges(self):
        import jax.numpy as jnp

        n, p = 6, 3
        base = _ring4(n)
        _, fold = self._fold(base)
        adj = base.copy()
        adj[:, 2] = 0.0   # stale sender
        adj[1, :] = 0.0   # receiver 1 is dead (alive fold zeroed its row)
        alive = np.ones((n,), np.float32)
        alive[1] = 0.0
        ones = jnp.ones((n,), jnp.float32)
        _, a_eff, _, _ = fold(
            jnp.ones((n, p)), jnp.asarray(adj),
            {CACHE_KEY: jnp.zeros((n, p)), AGE_KEY: jnp.zeros((n,))},
            jnp.asarray(alive), ones,
        )
        assert np.asarray(a_eff)[1].sum() == 0.0

    def test_wrong_width_base_mask_refused_at_trace(self):
        import jax.numpy as jnp

        n, p = 6, 3
        spec = StalenessSpec(2, 0.5, base_mask=np.zeros((4, 4), np.float32))
        fold = make_stale_fold(spec)
        ones = jnp.ones((n,), jnp.float32)
        with pytest.raises(ValueError, match="node axis"):
            fold(
                jnp.zeros((n, p)), jnp.asarray(_ring4(n)),
                {CACHE_KEY: jnp.zeros((n, p)), AGE_KEY: jnp.zeros((n,))},
                ones, ones,
            )

    def test_sparse_base_mask_rank_refused(self):
        spec = StalenessSpec(
            2, 0.5, base_mask=np.ones((3, 8), np.float32)
        )
        with pytest.raises(ValueError, match=r"\[k, N\]"):
            make_stale_fold(spec, sparse_offsets=(1, 2))

    def test_delivering_at_matches_schedule_masks(self):
        from murmura_tpu.faults.schedule import FaultSchedule

        sched = FaultSchedule(
            8, crash_prob=0.2, recovery_prob=0.5, straggler_prob=0.3,
            seed=5,
        )
        for r in range(6):
            np.testing.assert_array_equal(
                sched.delivering_at(r),
                sched.alive_at(r)
                * (1.0 - sched.straggler_at(r).astype(np.float32)),
            )

    def test_sparse_fold_matches_dense_on_circulant(self):
        import jax.numpy as jnp

        n, p = 8, 4
        offsets = (1, 3)
        base_k = np.ones((len(offsets), n), np.float32)
        base_d = np.zeros((n, n), np.float32)
        for j, o in enumerate(offsets):
            for i in range(n):
                base_d[i, (i + o) % n] = 1.0
        spec_d = StalenessSpec(2, 0.5, base_mask=base_d)
        spec_s = StalenessSpec(2, 0.5, base_mask=base_k)
        fold_d = make_stale_fold(spec_d)
        fold_s = make_stale_fold(spec_s, sparse_offsets=offsets)
        rng = np.random.default_rng(0)
        bcast = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
        cache = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
        age = jnp.asarray(
            rng.integers(0, 3, size=n).astype(np.float32)
        )
        dark = [2, 5]
        adj_d = base_d.copy()
        edge_k = base_k.copy()
        idx = np.arange(n)
        for s in dark:
            adj_d[:, s] = 0.0
        for j, o in enumerate(offsets):
            sender = (idx + o) % n
            edge_k[j] *= np.isin(sender, dark, invert=True)
        ones = jnp.ones((n,), jnp.float32)
        bd, ad, ud, sd = fold_d(
            bcast, jnp.asarray(adj_d),
            {CACHE_KEY: cache, AGE_KEY: age}, ones, ones,
        )
        bs, as_, us, ss = fold_s(
            bcast, jnp.asarray(edge_k),
            {CACHE_KEY: cache, AGE_KEY: age}, ones, ones,
        )
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(bs))
        np.testing.assert_array_equal(
            np.asarray(ud[CACHE_KEY]), np.asarray(us[CACHE_KEY])
        )
        np.testing.assert_array_equal(
            np.asarray(ud[AGE_KEY]), np.asarray(us[AGE_KEY])
        )
        # Dense-ify the sparse effective mask and compare edge weights.
        dense_from_sparse = np.zeros((n, n), np.float32)
        as_np = np.asarray(as_)
        for j, o in enumerate(offsets):
            for i in range(n):
                dense_from_sparse[i, (i + o) % n] = as_np[j, i]
        np.testing.assert_allclose(np.asarray(ad), dense_from_sparse)
        assert float(sd["stale_used"]) == float(ss["stale_used"])
        assert float(sd["stale_expired"]) == float(ss["stale_expired"])


# ---------------------------------------------------------------------------
# End-to-end runs
# ---------------------------------------------------------------------------


class TestStaleRuns:
    def test_stale_edges_served_and_finite(self):
        net = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 2})
        )
        h = net.train(rounds=5)
        assert sum(h["agg_stale_used"]) > 0
        assert all(np.isfinite(h["mean_loss"]))
        assert set(STALE_STATE_KEYS) <= set(net.agg_state)

    def test_zero_prob_faults_stale_is_inert(self):
        """With a fault schedule that never fires, the stale layer must
        be a semantic no-op: stale-on and stale-off histories are
        byte-identical (the cache exists but is never consulted)."""
        quiet = {"enabled": True, "seed": 11}
        h_off = build_network_from_config(_cfg(faults=quiet)).train(rounds=4)
        h_on = build_network_from_config(
            _cfg(faults=quiet, exchange={"max_staleness": 3,
                                         "staleness_discount": 0.5})
        ).train(rounds=4)
        assert sum(h_on.pop("agg_stale_used")) == 0
        h_on.pop("agg_stale_expired")
        assert h_off == h_on

    def test_fused_matches_per_round(self):
        h_per = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 2})
        ).train(rounds=4)
        h_fused = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 2})
        ).train(rounds=4, rounds_per_dispatch=4)
        assert h_per == h_fused

    def test_audit_taps_surface_per_node_staleness(self):
        cfg = _cfg(
            faults=FAULTS, exchange={"max_staleness": 2},
            telemetry={"enabled": True, "audit_taps": True,
                       "dir": "/tmp/murmura-test-stale-taps"},
        )
        import shutil

        net = build_network_from_config(cfg)
        try:
            h = net.train(rounds=4)
        finally:
            shutil.rmtree("/tmp/murmura-test-stale-taps", ignore_errors=True)
        assert "agg_tap_stale_used" in h and "agg_tap_stale_age" in h

    def test_quarantined_receiver_gets_no_stale_in_edges(self, tmp_path):
        """The receiver gate mirrors the fresh folds: quarantine zeroes
        a node's exchange edges BOTH ways (_edges_mask_both), so the
        stale layer must not re-add in-edges to a quarantined receiver
        — its rule math must see the same empty neighborhood drop-sync
        quarantine gives it (reviewer-found; per-node tap evidence via
        telemetry round events)."""
        import json

        cfg = _cfg(
            faults={"enabled": True, "straggler_prob": 0.5, "seed": 11,
                    "nan_inject_nodes": [2]},
            exchange={"max_staleness": 3},
            telemetry={"enabled": True, "audit_taps": True,
                       "dir": str(tmp_path / "run")},
        )
        net = build_network_from_config(cfg)
        h = net.train(rounds=5)
        assert sum(h["agg_stale_used"]) > 0  # the layer is live
        rounds = [
            json.loads(line)
            for line in (tmp_path / "run" / "events.jsonl").open()
            if '"round"' in line
        ]
        rounds = [e for e in rounds if e.get("type") == "round"]
        assert rounds
        checked = 0
        for e in rounds:
            m = e["metrics"]
            if m.get("agg_tap_quarantined", [0] * 8)[2] > 0:
                assert m["agg_tap_stale_used"][2] == 0.0, e
                checked += 1
        assert checked > 0  # node 2 was actually quarantined

    def test_int8_ef_sparse_exponential_composition(self):
        """staleness x int8+EF x sparse-exponential: the three carried-
        state subsystems compose in one program; with the schedule
        quiet, the composition matches stale-off (parity), and with it
        firing, stale edges are actually served.

        Parity here is allclose, not byte-equality: with staleness
        armed, quantized_exchange rules consume the receiver-side
        DECODED tensor instead of the Int8Blocks payload (one payload
        version per sender cannot be expressed inside a fresh/stale
        int8 mix — core/rounds.py), so the distance accumulations run
        in a different f32 summation order.  Same values, different
        rounding tails."""
        over = dict(
            topology={"type": "exponential", "num_nodes": 8},
            compression={"algorithm": "int8", "error_feedback": True,
                         "block": 64},
        )
        quiet = {"enabled": True, "seed": 11}
        h_off = build_network_from_config(
            _cfg(faults=quiet, **over)
        ).train(rounds=4)
        h_on = build_network_from_config(
            _cfg(faults=quiet, exchange={"max_staleness": 2}, **over)
        ).train(rounds=4)
        assert sum(h_on.pop("agg_stale_used")) == 0
        h_on.pop("agg_stale_expired")
        assert set(h_off) == set(h_on)
        for k in h_off:
            np.testing.assert_allclose(
                np.asarray(h_off[k], np.float64),
                np.asarray(h_on[k], np.float64),
                rtol=1e-5, atol=1e-7, err_msg=k,
            )

        h = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 2}, **over)
        ).train(rounds=5)
        assert sum(h["agg_stale_used"]) > 0
        assert all(np.isfinite(h["mean_loss"]))

    def test_zero_recompiles_across_staleness_variation(self):
        from murmura_tpu.analysis.sanitizers import track_compiles

        net = build_network_from_config(
            _cfg(faults=FAULTS, exchange={"max_staleness": 2})
        )
        net.train(rounds=2)
        with track_compiles() as tracker:
            net.train(rounds=3)
        assert tracker.total == 0


# ---------------------------------------------------------------------------
# Durability (the stale MUR901/902 grid cell)
# ---------------------------------------------------------------------------


class TestStaleDurability:
    def test_stale_grid_cell_clean(self):
        from murmura_tpu.analysis.durability import resume_cell_findings

        assert resume_cell_findings("krum", "stale") == []


# ---------------------------------------------------------------------------
# MUR1100-1103
# ---------------------------------------------------------------------------


class TestMUR110x:
    def test_registry_clean(self):
        from murmura_tpu.analysis.staleness import check_stale_state_registry

        assert check_stale_state_registry() == []

    def test_unregistered_group_is_a_finding(self, monkeypatch):
        from murmura_tpu.durability import snapshot
        from murmura_tpu.analysis.staleness import check_stale_state_registry

        broken = dict(snapshot.RESERVED_AGG_STATE_KEY_GROUPS)
        broken.pop("STALE_STATE_KEYS")
        monkeypatch.setattr(
            snapshot, "RESERVED_AGG_STATE_KEY_GROUPS", broken
        )
        fs = check_stale_state_registry()
        assert any("MUR900" in f.message or "RESERVED" in f.message
                   for f in fs), fs

    def test_recompile_cell_clean(self):
        from murmura_tpu.analysis.staleness import recompile_cell_findings

        assert recompile_cell_findings("fedavg", "dense") == []

    def test_collective_parity_cells_clean(self):
        from murmura_tpu.analysis.staleness import collective_cell_findings

        assert collective_cell_findings("krum", "dense") == []
        assert collective_cell_findings("fedavg", "sparse") == []

    def test_collective_parity_fires_on_stray_collective(self, monkeypatch):
        import murmura_tpu.analysis.staleness as stale_mod

        # collective_cell_findings traces the STALE program first, then
        # the drop-sync baseline: give the stale trace the stray prim.
        traces = iter([frozenset({"ppermute"}), frozenset()])
        monkeypatch.setattr(
            stale_mod, "_trace_collectives", lambda prog: next(traces)
        )
        fs = stale_mod.collective_cell_findings("krum", "dense")
        assert fs and fs[0].rule == "MUR1102"

    @pytest.mark.parametrize("rule", ["krum", "median", "fedavg"])
    def test_influence_cells_clean(self, rule):
        from murmura_tpu.analysis.staleness import stale_influence_findings

        assert stale_influence_findings(rule) == []

    def test_replay_hole_fires_on_ungated_fold(self):
        """Negative: a fold WITHOUT the scrub/age gates — every dark
        sender served from cache, every broadcast row cached — must trip
        both the probe-B cache-write contract and the probe-C replay
        hole, proving the taint probes can fire."""
        import jax.numpy as jnp

        from murmura_tpu.analysis.staleness import stale_influence_findings
        from murmura_tpu.core.stale import AGE_KEY as _AK, CACHE_KEY as _CK

        def leaky_factory(spec, sparse_offsets=(), audit=False):
            base_c = jnp.asarray(np.asarray(spec.base_mask, np.float32))

            def fold(bcast, adj, state, alive, scrub_ok):
                deliver = (adj.sum(axis=0) > 0).astype(jnp.float32)
                # No scrub gate, no age bound: every dark sender served.
                readd = base_c * alive[:, None] * (1.0 - deliver)[None, :]
                b_eff = jnp.where(
                    deliver[:, None] > 0, bcast,
                    state[_CK].astype(bcast.dtype),
                )
                updates = {
                    # Unconditional adoption: scrubbed rows cached too.
                    _CK: bcast.astype(state[_CK].dtype),
                    _AK: jnp.zeros_like(state[_AK]),
                }
                return b_eff, adj + readd, updates, {}

            return fold

        fs = stale_influence_findings("fedavg", fold_factory=leaky_factory)
        msgs = "\n".join(f.message for f in fs)
        assert "never be stored for replay" in msgs, fs
        assert "replay hole" in msgs, fs
