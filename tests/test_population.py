"""Population engine (ISSUE 6): sparse exponential-graph exchange +
sampled-cohort streaming (docs/SCALING.md).

Load-bearing contracts, in test-class order:

- **Sparse parity** (the test_gang.py-style harness): for small N, the
  sparse [k, N] edge-mask path produces histories BYTE-IDENTICAL to the
  static circulant path (an all-active mask reduces every formula to the
  static one exactly) and allclose to the dense [N, N] path (matmul vs
  rolls differ in f32 summation order — the pre-existing dense/circulant
  tolerance, tests/test_backends.py) — for every registered aggregator.
- **one_peer mask-awareness**: a round under the single-active-offset
  schedule aggregates exactly the active edge — pinned against a dense
  network driven by the equivalent per-round graph.
- **Default-off discipline**: no sparse topology and no population block
  ⇒ byte-identical programs and histories (the faults/telemetry/sweep
  contract).
- **Cohort streaming**: seed-deterministic draws, per-user persistence
  across re-activations, zero recompiles across swaps, and the 1M-user
  memmap-bank smoke.
"""

import numpy as np
import pytest

from murmura_tpu.aggregation import AGGREGATORS, build_aggregator
from murmura_tpu.config import Config
from murmura_tpu.core.network import Network, effective_edge_mask
from murmura_tpu.core.rounds import build_round_program
from murmura_tpu.data.base import FederatedArrays
from murmura_tpu.models import make_mlp
from murmura_tpu.topology import (
    SparseTopology,
    create_topology,
    exponential_offsets,
)
from murmura_tpu.utils.factories import (
    ConfigError,
    build_gang_from_config,
    build_network_from_config,
)

N = 8
AGG_PARAMS = {
    "krum": {"num_compromised": 1},
    "sketchguard": {"sketch_size": 32},
    "trimmed_mean": {"trim_ratio": 0.2},
    "geometric_median": {"max_iters": 4},
}
# sketchguard's sparse filter runs in circulant sketch space (rolled
# distances) while its circulant mode filters via the pairwise Gram — same
# math, different f32 path, so its sparse-vs-circulant parity is allclose.
BYTE_EXACT_VS_CIRCULANT = set(AGGREGATORS) - {"sketchguard"}


def _data():
    rng = np.random.default_rng(0)
    s = 16
    return FederatedArrays(
        x=rng.normal(size=(N, s, 6)).astype(np.float32),
        y=rng.integers(0, 3, size=(N, s)).astype(np.int32),
        mask=np.ones((N, s), np.float32),
        num_samples=np.full((N,), s),
        num_classes=3,
    )


def _model_and_dim():
    import jax

    from murmura_tpu.ops.flatten import model_dimension

    model = make_mlp(input_dim=6, hidden_dims=(8,), num_classes=3)
    dim = model_dimension(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return model, dim


def _history(mode, algo, topo, *, mobility=None, fault_schedule=None,
             faults=None, rounds=2):
    """One tiny training history on the given exchange mode:
    'sparse' ([k, N] edge mask), 'circulant' (static offsets, dense adj
    input ignored), 'dense' (gathered [N, N] masking)."""
    model, dim = _model_and_dim()
    offsets = list(topo.offsets)
    params = dict(AGG_PARAMS.get(algo, {}))
    if mode == "sparse":
        params.update(exchange_offsets=offsets, sparse_exchange=True)
    elif mode == "circulant":
        params.update(exchange_offsets=offsets)
    agg = build_aggregator(algo, params, model_dim=dim, total_rounds=4)
    prog = build_round_program(
        model, agg, _data(), total_rounds=4, batch_size=8, faults=faults,
        sparse_offsets=tuple(offsets) if mode == "sparse" else None,
    )
    net = Network(
        prog, topology=topo, mobility=mobility, backend="simulation",
        fault_schedule=fault_schedule,
    )
    return net.train(rounds=rounds)


class TestSparseTopology:
    def test_exponential_offsets(self):
        assert exponential_offsets(8) == (1, 2, 4)
        assert exponential_offsets(4096) == tuple(2 ** i for i in range(12))
        # Non-power-of-two N: the default horizon never collides...
        assert exponential_offsets(9) == (1, 2, 4, 8)
        assert exponential_offsets(6) == (1, 2, 4)

    def test_exponential_offsets_dedupe_regression(self):
        # ...but an over-long horizon revisits offsets at non-power-of-two
        # N (2^3 mod 6 == 2): the raw sequence collides and MUST dedupe —
        # a duplicated offset double-counts that neighbor in every
        # weighted circulant kernel.
        assert exponential_offsets(6, horizon=4) == (1, 2, 4)

    def test_exponential_offset_zero_rejected_loud(self):
        # Power-of-two N with an over-long horizon degenerates to offset
        # 0 (2^3 mod 8 == 0) — a self-loop; must raise, not emit.
        with pytest.raises(ValueError, match="self-loop"):
            exponential_offsets(8, horizon=4)
        with pytest.raises(ValueError, match=">= 2"):
            exponential_offsets(1)

    def test_sparse_topology_validates_offsets(self):
        with pytest.raises(ValueError, match="zero"):
            SparseTopology(num_nodes=8, offsets=(0, 1))
        with pytest.raises(ValueError, match="collide"):
            SparseTopology(num_nodes=6, offsets=(2, 8))  # 8 mod 6 == 2
        with pytest.raises(ValueError, match="at least one"):
            SparseTopology(num_nodes=8, offsets=())

    def test_edge_masks_and_views(self):
        topo = create_topology("exponential", num_nodes=8)
        assert isinstance(topo, SparseTopology)
        assert topo.degree == 3 and topo.is_connected()
        assert topo.edge_mask(0).shape == (3, 8)
        assert (topo.edge_mask(5) == 1.0).all()
        adj = topo.adjacency
        assert not adj.diagonal().any()
        assert adj.sum() == 3 * 8
        # one_peer: exactly one active offset row per round, cycling.
        op = create_topology("one_peer", num_nodes=8)
        for r in range(4):
            mask = op.edge_mask(r)
            assert mask.sum() == 8
            assert (mask[r % 3] == 1.0).all()

    def test_in_degree_from_edge_mask(self):
        topo = create_topology("exponential", num_nodes=8)
        full = topo.in_degree_from_edge_mask(topo.edge_mask(0))
        np.testing.assert_array_equal(full, np.full(8, 3.0))
        # Zero one receiver's edges: each of its 3 senders loses one read.
        mask = topo.edge_mask(0)
        mask[:, 2] = 0.0
        partial = topo.in_degree_from_edge_mask(mask)
        assert partial.sum() == 3 * 8 - 3


# Tier-1 runs a representative subset of the 9-rule parity grid (the
# repo's slow-gating pattern, e.g. test_durability's resume grid): one
# linear rule, the flagship selection rule, a sort-based rule, and the
# carried-state exception.  The full grid runs under -m slow.
_TIER1_SPARSE_PARITY = {"fedavg", "krum", "median", "evidential_trust"}


class TestSparseParity:
    """The ISSUE-6 parity harness: sparse vs circulant vs dense, every
    registered aggregator."""

    @pytest.mark.parametrize("algo", [
        pytest.param(
            a,
            marks=() if a in _TIER1_SPARSE_PARITY else (pytest.mark.slow,),
        )
        for a in sorted(AGGREGATORS)
    ])
    def test_sparse_matches_circulant_and_dense(self, algo):
        topo = create_topology("exponential", num_nodes=N)
        hs = _history("sparse", algo, topo)
        hc = _history("circulant", algo, topo)
        hd = _history("dense", algo, topo)
        for key in hc:
            if not hc[key]:
                continue
            if algo in BYTE_EXACT_VS_CIRCULANT:
                # assert_array_equal = exact elementwise equality with
                # NaN==NaN (evidential stats are NaN under non-evidential
                # models in BOTH paths).
                np.testing.assert_array_equal(
                    hs[key], hc[key],
                    err_msg=f"history[{key}] sparse vs circulant",
                )
            else:
                np.testing.assert_allclose(
                    hs[key], hc[key], rtol=1e-3, atol=1e-5,
                    err_msg=f"history[{key}]",
                )
        for key in ("mean_accuracy", "mean_loss"):
            np.testing.assert_allclose(
                hs[key], hd[key], rtol=1e-3, atol=1e-3,
                err_msg=f"history[{key}] sparse vs dense",
            )


class _SingleOffsetMobility:
    """Dense per-round reference for one_peer: round r's graph is exactly
    the single active offset's directed circulant."""

    def __init__(self, topo):
        self.topo = topo

    def adjacency_at(self, r):
        n = self.topo.num_nodes
        o = self.topo.offsets[r % len(self.topo.offsets)]
        adj = np.zeros((n, n), np.float32)
        idx = np.arange(n)
        adj[idx, (idx + o) % n] = 1.0
        return adj


class TestOnePeer:
    @pytest.mark.parametrize("algo", ["fedavg", "krum", "median", "balance"])
    def test_one_peer_matches_per_round_dense_graph(self, algo):
        op = create_topology("one_peer", num_nodes=N)
        hs = _history("sparse", algo, op, rounds=4)
        # Dense reference: same program family, per-round single-offset
        # graph supplied the mobility way (host-side per-round values).
        model, dim = _model_and_dim()
        agg = build_aggregator(
            algo, dict(AGG_PARAMS.get(algo, {})), model_dim=dim,
            total_rounds=4,
        )
        prog = build_round_program(model, agg, _data(), total_rounds=4,
                                   batch_size=8)
        hd = Network(
            prog, topology=op, mobility=_SingleOffsetMobility(op),
            backend="simulation",
        ).train(rounds=4)
        for key in ("mean_accuracy", "mean_loss"):
            np.testing.assert_allclose(
                hs[key], hd[key], rtol=1e-4, atol=1e-5,
                err_msg=f"history[{key}]",
            )


class TestSparseFaults:
    def test_masked_edge_mask_only_removes(self):
        from murmura_tpu.faults.schedule import FaultSchedule

        topo = create_topology("exponential", num_nodes=8)
        sched = FaultSchedule(
            8, crash_prob=0.3, recovery_prob=0.4, link_drop_prob=0.3,
            straggler_prob=0.3, seed=1,
        )
        for r in (0, 3, 7):
            base = topo.edge_mask(r)
            masked = sched.masked_edge_mask(base, topo.offsets, r)
            assert masked.shape == base.shape
            assert (masked <= base).all()

    def test_sparse_faulted_run_matches_dense_faulted_run(self):
        # The same fault schedule folded into the [k, N] mask (sparse) and
        # into the directed dense adjacency (dense) must train the same —
        # drift here means the two fold paths disagree about which edges a
        # fault kills.
        from murmura_tpu.faults.schedule import FaultSchedule, FaultSpec

        topo = create_topology("exponential", num_nodes=8)
        mk = lambda: FaultSchedule(  # noqa: E731
            8, crash_prob=0.25, recovery_prob=0.5, link_drop_prob=0.2,
            straggler_prob=0.2, seed=3,
        )
        hs = _history("sparse", "fedavg", topo, fault_schedule=mk(),
                      faults=FaultSpec(), rounds=4)
        hd = _history("dense", "fedavg", topo, fault_schedule=mk(),
                      faults=FaultSpec(), rounds=4)
        assert hs["agg_alive"] == hd["agg_alive"]
        for key in ("mean_accuracy", "mean_loss"):
            np.testing.assert_allclose(
                hs[key], hd[key], rtol=1e-3, atol=1e-4,
                err_msg=f"history[{key}]",
            )


def _raw(**over):
    r = {
        "experiment": {"name": "pop-test", "seed": 3, "rounds": 4},
        "topology": {"type": "exponential", "num_nodes": 8},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 160, "input_dim": 10,
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 10, "hidden_dims": [16],
                             "num_classes": 3}},
        "backend": "simulation",
    }
    r.update(over)
    return r


class TestConfigWiring:
    def test_exponential_via_config_trains(self):
        net = build_network_from_config(Config.model_validate(_raw()))
        assert net.program.sparse
        assert net.program.sparse_offsets == (1, 2, 4)
        hist = net.train(rounds=2)
        assert np.isfinite(hist["mean_loss"]).all()

    def test_one_peer_via_config_trains(self):
        net = build_network_from_config(
            Config.model_validate(_raw(topology={"type": "one_peer",
                                                 "num_nodes": 8}))
        )
        hist = net.train(rounds=3)
        assert np.isfinite(hist["mean_loss"]).all()

    def test_sparse_rejects_distributed_backend(self):
        with pytest.raises(Exception, match="sparse"):
            Config.model_validate(_raw(backend="distributed"))

    def test_sparse_rejects_mobility_and_dmtt(self):
        with pytest.raises(Exception, match="mobility"):
            Config.model_validate(_raw(mobility={"seed": 1}))
        with pytest.raises(Exception, match="dmtt"):
            Config.model_validate(_raw(dmtt={"allow_static": True}))

    def test_sparse_gang_batchable_on_simulation(self):
        # Lifted for ISSUE 11 (the frontier sweeps sparse exponential
        # graphs at gang speed): the member-shared [k, N] edge mask rides
        # the gang vmap unbatched like the dense [N, N] matrix, and each
        # member's history matches its unganged single run.
        gang = build_gang_from_config(
            Config.model_validate(_raw(sweep={"seeds": [1, 2]}))
        )
        hists = gang.train(rounds=2)
        for seed, hist in zip((1, 2), hists):
            single = build_network_from_config(Config.model_validate(
                _raw(experiment={"name": "pop-test", "seed": seed,
                                 "rounds": 4})
            ))
            shist = single.train(rounds=2)
            assert hist["mean_accuracy"] == shist["mean_accuracy"], seed

    def test_sparse_not_gang_batchable_on_tpu_mesh(self):
        # The gang MESH still shards adjacency on node rows; the [k, N]
        # edge mask needs an edge_mask_sharding layout the gang path has
        # not wired — fail loud rather than mis-shard.
        raw = _raw(backend="tpu", sweep={"seeds": [1, 2]})
        raw["tpu"] = {"num_devices": 1, "compute_dtype": "float32"}
        with pytest.raises(ConfigError, match="gang"):
            build_gang_from_config(Config.model_validate(raw))

    def test_tpu_exchange_setting_is_moot_for_sparse(self):
        # Both tpu.exchange values route a sparse topology through the
        # edge-mask engine; neither errors, histories identical.
        hists = []
        for exch in ("allgather", "ppermute"):
            raw = _raw(backend="tpu")
            raw["tpu"] = {"exchange": exch, "num_devices": 1,
                          "compute_dtype": "float32",
                          "param_dtype": "float32"}
            hists.append(
                build_network_from_config(
                    Config.model_validate(raw)
                ).train(rounds=2)
            )
        assert hists[0] == hists[1]

    def test_sparse_tpu_mesh_runs_sharded(self):
        # 8 virtual devices, node axis sharded: the [k, N] mask shards on
        # its node columns (mesh.edge_mask_sharding) and the history
        # matches the single-device run.
        raw = _raw(backend="tpu")
        raw["tpu"] = {"num_devices": 8, "compute_dtype": "float32",
                      "param_dtype": "float32"}
        sharded = build_network_from_config(
            Config.model_validate(raw)
        ).train(rounds=2)
        single = build_network_from_config(
            Config.model_validate(_raw())
        ).train(rounds=2)
        for key in ("mean_accuracy", "mean_loss"):
            np.testing.assert_allclose(
                sharded[key], single[key], rtol=1e-4, atol=1e-5,
                err_msg=f"history[{key}]",
            )


class TestSamplers:
    def test_draws_are_pure_functions_of_seed_and_index(self):
        from murmura_tpu.population import draw_cohort

        for sampler in ("uniform", "stratified"):
            a = draw_cohort(sampler, 10_000, 16, 7, 42)
            b = draw_cohort(sampler, 10_000, 16, 7, 42)
            np.testing.assert_array_equal(a, b)
            c = draw_cohort(sampler, 10_000, 16, 8, 42)
            assert not np.array_equal(a, c)
            assert len(np.unique(a)) == 16

    def test_stratified_covers_every_stratum(self):
        from murmura_tpu.population import draw_cohort

        cohort = draw_cohort("stratified", 1000, 10, 0, 1)
        bounds = np.linspace(0, 1000, 11).astype(int)
        for j in range(10):
            assert bounds[j] <= cohort[j] < bounds[j + 1]

    def test_unknown_sampler_rejected(self):
        from murmura_tpu.population import draw_cohort

        with pytest.raises(ValueError, match="unknown population sampler"):
            draw_cohort("nope", 100, 8, 0, 1)


class TestBank:
    def test_lazy_init_and_persistence(self):
        from murmura_tpu.population import PopulationBank

        bank = PopulationBank(100, 4)
        defaults = np.arange(12, dtype=np.float32).reshape(3, 4)
        users = np.array([5, 50, 99])
        rows = bank.gather(users, defaults)
        np.testing.assert_array_equal(rows, defaults)  # never activated
        assert bank.activated == 0
        bank.scatter(users, rows + 1.0)
        assert bank.activated == 3
        again = bank.gather(users, defaults)
        np.testing.assert_array_equal(again, defaults + 1.0)  # persisted
        # A different user in the same slot still gets the slot default.
        other = bank.gather(np.array([6, 51, 98]), defaults)
        np.testing.assert_array_equal(other, defaults)

    def test_large_bank_is_memmapped(self, tmp_path):
        from murmura_tpu.population import PopulationBank

        bank = PopulationBank(1_000_000, 128, directory=str(tmp_path))
        assert bank.path is not None
        users = np.array([0, 999_999])
        bank.scatter(users, np.ones((2, 128), np.float32))
        np.testing.assert_array_equal(
            bank.rows_of(users), np.ones((2, 128), np.float32)
        )
        assert bank.activated == 2


class TestPopulationEngine:
    def test_default_off_is_byte_identical(self):
        base = _raw(topology={"type": "ring", "num_nodes": 8})
        ha = build_network_from_config(
            Config.model_validate(base)
        ).train(rounds=3)
        withblock = _raw(topology={"type": "ring", "num_nodes": 8},
                         population={"enabled": False})
        net = build_network_from_config(Config.model_validate(withblock))
        assert type(net) is Network  # not a PopulationNetwork
        hb = net.train(rounds=3)
        assert ha == hb

    def test_deterministic_and_users_persist(self):
        cfg = Config.model_validate(_raw(
            population={"enabled": True, "virtual_size": 64,
                        "sampler": "uniform", "seed": 9},
        ))
        net = build_network_from_config(cfg)
        h1 = net.train(rounds=4)
        # Every drawn user's row was written back and differs from the
        # never-trained slot init.
        drawn = {u for r in range(4) for u in net._draw(r)}
        assert net.bank.activated == len(drawn)
        net2 = build_network_from_config(cfg)
        h2 = net2.train(rounds=4)
        assert h1 == h2  # seed-deterministic end to end

    def test_rounds_per_cohort_and_reactivation_resumes(self):
        cfg = Config.model_validate(_raw(
            experiment={"name": "pop", "seed": 3, "rounds": 6},
            population={"enabled": True, "virtual_size": 8,
                        "sampler": "uniform", "seed": 9,
                        "rounds_per_cohort": 2},
        ))
        net = build_network_from_config(cfg)
        net.train(rounds=6)
        assert net.cohorts_seen == 3
        # virtual_size == cohort size: every user re-activates each swap,
        # so all 8 rows are persistent and none equals the slot init (the
        # users actually trained across re-activations).
        assert net.bank.activated == 8
        rows = net.bank.rows_of(np.arange(8))
        assert not np.allclose(rows, net._slot_init[:1])

    def test_zero_recompiles_across_swaps(self):
        raw = _raw(population={"enabled": True, "virtual_size": 128})
        raw["tpu"] = {"recompile_guard": True}
        net = build_network_from_config(Config.model_validate(raw))
        # tpu.recompile_guard raises RecompileError on any post-warmup
        # compile; 3 swaps under the guard ARE the assertion.
        net.train(rounds=3)
        assert net.cohorts_seen == 3
        assert net.last_compile_report is not None

    def test_million_user_smoke(self):
        # The tier-1 acceptance row: virtual_size >= 1M streams through a
        # fixed 8-node cohort; the bank memmaps (sparse file) and only the
        # activated rows exist.
        net = build_network_from_config(Config.model_validate(_raw(
            population={"enabled": True, "virtual_size": 1_000_000,
                        "sampler": "stratified"},
        )))
        hist = net.train(rounds=3, eval_every=3)
        assert np.isfinite(hist["mean_loss"]).all()
        assert net.bank.path is not None  # memory-mapped, not resident
        assert 0 < net.bank.activated <= 24

    def test_consecutive_cohort_overlap_resumes_fresh_rows(self):
        # Regression (review finding): the prefetch stages the incoming
        # cohort BEFORE the outgoing write-back; users in BOTH consecutive
        # cohorts must still resume the just-trained row, not a stale (or
        # absent) one.  virtual_size == cohort size makes every swap a
        # full overlap: with inherit=slot_init, the buggy order reset all
        # users to seed init each round and the loss never moved.
        net = build_network_from_config(Config.model_validate(_raw(
            experiment={"name": "pop-overlap", "seed": 3, "rounds": 6},
            population={"enabled": True, "virtual_size": 8,
                        "sampler": "uniform", "seed": 9,
                        "inherit": "slot_init"},
        )))
        h = net.train(rounds=6)
        assert h["mean_loss"][-1] < 0.85 * h["mean_loss"][0]
        assert h["mean_accuracy"][-1] > h["mean_accuracy"][0]

    def test_teleport_inheritance_accumulates_learning(self):
        # The Teleportation mechanism (arXiv:2501.15259): with rare
        # re-activation (large virtual_size), teleport hands the outgoing
        # cohort's trained models to fresh users so learning accumulates
        # across cohorts; slot_init restarts them from seed init — the
        # contrast is the correctness signal (same seeds otherwise).
        def run(inherit):
            net = build_network_from_config(Config.model_validate(_raw(
                experiment={"name": "pop-inh", "seed": 3, "rounds": 8},
                population={"enabled": True, "virtual_size": 10_000,
                            "sampler": "uniform", "seed": 9,
                            "inherit": inherit},
            )))
            return net.train(rounds=8, eval_every=8)

        tele = run("teleport")
        fresh = run("slot_init")
        assert tele["mean_loss"][-1] < fresh["mean_loss"][-1]
        assert tele["mean_accuracy"][-1] > fresh["mean_accuracy"][-1]

    def test_population_composes_with_faults(self):
        net = build_network_from_config(Config.model_validate(_raw(
            population={"enabled": True, "virtual_size": 64},
            faults={"enabled": True, "seed": 5, "crash_prob": 0.2,
                    "recovery_prob": 0.5},
        )))
        hist = net.train(rounds=4)
        assert "agg_alive" in hist
        assert np.isfinite(hist["mean_loss"]).all()

    def test_checkpointing_supported(self, tmp_path):
        # ISSUE-10 lifted the old loud rejection: population runs snapshot
        # the full streaming state (durability/snapshot.py; resume
        # determinism is proven in tests/test_durability.py).
        from murmura_tpu.utils.checkpoint import has_checkpoint

        net = build_network_from_config(Config.model_validate(_raw(
            population={"enabled": True, "virtual_size": 64},
        )))
        net.train(rounds=1, checkpoint_dir=str(tmp_path),
                  checkpoint_every=1)
        assert has_checkpoint(tmp_path)

    def test_slot_binding_skips_data_restage(self):
        net = build_network_from_config(Config.model_validate(_raw(
            population={"enabled": True, "virtual_size": 64,
                        "data_binding": "slot"},
        )))
        hist = net.train(rounds=3)
        assert np.isfinite(hist["mean_loss"]).all()


class TestPopulationSchema:
    def test_cohort_size_must_match_nodes(self):
        with pytest.raises(Exception, match="cohort_size"):
            Config.model_validate(_raw(
                population={"enabled": True, "virtual_size": 100,
                            "cohort_size": 4},
            ))

    def test_virtual_size_floor(self):
        with pytest.raises(Exception, match="virtual_size"):
            Config.model_validate(_raw(
                population={"enabled": True, "virtual_size": 4},
            ))

    def test_disabled_with_sizes_fails_loud(self):
        with pytest.raises(Exception, match="enabled"):
            Config.model_validate(_raw(
                population={"enabled": False, "virtual_size": 100},
            ))

    def test_population_rejects_sweep_and_distributed(self):
        with pytest.raises(Exception, match="sweep|gang"):
            Config.model_validate(_raw(
                population={"enabled": True, "virtual_size": 100},
                sweep={"seeds": [1, 2]},
            ))
        with pytest.raises(Exception, match="distributed|sparse"):
            Config.model_validate(_raw(
                population={"enabled": True, "virtual_size": 100},
                backend="distributed",
            ))


class TestExampleConfig:
    @pytest.mark.slow
    def test_population_1m_example_runs(self):
        import yaml
        from pathlib import Path

        raw = yaml.safe_load(
            (Path(__file__).parent.parent / "examples" / "configs" /
             "population_1m.yaml").read_text()
        )
        raw["experiment"]["rounds"] = 1
        raw["experiment"]["verbose"] = False
        net = build_network_from_config(Config.model_validate(raw))
        hist = net.train(rounds=1)
        assert np.isfinite(hist["mean_loss"]).all()
        assert net.program.sparse and net.program.num_nodes == 256


class TestSparseIRContracts:
    """MUR600/601 snapshots at the unit level (the full sweep runs in
    check --ir, tests/test_analysis_contracts.py::TestRepoIsClean)."""

    def test_sparse_cells_trace_dense_free(self):
        from murmura_tpu.analysis import ir

        n = 12
        for name in ir.SPARSE_DENSE_FREE:
            prog = ir.build_canonical(name, n, "float32", sparse=True)
            for eqn in ir.iter_eqns(ir.trace_jaxpr(prog)):
                for var in list(eqn.invars) + list(eqn.outvars):
                    shape = tuple(
                        getattr(getattr(var, "aval", None), "shape", ())
                        or ()
                    )
                    assert sum(1 for d in shape if d == n) < 2, (
                        name, eqn.primitive.name, shape
                    )

    def test_sparse_inventory_is_ppermute_only(self):
        from murmura_tpu.analysis import ir

        prog = ir.build_canonical(
            "fedavg", 8, "float32", sparse=True, node_axis_sharded=True
        )
        assert ir.collective_inventory(prog) == {"ppermute"}
