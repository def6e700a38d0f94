"""Run-level durability (ISSUE 10): crash-equivalent checkpoint/resume for
every in-jit orchestrator + the elastic dispatch envelope.

The contract under test (docs/ROBUSTNESS.md "Run durability"): a run
killed at ANY round boundary and resumed from its snapshot produces a
history/params/agg_state byte-identical to the uninterrupted run, with
zero extra recompiles — for single runs (dense / circulant / sparse /
int8+EF exchange), gangs, and cohort-streaming population runs.  Plus the
dispatch envelope: transient-vs-fatal classification, seeded backoff,
restore-before-retry, and the ``--require-tpu`` hard-fail.

A "kill" here is a fresh orchestrator restoring the snapshot — process
death equivalence rests on the snapshot being the ONLY state channel,
which the fresh-object restore exercises identically (the cross-process
variant lives in test_checkpoint.py's mesh test).  Representative cells
run tier-1; the exhaustive kill-at-every-boundary × every-mode matrix and
the full MUR901/902 grid are ``slow``.
"""

import json

import jax
import numpy as np
import pytest

from murmura_tpu.analysis.durability import (
    DURABILITY_MODES,
    check_durability,
    history_equal,
    resume_cell_findings,
)
from murmura_tpu.config import Config
from murmura_tpu.durability import dispatch as ddispatch
from murmura_tpu.durability import snapshot as dsnap
from murmura_tpu.utils.checkpoint import has_checkpoint
from murmura_tpu.utils.factories import (
    build_gang_from_config,
    build_network_from_config,
)


def _raw(**over):
    r = {
        "experiment": {"name": "durability-test", "seed": 7, "rounds": 4},
        "topology": {"type": "ring", "num_nodes": 5},
        "aggregation": {"algorithm": "balance", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 40, "input_shape": [6],
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 6, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "simulation",
    }
    r.update(over)
    return r


def _cfg(**over):
    return Config.model_validate(_raw(**over))


def _hist(net):
    return {k: list(v) for k, v in net.history.items()}


def _assert_same_run(full, resumed, label=""):
    assert history_equal(_hist(full), _hist(resumed)), (
        label,
        sorted(k for k in full.history
               if not history_equal(list(full.history[k]),
                                    list(resumed.history.get(k, [])))),
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(full.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=label)
    assert set(full.agg_state) == set(resumed.agg_state), label
    for k in full.agg_state:
        np.testing.assert_array_equal(
            np.asarray(full.agg_state[k]), np.asarray(resumed.agg_state[k]),
            err_msg=f"{label}:{k}",
        )


# ---------------------------------------------------------------------------
# Dispatch envelope (durability/dispatch.py)
# ---------------------------------------------------------------------------


class TestErrorClassification:
    def test_transport_types_are_transient(self):
        assert ddispatch.classify_error(ConnectionError("boom")) == "transient"
        assert ddispatch.classify_error(TimeoutError()) == "transient"

    def test_marker_substrings_are_transient(self):
        for msg in ("DEADLINE_EXCEEDED while waiting", "socket closed",
                    "transport reset by peer", "heartbeat lost",
                    "UNAVAILABLE: connection to TPU worker"):
            assert ddispatch.classify_error(RuntimeError(msg)) == "transient", msg

    def test_deterministic_failures_are_fatal(self):
        for exc in (ValueError("shape mismatch [5,3] vs [5,4]"),
                    TypeError("unsupported operand"),
                    KeyError("missing")):
            assert ddispatch.classify_error(exc) == "fatal", exc

    def test_backend_requirement_is_always_fatal(self):
        # Even though the message contains transient-looking markers,
        # retrying cannot conjure a chip.
        exc = ddispatch.BackendRequirementError("device unavailable timeout")
        assert ddispatch.classify_error(exc) == "fatal"


class TestRetryPolicy:
    def test_invalid_policies_rejected(self):
        with pytest.raises(ValueError, match="max_retries"):
            ddispatch.RetryPolicy(max_retries=-1)
        with pytest.raises(ValueError, match="delay"):
            ddispatch.RetryPolicy(base_delay_s=10.0, max_delay_s=1.0)
        with pytest.raises(ValueError, match="jitter"):
            ddispatch.RetryPolicy(jitter=1.5)

    def test_backoff_is_exponential_capped_and_seeded(self):
        policy = ddispatch.RetryPolicy(
            max_retries=6, base_delay_s=1.0, max_delay_s=8.0, jitter=0.25,
            seed=42,
        )
        a = list(ddispatch.backoff_delays(policy))
        b = list(ddispatch.backoff_delays(policy))
        assert a == b  # seeded => reproducible schedule
        assert len(a) == 6
        for i, d in enumerate(a):
            base = min(8.0, 2.0 ** i)
            assert base * 0.75 <= d <= base * 1.25, (i, d)

    def test_retry_restores_then_succeeds(self):
        calls, sleeps = [], []

        def attempt(try_idx):
            calls.append(try_idx)
            if try_idx < 2:
                raise ConnectionError("link died")
            return "done"

        result = ddispatch.run_with_retry(
            attempt,
            policy=ddispatch.RetryPolicy(max_retries=3, base_delay_s=0.01,
                                         max_delay_s=0.04, seed=0),
            sleep=sleeps.append,
        )
        assert result == "done"
        assert calls == [0, 1, 2]  # the try index IS the restore signal
        assert len(sleeps) == 2

    def test_fatal_raises_immediately(self):
        calls = []

        def attempt(try_idx):
            calls.append(try_idx)
            raise ValueError("deterministic bug")

        with pytest.raises(ValueError, match="deterministic"):
            ddispatch.run_with_retry(
                attempt, policy=ddispatch.RetryPolicy(max_retries=5),
                sleep=lambda s: pytest.fail("must not sleep on fatal"),
            )
        assert calls == [0]

    def test_exhausted_retries_reraise_original(self):
        hooks = []

        def attempt(try_idx):
            raise TimeoutError(f"try {try_idx}")

        with pytest.raises(TimeoutError, match="try 2"):
            ddispatch.run_with_retry(
                attempt,
                policy=ddispatch.RetryPolicy(max_retries=2, base_delay_s=0.0,
                                             seed=1),
                on_retry=lambda e, i, d: hooks.append((i, d)),
                sleep=lambda s: None,
            )
        assert [i for i, _ in hooks] == [1, 2]


class TestRequireTpu:
    def test_require_tpu_fails_loudly_on_cpu(self):
        # The suite pins jax to CPU (conftest) — exactly the silent
        # fallback the flag exists to refuse.
        with pytest.raises(ddispatch.BackendRequirementError,
                           match="refusing to run on another device"):
            ddispatch.require_tpu(source="--require-tpu")

    def test_tpu_required_env_and_config(self, monkeypatch):
        monkeypatch.delenv("MURMURA_REQUIRE_TPU", raising=False)
        assert not ddispatch.tpu_required(None)
        assert ddispatch.tpu_required(_cfg(durability={"require_tpu": True}))
        monkeypatch.setenv("MURMURA_REQUIRE_TPU", "1")
        assert ddispatch.tpu_required(None)


# ---------------------------------------------------------------------------
# MUR900: snapshot completeness bijection (durability/snapshot.py +
# analysis/contracts.py)
# ---------------------------------------------------------------------------


class TestSnapshotCompleteness:
    def test_reserved_groups_discovered_and_registered(self):
        from murmura_tpu import __file__ as pkg_init

        from pathlib import Path

        discovered = dsnap.discover_state_key_groups(Path(pkg_init).parent)
        # The two groups the repo reserves today must both be discovered
        # AND registered — a third party adding one without registering it
        # is exactly what MUR900 fires on.
        assert set(discovered) >= {"COMPRESS_STATE_KEYS", "DMTT_STATE_KEYS"}
        assert set(discovered) == set(dsnap.RESERVED_AGG_STATE_KEY_GROUPS)

    def test_resolve_returns_nonempty_string_tuples(self):
        groups = dsnap.resolve_reserved_agg_state_keys()
        assert groups
        for name, keys in groups.items():
            assert keys and all(isinstance(k, str) for k in keys), name

    def test_unregistered_group_is_a_finding(self):
        from murmura_tpu.analysis.contracts import _mur900_registry_findings

        fs = _mur900_registry_findings(
            {"COMPRESS_STATE_KEYS": "murmura_tpu.ops.compress",
             "ROGUE_STATE_KEYS": "murmura_tpu.ops.rogue"},
            {"COMPRESS_STATE_KEYS": "murmura_tpu.ops.compress"},
            "snapshot.py",
        )
        assert len(fs) == 1 and "ROGUE_STATE_KEYS" in fs[0].message
        assert fs[0].rule == "MUR900"

    def test_stale_registry_entry_is_a_finding(self):
        from murmura_tpu.analysis.contracts import _mur900_registry_findings

        fs = _mur900_registry_findings(
            {}, {"GONE_STATE_KEYS": "murmura_tpu.ops.gone"}, "snapshot.py",
        )
        assert len(fs) == 1 and "stale" in fs[0].message

    def test_moved_group_is_a_finding(self):
        from murmura_tpu.analysis.contracts import _mur900_registry_findings

        fs = _mur900_registry_findings(
            {"COMPRESS_STATE_KEYS": "murmura_tpu.ops.elsewhere"},
            {"COMPRESS_STATE_KEYS": "murmura_tpu.ops.compress"},
            "snapshot.py",
        )
        assert len(fs) == 1 and "registered under" in fs[0].message

    def test_roundtrip_probe_detects_missing_section(self, tmp_path):
        missing, corrupted = dsnap.snapshot_roundtrip_missing_sections(
            tmp_path, {"params": {"w": np.zeros(2, np.float32)}},
        )
        assert "agg_state" in missing and "rng" in missing
        assert corrupted == []

    def test_roundtrip_probe_full_payload_survives(self, tmp_path):
        rng = np.random.default_rng(0)
        agg = {k: rng.normal(size=(3,)).astype(np.float32)
               for keys in dsnap.resolve_reserved_agg_state_keys().values()
               for k in keys}
        agg["plain"] = np.float32([1.5, np.nan])  # NaN must survive too
        payload = {
            "params": {"w": rng.normal(size=(2, 2)).astype(np.float32)},
            "agg_state": agg,
            "rng": np.zeros(2, np.uint32),
            "round": 5,
            "history": {"round": [1, 2, 3, 4, 5]},
            "round_times": [0.1] * 5,
        }
        missing, corrupted = dsnap.snapshot_roundtrip_missing_sections(
            tmp_path, payload
        )
        assert missing == [] and corrupted == []

    def test_contracts_gate_is_clean(self):
        # The tier-1 MUR900 gate: the live registry and the live
        # serialization path satisfy the completeness bijection.
        from murmura_tpu.analysis.contracts import check_contracts

        assert [f for f in check_contracts() if f.rule.startswith("MUR9")] == []


# ---------------------------------------------------------------------------
# MUR901/902: resume determinism (analysis/durability.py)
# ---------------------------------------------------------------------------


class TestResumeDeterminism:
    # One representative cell per exchange mode, biased toward carried
    # state (int8+EF is the mode a shallow snapshot silently corrupts);
    # the full 9-rule x 4-mode grid runs under -m slow and in
    # `murmura check --durability`.
    @pytest.mark.parametrize("rule,mode", [
        ("krum", "compressed"),
        ("fedavg", "sparse"),
        ("median", "circulant"),
    ])
    def test_representative_cells_clean(self, rule, mode):
        assert resume_cell_findings(rule, mode) == []

    def test_mur901_fires_on_corrupted_restore(self, monkeypatch):
        # Negative: a restore that perturbs one param leaf must surface as
        # MUR901 divergence, proving the byte-equality probe can fire.
        import murmura_tpu.core.network as core_network

        real = core_network.Network.restore_checkpoint

        def corrupting(self, directory):
            round_num = real(self, directory)
            leaves, treedef = jax.tree_util.tree_flatten(self.params)
            leaves[0] = leaves[0] + 1e-3
            self.params = jax.tree_util.tree_unflatten(treedef, leaves)
            return round_num

        monkeypatch.setattr(
            core_network.Network, "restore_checkpoint", corrupting
        )
        fs = resume_cell_findings("fedavg", "dense")
        assert any(f.rule == "MUR901" for f in fs), fs

    def test_mur902_fires_on_replay_compile(self, monkeypatch):
        # Negative: any compile landing inside the post-restore replay
        # must surface as MUR902 (here: a fresh jit per recorded round).
        import murmura_tpu.core.network as core_network

        real = core_network.Network._record

        def compiling(self, round_num, metrics, verbose):
            jax.jit(lambda x: x + round_num)(1.0)
            return real(self, round_num, metrics, verbose)

        monkeypatch.setattr(core_network.Network, "_record", compiling)
        fs = resume_cell_findings("fedavg", "dense")
        assert any(f.rule == "MUR902" for f in fs), fs

    @pytest.mark.slow
    def test_full_grid_clean(self):
        # The acceptance sweep: every rule x {dense, circulant, sparse,
        # compressed} resumes byte-identically with zero recompiles.
        assert check_durability(force=True) == []


# ---------------------------------------------------------------------------
# Crash matrix: kill at round boundaries, resume in a fresh orchestrator
# ---------------------------------------------------------------------------


def _crash_resume(cfg_over, kill_at, total, fused=0):
    """Uninterrupted ``total`` rounds vs kill-at-``kill_at``-then-resume in
    a FRESH network (the in-process stand-in for SIGKILL: the snapshot is
    the only state channel)."""
    kw = {"rounds_per_dispatch": fused} if fused else {}
    full = build_network_from_config(_cfg(**cfg_over))
    full.train(rounds=total, **kw)

    first = build_network_from_config(_cfg(**cfg_over))
    first.train(rounds=kill_at, checkpoint_dir=None, **kw)
    import tempfile

    with tempfile.TemporaryDirectory() as snap:
        first.save_checkpoint(snap)
        resumed = build_network_from_config(_cfg(**cfg_over))
        assert resumed.restore_checkpoint(snap) == kill_at
        resumed.train(rounds=total - kill_at, **kw)
    return full, resumed


class TestCrashMatrix:
    def test_dense_every_round_boundary(self, tmp_path):
        # ONE run snapshots at every boundary as it goes (so it doubles
        # as both the uninterrupted reference and the interrupted run);
        # each boundary then gets its own fresh-network resume.
        full = build_network_from_config(_cfg())
        for r in (1, 2, 3):
            full.train(rounds=1)
            full.save_checkpoint(str(tmp_path / f"r{r}"))
        full.train(rounds=1)
        for kill_at in (1, 2, 3):
            resumed = build_network_from_config(_cfg())
            assert resumed.restore_checkpoint(
                str(tmp_path / f"r{kill_at}")
            ) == kill_at
            resumed.train(rounds=4 - kill_at)
            _assert_same_run(full, resumed, f"dense@r{kill_at}")

    def test_fused_chunk_boundary(self):
        # rounds_per_dispatch=2: the snapshot lands on a chunk boundary
        # and the resumed run re-enters the fused scan mid-schedule.
        full, resumed = _crash_resume({}, 2, 4, fused=2)
        _assert_same_run(full, resumed, "fused@r2")

    def test_adaptive_attack_state_survives(self):
        # The closed-loop attacker's bracket/EMA (ATTACK_STATE_KEYS) is
        # round-crossing state: killing mid-bisection and dropping it
        # would resume a silently-cold adversary whose probe restarts
        # from scale_init — the frontier's curves would then depend on
        # where the run got interrupted.
        over = {"attack": {"enabled": True, "type": "gaussian",
                           "percentage": 0.3,
                           "params": {"noise_std": 5.0},
                           "adaptive": {"enabled": True}}}
        full, resumed = _crash_resume(over, 2, 4)
        from murmura_tpu.attacks.adaptive import ATTACK_STATE_KEYS

        carried = set(ATTACK_STATE_KEYS) & set(full.agg_state)
        assert carried, (
            "the cell must actually carry adaptation state for this test "
            "to mean anything"
        )
        _assert_same_run(full, resumed, "adaptive@r2")

    def test_adaptive_ipm_epsilon_survives(self):
        # Adaptive IPM's negation factor (atk_eps, ATTACK_STATE_KEYS —
        # the PR 11 follow-up) is round-crossing state: killing
        # mid-walk and dropping it would resume the attacker at the
        # paper-default epsilon instead of its converged strength.
        over = {"attack": {"enabled": True, "type": "ipm",
                           "percentage": 0.3,
                           "adaptive": {"enabled": True}}}
        full, resumed = _crash_resume(over, 2, 4)
        assert "atk_eps" in full.agg_state, (
            "the cell must actually carry the epsilon walk for this "
            "test to mean anything"
        )
        _assert_same_run(full, resumed, "adaptive_ipm@r2")

    def test_stale_cache_survives_populated(self):
        # SIGKILL with a POPULATED stale cache (STALE_STATE_KEYS): a
        # snapshot that dropped the payload cache or the age stamps
        # would resume serving zeros as "cached" neighbor models, or
        # re-serve expired ones.
        over = {"faults": {"enabled": True, "straggler_prob": 0.4,
                           "link_drop_prob": 0.2, "seed": 11},
                "exchange": {"max_staleness": 2,
                             "staleness_discount": 0.5}}
        full, resumed = _crash_resume(over, 2, 4)
        import numpy as np

        from murmura_tpu.core.stale import STALE_STATE_KEYS

        assert set(STALE_STATE_KEYS) <= set(full.agg_state)
        # The kill point must actually have a populated cache, or the
        # test silently degrades to the dense cell.
        assert np.abs(np.asarray(full.agg_state["stale_cache"])).sum() > 0
        _assert_same_run(full, resumed, "stale@r2")

    def test_int8_ef_carried_residual_survives(self):
        # The EF residual is round-crossing state: killing between rounds
        # and dropping it would silently decay compression accuracy.
        over = {"compression": {"algorithm": "int8", "error_feedback": True,
                                "block": 64}}
        full, resumed = _crash_resume(over, 2, 4)
        from murmura_tpu.ops.compress import COMPRESS_STATE_KEYS

        assert set(COMPRESS_STATE_KEYS) & set(full.agg_state), (
            "the cell must actually carry the EF residual for this test "
            "to mean anything"
        )
        _assert_same_run(full, resumed, "int8ef@r2")

    @pytest.mark.slow
    def test_every_mode_every_boundary(self):
        mode_over = {
            "dense": {},
            "circulant": {"backend": "tpu",
                          "tpu": {"exchange": "ppermute", "num_devices": 1,
                                  "compute_dtype": "float32"}},
            "sparse": {"topology": {"type": "exponential", "num_nodes": 8}},
            "compressed": {"compression": {"algorithm": "int8",
                                           "error_feedback": True,
                                           "block": 64}},
            "adaptive": {"attack": {"enabled": True, "type": "gaussian",
                                    "percentage": 0.3,
                                    "params": {"noise_std": 5.0},
                                    "adaptive": {"enabled": True}}},
            "stale": {"faults": {"enabled": True, "straggler_prob": 0.4,
                                 "link_drop_prob": 0.2, "seed": 11},
                      "exchange": {"max_staleness": 2,
                                   "staleness_discount": 0.5}},
        }
        assert set(mode_over) == set(DURABILITY_MODES)
        for mode, over in mode_over.items():
            for kill_at in (1, 2, 3):
                full, resumed = _crash_resume(over, kill_at, 4)
                _assert_same_run(full, resumed, f"{mode}@r{kill_at}")
        # fused-scan chunk kills: every chunk boundary of a 6-round run
        for kill_at in (2, 4):
            full, resumed = _crash_resume({}, kill_at, 6, fused=2)
            _assert_same_run(full, resumed, f"fused@r{kill_at}")


# ---------------------------------------------------------------------------
# Gang durability (core/gang.py)
# ---------------------------------------------------------------------------


def _gang_cfg(seeds=3, **over):
    return _cfg(sweep={"num_seeds": seeds},
                experiment={"name": "gang-dur", "seed": 5, "rounds": 6},
                **over)


class TestGangDurability:
    def test_gang_resume_every_member_byte_identical(self, tmp_path):
        full = build_gang_from_config(_gang_cfg())
        full.train(rounds=4)

        first = build_gang_from_config(_gang_cfg())
        first.train(rounds=2, checkpoint_dir=str(tmp_path),
                    checkpoint_every=2)
        assert has_checkpoint(tmp_path)
        resumed = build_gang_from_config(_gang_cfg())
        assert resumed.restore_checkpoint(str(tmp_path)) == 2
        resumed.train(rounds=2)

        assert len(full.histories) == len(resumed.histories) == 3
        for s, (hf, hr) in enumerate(zip(full.histories, resumed.histories)):
            assert history_equal(
                {k: list(v) for k, v in hf.items()},
                {k: list(v) for k, v in hr.items()},
            ), f"member {s}"
        for a, b in zip(
            jax.tree_util.tree_leaves(full.params),
            jax.tree_util.tree_leaves(resumed.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gang_snapshot_refuses_member_mismatch(self, tmp_path):
        gang = build_gang_from_config(_gang_cfg())
        gang.train(rounds=2, checkpoint_dir=str(tmp_path),
                   checkpoint_every=2)
        other = build_gang_from_config(_gang_cfg(seeds=2))
        with pytest.raises(ValueError):
            other.restore_checkpoint(str(tmp_path))

    def test_single_run_snapshot_refused_by_gang(self, tmp_path):
        net = build_network_from_config(_cfg())
        net.train(rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        gang = build_gang_from_config(_gang_cfg())
        with pytest.raises(ValueError, match="single run"):
            gang.restore_checkpoint(str(tmp_path))

    def test_gang_snapshot_refused_by_single_run(self, tmp_path):
        # The reverse guard: the gang snapshot carries its member data in
        # extra_meta with NO extra arrays, and flax would happily load the
        # [S, ...]-stacked leaves into a single run — the base hook must
        # refuse on the meta key, not slip through the arrays-only check.
        gang = build_gang_from_config(_gang_cfg())
        gang.train(rounds=2, checkpoint_dir=str(tmp_path),
                   checkpoint_every=2)
        net = build_network_from_config(_cfg())
        with pytest.raises(ValueError, match="gang"):
            net.restore_checkpoint(str(tmp_path))

    def test_freeze_member_degrades_gracefully_and_survives_resume(
        self, tmp_path
    ):
        gang = build_gang_from_config(_gang_cfg())
        gang.train(rounds=2)
        frozen_len = len(gang.histories[1]["round"])
        gang.freeze_member(1, reason="simulated lane death")
        gang.freeze_member(1, reason="idempotent")  # no-op second call
        gang.train(rounds=2)
        # The dead lane's history froze at the failure round; survivors
        # recorded the full run.
        assert len(gang.histories[1]["round"]) == frozen_len
        assert gang.histories[0]["round"] == [1, 2, 3, 4]
        assert gang.histories[2]["round"] == [1, 2, 3, 4]
        assert gang.member_active == [True, False, True]
        with pytest.raises(ValueError, match="out of range"):
            gang.freeze_member(7, reason="nope")
        # Frozen membership is part of the run state: it rides the
        # snapshot and lands in a fresh gang on resume.
        gang.save_checkpoint(str(tmp_path))
        resumed = build_gang_from_config(_gang_cfg())
        resumed.restore_checkpoint(str(tmp_path))
        assert resumed.member_active == [True, False, True]


# ---------------------------------------------------------------------------
# Population durability (population/engine.py + bank.py)
# ---------------------------------------------------------------------------


def _pop_raw(**over):
    r = _raw(
        experiment={"name": "pop-dur", "seed": 3, "rounds": 6},
        topology={"type": "exponential", "num_nodes": 8},
        aggregation={"algorithm": "fedavg", "params": {}},
        data={"adapter": "synthetic",
              "params": {"num_samples": 64, "input_dim": 6,
                         "num_classes": 3}},
        model={"factory": "mlp",
               "params": {"input_dim": 6, "hidden_dims": [8],
                          "num_classes": 3}},
        population={"enabled": True, "virtual_size": 64,
                    "rounds_per_cohort": 2},
    )
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(r.get(k), dict):
            r[k] = {**r[k], **v}
        else:
            r[k] = v
    return r


class TestPopulationDurability:
    def test_population_resume_across_cohort_swaps(self, tmp_path):
        cfg = Config.model_validate(_pop_raw())
        full = build_network_from_config(cfg)
        full.train(rounds=6)

        first = build_network_from_config(Config.model_validate(_pop_raw()))
        # Kill mid-cohort (round 3 is inside the second 2-round cohort).
        first.train(rounds=3, checkpoint_dir=str(tmp_path),
                    checkpoint_every=3)
        resumed = build_network_from_config(
            Config.model_validate(_pop_raw())
        )
        assert resumed.restore_checkpoint(str(tmp_path)) == 3
        resumed.train(rounds=3)

        assert history_equal(_hist(full), _hist(resumed))
        for a, b in zip(
            jax.tree_util.tree_leaves(full.params),
            jax.tree_util.tree_leaves(resumed.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # The state bank (every trained user row + activation mask) is
        # part of "the run": byte-identical too.
        np.testing.assert_array_equal(
            np.asarray(full.bank._rows), np.asarray(resumed.bank._rows)
        )
        np.testing.assert_array_equal(full.bank._has_row,
                                      resumed.bank._has_row)
        assert full.cohorts_seen == resumed.cohorts_seen

    def test_external_bank_reattaches_in_place(self, tmp_path):
        bank_dir = tmp_path / "bank"
        snap = tmp_path / "snap"
        over = {"population": {"bank_dir": str(bank_dir)}}
        first = build_network_from_config(
            Config.model_validate(_pop_raw(**over))
        )
        first.train(rounds=4, checkpoint_dir=str(snap), checkpoint_every=4)
        assert first.bank.path is not None
        rows_before = np.array(first.bank._rows)

        resumed = build_network_from_config(
            Config.model_validate(_pop_raw(**over))
        )
        assert resumed.bank.reattached  # adopted, not truncated
        assert resumed.restore_checkpoint(str(snap)) == 4
        np.testing.assert_array_equal(
            np.asarray(resumed.bank._rows), rows_before
        )
        resumed.train(rounds=2)  # keeps going across a swap

    def test_external_bank_missing_file_refused(self, tmp_path):
        over = {"population": {"bank_dir": str(tmp_path / "bank")}}
        net = build_network_from_config(
            Config.model_validate(_pop_raw(**over))
        )
        net.train(rounds=2, checkpoint_dir=str(tmp_path / "snap"),
                  checkpoint_every=2)
        import shutil

        shutil.rmtree(tmp_path / "bank")
        fresh = build_network_from_config(
            Config.model_validate(_pop_raw(**over))
        )
        with pytest.raises(ValueError, match="bank"):
            fresh.restore_checkpoint(str(tmp_path / "snap"))

    def test_external_bank_wrong_dir_refused(self, tmp_path):
        # A reattachable bank of the RIGHT size under the WRONG dir is
        # some other run's rows; adopting it would silently diverge the
        # continued history — refuse on the recorded path.
        import shutil

        net = build_network_from_config(Config.model_validate(
            _pop_raw(population={"bank_dir": str(tmp_path / "bank_a")})
        ))
        net.train(rounds=2, checkpoint_dir=str(tmp_path / "snap"),
                  checkpoint_every=2)
        (tmp_path / "bank_b").mkdir()
        shutil.copy(tmp_path / "bank_a" / "bank.dat",
                    tmp_path / "bank_b" / "bank.dat")
        fresh = build_network_from_config(Config.model_validate(
            _pop_raw(population={"bank_dir": str(tmp_path / "bank_b")})
        ))
        assert fresh.bank.reattached  # right size — only the path is off
        with pytest.raises(ValueError, match="different bank file"):
            fresh.restore_checkpoint(str(tmp_path / "snap"))

    def test_mismatched_bank_build_refuses_truncation(self, tmp_path):
        # The flushed bank IS the snapshot's row data ("external" mode):
        # a build whose nominal size differs must refuse BEFORE np.memmap
        # mode="w+" truncates it — a restore-time refusal would come
        # after the data is already gone.
        over = {"population": {"bank_dir": str(tmp_path / "bank")}}
        net = build_network_from_config(
            Config.model_validate(_pop_raw(**over))
        )
        net.train(rounds=2, checkpoint_dir=str(tmp_path / "snap"),
                  checkpoint_every=2)
        bank_file = tmp_path / "bank" / "bank.dat"
        before = bank_file.read_bytes()
        with pytest.raises(ValueError, match="refusing to truncate"):
            build_network_from_config(Config.model_validate(_pop_raw(
                population={"bank_dir": str(tmp_path / "bank"),
                            "virtual_size": 128},
            )))
        assert bank_file.read_bytes() == before  # data survived the refusal

    def test_population_snapshot_refuses_config_mismatch(self, tmp_path):
        net = build_network_from_config(Config.model_validate(_pop_raw()))
        net.train(rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        other = build_network_from_config(Config.model_validate(
            _pop_raw(population={"virtual_size": 128})
        ))
        with pytest.raises(ValueError, match="virtual_size"):
            other.restore_checkpoint(str(tmp_path))

    def test_plain_and_population_snapshots_not_interchangeable(
        self, tmp_path
    ):
        plain_snap, pop_snap = tmp_path / "plain", tmp_path / "pop"
        net = build_network_from_config(_cfg())
        net.train(rounds=2, checkpoint_dir=str(plain_snap),
                  checkpoint_every=2)
        pop = build_network_from_config(Config.model_validate(_pop_raw()))
        pop.train(rounds=2, checkpoint_dir=str(pop_snap), checkpoint_every=2)
        with pytest.raises(ValueError, match="population"):
            pop.restore_checkpoint(str(plain_snap))
        with pytest.raises(ValueError, match="extra sections"):
            net.restore_checkpoint(str(pop_snap))

    def test_packed_mask_roundtrip(self):
        rng = np.random.default_rng(3)
        mask = rng.random(1000) < 0.3
        packed = dsnap.embed_bool_mask(mask)
        assert packed.nbytes < mask.size // 7
        np.testing.assert_array_equal(
            dsnap.unpack_bool_mask(packed, mask.size), mask
        )


# ---------------------------------------------------------------------------
# Torn-write detection for the extra-section trio (utils/checkpoint.py)
# ---------------------------------------------------------------------------


class TestTornExtraSection:
    def test_torn_extra_npz_detected(self, tmp_path):
        pop = build_network_from_config(Config.model_validate(_pop_raw()))
        pop.train(rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        pop.train(rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        # A spliced extra section: a round-2 payload copied under the
        # committed round-4 generation name (the commit-point writer
        # cannot produce this; a hand-copy can).
        from murmura_tpu.durability.snapshot import (
            load_npz_bytes,
            npz_bytes,
        )

        extra = load_npz_bytes((tmp_path / "extra.4.npz").read_bytes())
        extra["__round__"] = np.asarray(2, np.int64)
        (tmp_path / "extra.4.npz").write_bytes(npz_bytes(extra))
        fresh = build_network_from_config(Config.model_validate(_pop_raw()))
        with pytest.raises(ValueError, match="[Tt]orn"):
            fresh.restore_checkpoint(str(tmp_path))

    def test_missing_listed_section_detected(self, tmp_path):
        pop = build_network_from_config(Config.model_validate(_pop_raw()))
        pop.train(rounds=2, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        from murmura_tpu.durability.snapshot import (
            load_npz_bytes,
            npz_bytes,
        )

        extra = load_npz_bytes((tmp_path / "extra.2.npz").read_bytes())
        extra.pop("population/bank_has_row")
        (tmp_path / "extra.2.npz").write_bytes(npz_bytes(extra))
        fresh = build_network_from_config(Config.model_validate(_pop_raw()))
        with pytest.raises(ValueError, match="Incomplete snapshot"):
            fresh.restore_checkpoint(str(tmp_path))


# ---------------------------------------------------------------------------
# Telemetry: a resumed run appends to its own event stream
# ---------------------------------------------------------------------------


class TestTelemetryResume:
    def _tele_cfg(self, tmp_path):
        return _cfg(telemetry={"enabled": True, "dir": str(tmp_path / "tele")})

    def test_restore_appends_instead_of_rotating(self, tmp_path):
        snap = tmp_path / "snap"
        net = build_network_from_config(self._tele_cfg(tmp_path))
        net.train(rounds=2, checkpoint_dir=str(snap), checkpoint_every=2)
        run_id = net.telemetry.run_id
        net.telemetry.finalize(history=net.history)

        # The durability restore path flips telemetry into resume mode
        # automatically — no --resume/telemetry_resume flag to forget.
        resumed = build_network_from_config(
            self._tele_cfg(tmp_path), checkpoint_dir=str(snap)
        )
        assert resumed.restore_checkpoint(str(snap)) == 2
        resumed.train(rounds=2)
        resumed.telemetry.finalize(history=resumed.history)

        tele = tmp_path / "tele"
        assert not list(tele.glob("*.prev")), (
            "a resumed run must never rotate its own stream"
        )
        assert resumed.telemetry.run_id == run_id  # stable across resumes
        events = [json.loads(line) for line in
                  (tele / "events.jsonl").read_text().splitlines()]
        kinds = [e.get("type") for e in events]
        assert "run_resumed" in kinds
        # Both generations landed in ONE stream.
        assert kinds.count("run") >= 2

    def test_fresh_run_into_stale_dir_still_rotates(self, tmp_path):
        net = build_network_from_config(self._tele_cfg(tmp_path))
        net.train(rounds=2)
        net.telemetry.finalize(history=net.history)
        # No snapshot in the checkpoint dir => this is a NEW run; the
        # stale stream must rotate exactly as before.
        fresh = build_network_from_config(
            self._tele_cfg(tmp_path), checkpoint_dir=str(tmp_path / "nope")
        )
        fresh.train(rounds=1)
        fresh.telemetry.finalize(history=fresh.history)
        assert list((tmp_path / "tele").glob("*.prev"))


# ---------------------------------------------------------------------------
# Config schema: the durability block
# ---------------------------------------------------------------------------


class TestDurabilityConfig:
    def test_default_block_is_off(self):
        d = _cfg().durability
        assert d.checkpoint_dir is None and not d.resume and d.retries == 0
        assert not d.require_tpu

    def test_resume_without_dir_rejected(self):
        with pytest.raises(Exception, match="checkpoint_dir"):
            _cfg(durability={"resume": True})

    def test_retries_without_dir_rejected(self):
        with pytest.raises(Exception, match="checkpoint_dir"):
            _cfg(durability={"retries": 2})

    def test_delay_ordering_rejected(self):
        with pytest.raises(Exception, match="retry_max_delay_s"):
            _cfg(durability={"checkpoint_dir": "/tmp/x",
                             "retry_base_delay_s": 5.0,
                             "retry_max_delay_s": 1.0})

    def test_distributed_backend_rejected(self):
        raw = _raw(durability={"checkpoint_dir": "/tmp/x"})
        raw["backend"] = "distributed"
        raw["distributed"] = {"num_nodes": 4}
        with pytest.raises(Exception, match="distributed"):
            Config.model_validate(raw)
