"""Unified telemetry subsystem (docs/OBSERVABILITY.md; ISSUE 4).

Covers the acceptance contracts:
- default off => byte-identical histories and an unchanged traced round
  program (the compiled-program twin of the faults-off bit-identity test);
- the manifest/event-stream writer: atomic finalization, append-only
  events, resume semantics, torn-tail tolerance;
- phase_times semantics across dispatch modes (per-round wall times vs
  the fused elapsed/k split), including the checkpoint/restore path;
- the in-jit audit taps end-to-end on the chaos_churn.yaml scenario:
  `murmura report` surfaces per-node krum rejection counts, and tap
  recording toggles cause zero recompiles (the MUR402 contract, exercised
  here through the real orchestrator under tpu.recompile_guard).
"""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from murmura_tpu.config import Config, load_config
from murmura_tpu.telemetry.schema import MANIFEST_SCHEMA_VERSION
from murmura_tpu.telemetry.writer import (
    TelemetryWriter,
    events_of_type,
    iter_events,
    read_manifest,
)
from murmura_tpu.utils.factories import build_network_from_config

EXAMPLES = Path(__file__).resolve().parent.parent / "examples" / "configs"


def _base_cfg(**overrides):
    cfg = {
        "experiment": {"name": "telemetry", "seed": 3, "rounds": 4},
        "topology": {"type": "ring", "num_nodes": 4},
        "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {
            "adapter": "synthetic",
            "params": {"num_samples": 320, "input_dim": 8, "num_classes": 3},
        },
        "model": {
            "factory": "mlp",
            "params": {"input_dim": 8, "hidden_dims": [16], "num_classes": 3},
        },
        "backend": "simulation",
    }
    cfg.update(overrides)
    return Config.model_validate(cfg)


def _tel(tmp_path, **overrides):
    t = {"enabled": True, "dir": str(tmp_path / "run")}
    t.update(overrides)
    return t


class TestWriter:
    def test_manifest_and_event_roundtrip(self, tmp_path):
        w = TelemetryWriter(tmp_path / "r", run_id="abc")
        w.emit("phase_times", round=0, mode="per_round", wall_s=0.5)
        w.add_counters({"reconnects": 2})
        w.add_counters({"reconnects": 1, "send_failures": 1})
        path = w.finalize(history={"round": [1], "mean_accuracy": [0.5]})
        w.close()
        m = read_manifest(tmp_path / "r")
        assert path.name == "manifest.json"
        assert m["schema_version"] == MANIFEST_SCHEMA_VERSION
        assert m["run_id"] == "abc"
        assert m["finalized"] is True
        assert m["history"]["round"] == [1]
        assert m["counters"] == {"reconnects": 3.0, "send_failures": 1.0}
        events = list(iter_events(tmp_path / "r"))
        # run-started marker + the emitted event, in seq order
        assert [e["type"] for e in events] == ["run", "phase_times"]
        assert [e["seq"] for e in events] == [0, 1]

    def test_reopen_with_resume_appends_and_marks_resumed(self, tmp_path):
        w = TelemetryWriter(tmp_path / "r", run_id="abc")
        w.emit("phase_times", round=0, mode="per_round", wall_s=0.1)
        w.finalize(history={})
        w.close()
        w2 = TelemetryWriter(tmp_path / "r", resume=True)  # continuation
        w2.emit("phase_times", round=1, mode="per_round", wall_s=0.2)
        w2.finalize(history={})
        w2.close()
        m = read_manifest(tmp_path / "r")
        assert m["resumed"] is True
        assert m["run_id"] == "abc"  # stable across resume
        rounds = [e["round"] for e in events_of_type(tmp_path / "r", "phase_times")]
        assert rounds == [0, 1]

    def test_fresh_run_into_existing_dir_rotates_stale_stream(self, tmp_path):
        """A re-run of a deterministically-named experiment must NOT
        append to the prior run's events — `murmura report` would
        double-count every sum.  The stale stream rotates to *.prev."""
        w = TelemetryWriter(tmp_path / "r", run_id="old")
        w.add_counters({"reconnects": 5})
        w.emit("phase_times", round=0, mode="per_round", wall_s=0.1)
        w.finalize(history={})
        w.close()
        w2 = TelemetryWriter(tmp_path / "r")  # fresh run, same dir
        w2.emit("phase_times", round=0, mode="per_round", wall_s=0.2)
        w2.finalize(history={})
        w2.close()
        m = read_manifest(tmp_path / "r")
        assert m["resumed"] is False
        assert m["run_id"] != "old"
        assert m["counters"] == {}  # not inherited from the stale run
        records = events_of_type(tmp_path / "r", "phase_times")
        assert [r["wall_s"] for r in records] == [0.2]  # no double count
        assert (tmp_path / "r" / "events.jsonl.prev").exists()

    def test_torn_final_line_tolerated(self, tmp_path):
        w = TelemetryWriter(tmp_path / "r")
        w.emit("round", round=1, metrics={})
        w.close()
        with open(tmp_path / "r" / "events.jsonl", "a") as f:
            f.write('{"type": "round", "torn')  # crash mid-append
        events = list(iter_events(tmp_path / "r"))
        assert [e["type"] for e in events] == ["run", "round"]

    def test_record_taps_toggle_is_host_side(self, tmp_path):
        w = TelemetryWriter(tmp_path / "r", record_taps=False)
        w.round_event(1, {"accuracy": [0.5], "agg_tap_selected_by": [1.0]})
        w.record_taps = True
        w.round_event(2, {"accuracy": [0.6], "agg_tap_selected_by": [2.0]})
        w.close()
        rounds = events_of_type(tmp_path / "r", "round")
        assert "agg_tap_selected_by" not in rounds[0]["metrics"]
        assert rounds[1]["metrics"]["agg_tap_selected_by"] == [2.0]

    def test_nonfinite_values_survive_json(self, tmp_path):
        w = TelemetryWriter(tmp_path / "r")
        w.emit("round", metrics={"loss": float("nan")})
        w.close()
        assert events_of_type(tmp_path / "r", "round")  # parseable


class TestDefaultOffByteIdentity:
    def test_history_identical_without_and_with_disabled_block(self):
        """telemetry absent or {enabled: false} => byte-identical run (the
        acceptance contract: the compiled program, inputs, and random
        streams are untouched)."""
        h0 = build_network_from_config(_base_cfg()).train(rounds=4)
        h1 = build_network_from_config(
            _base_cfg(telemetry={"enabled": False})
        ).train(rounds=4)
        assert h0 == h1

    def test_untapped_program_is_the_default_program(self):
        """audit_taps=False traces the identical round program as the
        default build — the jaxpr-structure half of the byte-identity
        contract (MUR400 pins the tapped/untapped collective inventories
        in `check --ir`)."""
        import jax
        import jax.numpy as jnp

        from murmura_tpu.analysis.ir import jaxpr_signature

        from murmura_tpu.aggregation import build_aggregator
        from murmura_tpu.core.rounds import build_round_program
        from murmura_tpu.data.registry import build_federated_data
        from murmura_tpu.utils.factories import resolve_model

        cfg = _base_cfg()
        data = build_federated_data(
            cfg.data.adapter, cfg.data.params,
            num_nodes=4, seed=cfg.experiment.seed,
        )
        model = resolve_model(cfg, data)
        agg = build_aggregator("krum", {"num_compromised": 1}, total_rounds=4)

        def trace(**kwargs):
            prog = build_round_program(
                model, agg, data, total_rounds=4, batch_size=16, **kwargs
            )
            args = (
                prog.init_params,
                {k: jnp.asarray(v) for k, v in prog.init_agg_state.items()},
                jax.random.PRNGKey(0),
                jnp.asarray(np.ones((4, 4), np.float32) - np.eye(4, dtype=np.float32)),
                jnp.zeros((4,), jnp.float32),
                jnp.asarray(0.0, jnp.float32),
                {k: jnp.asarray(v) for k, v in prog.data_arrays.items()},
            )
            return jaxpr_signature(jax.make_jaxpr(prog.train_step)(*args))

        assert trace() == trace(audit_taps=False)  # default == explicit off

    def test_taps_add_outputs_but_histories_stay_aligned(self, tmp_path):
        """With taps ON the ordinary history keys are unchanged — taps only
        ADD agg_tap_* columns."""
        h0 = build_network_from_config(_base_cfg()).train(rounds=3)
        cfg = _base_cfg(telemetry=_tel(tmp_path, audit_taps=True))
        h1 = build_network_from_config(cfg).train(rounds=3)
        for k, v in h0.items():
            assert h1[k] == v, f"history[{k!r}] changed under audit taps"
        assert any(k.startswith("agg_tap_") for k in h1)

    def test_sub_settings_require_enabled(self):
        with pytest.raises(Exception, match="telemetry.enabled"):
            _base_cfg(telemetry={"enabled": False, "audit_taps": True})


class TestPhaseTimes:
    """Satellite: round-times semantics across dispatch modes, pinned on
    the manifest's phase_times records (fused elapsed/k split vs per-round
    wall times), including the checkpoint/restore path."""

    def test_per_round_dispatch_records_wall_times(self, tmp_path):
        cfg = _base_cfg(telemetry=_tel(tmp_path))
        net = build_network_from_config(cfg)
        net.train(rounds=4)
        run = tmp_path / "run"
        records = events_of_type(run, "phase_times")
        assert [r["round"] for r in records] == [0, 1, 2, 3]
        assert all(r["mode"] == "per_round" for r in records)
        assert all(r["wall_s"] > 0 for r in records)
        # phase_times mirror round_times exactly — one schema, one truth.
        assert [r["wall_s"] for r in records] == pytest.approx(net.round_times)
        m = read_manifest(run)
        assert m["finalized"] and m["history"]["round"] == [1, 2, 3, 4]

    def test_fused_dispatch_records_amortized_times(self, tmp_path):
        cfg = _base_cfg(telemetry=_tel(tmp_path))
        net = build_network_from_config(cfg)
        net.train(rounds=4, rounds_per_dispatch=2)
        records = events_of_type(tmp_path / "run", "phase_times")
        assert [r["round"] for r in records] == [0, 1, 2, 3]
        assert all(r["mode"] == "fused" and r["chunk"] == 2 for r in records)
        # elapsed/k: the two rounds of one chunk share one amortized time.
        assert records[0]["wall_s"] == pytest.approx(records[1]["wall_s"])
        assert records[2]["wall_s"] == pytest.approx(records[3]["wall_s"])
        assert [r["wall_s"] for r in records] == pytest.approx(net.round_times)

    def test_checkpoint_restore_path(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        cfg = _base_cfg(telemetry=_tel(tmp_path))
        net = build_network_from_config(cfg)
        net.train(rounds=2, checkpoint_dir=ckpt, checkpoint_every=2)
        # Fresh orchestrator (same config => same run dir) CONTINUING the
        # run: telemetry_resume appends to the event stream (the CLI
        # --resume path); without it the stale stream would rotate.
        net2 = build_network_from_config(cfg, telemetry_resume=True)
        assert net2.restore_checkpoint(ckpt) == 2
        net2.train(rounds=2)
        run = tmp_path / "run"
        records = events_of_type(run, "phase_times")
        assert [r["round"] for r in records] == [0, 1, 2, 3]
        ckpts = events_of_type(run, "checkpoint")
        saves = [e for e in ckpts if e["action"] == "save"]
        restores = [e for e in ckpts if e["action"] == "restore"]
        assert saves and all(e["duration_s"] > 0 for e in saves)
        assert [e["round"] for e in restores] == [2]
        m = read_manifest(run)
        assert m["resumed"] is True
        assert m["history"]["round"] == [1, 2, 3, 4]

    def test_memory_events_emitted_when_enabled(self, tmp_path):
        cfg = _base_cfg(telemetry=_tel(tmp_path, memory_stats=True))
        build_network_from_config(cfg).train(rounds=2)
        mem = events_of_type(tmp_path / "run", "memory")
        # CPU may expose no stats (null) — the event must still exist.
        assert [e["round"] for e in mem] == [0, 1]

    def test_round_events_carry_per_node_arrays_and_in_degree(self, tmp_path):
        cfg = _base_cfg(telemetry=_tel(tmp_path, audit_taps=True))
        build_network_from_config(cfg).train(rounds=2)
        rounds = events_of_type(tmp_path / "run", "round")
        assert [e["round"] for e in rounds] == [1, 2]
        for e in rounds:
            assert len(e["metrics"]["accuracy"]) == 4
            assert len(e["metrics"]["agg_tap_selected_by"]) == 4
            assert e["in_degree"] == [2.0, 2.0, 2.0, 2.0]  # ring(4)


class TestAuditTapsChaos:
    """Acceptance: with audit taps on, `murmura report` shows per-node
    krum rejection counts for the chaos_churn.yaml scenario."""

    @pytest.fixture(scope="class")
    def chaos_run(self, tmp_path_factory):
        run_dir = tmp_path_factory.mktemp("chaos") / "run"
        cfg = load_config(EXAMPLES / "chaos_churn.yaml")
        cfg.experiment.rounds = 6
        cfg.experiment.verbose = False
        cfg.telemetry.enabled = True
        cfg.telemetry.audit_taps = True
        cfg.telemetry.dir = str(run_dir)
        build_network_from_config(cfg).train(rounds=6)
        return run_dir

    def test_report_shows_per_node_krum_rejection_counts(self, chaos_run):
        from murmura_tpu.telemetry.report import build_report

        report = build_report(chaos_run)
        taps = report["taps"]
        assert len(taps["rejections"]) == 8
        assert len(taps["selected_by"]) == 8
        # The chaos scenario rejects SOMEONE: 2 gaussian attackers and a
        # NaN-diverging node cannot all be krum winners.
        assert sum(taps["rejections"]) > 0
        assert all(r >= 0 for r in taps["rejections"])

    def test_report_shows_quarantine_flags(self, chaos_run):
        from murmura_tpu.telemetry.report import build_report

        faults = build_report(chaos_run)["faults"]
        q = faults["quarantined_rounds"]
        # Node 2 is the NaN injector: quarantined on (alive) rounds, and
        # nobody else ever is (chaos_churn.yaml module comment).
        assert q[2] >= 1
        assert all(v == 0 for i, v in enumerate(q) if i != 2)

    def test_report_cli_renders(self, chaos_run):
        from click.testing import CliRunner

        from murmura_tpu.cli import app

        result = CliRunner().invoke(app, ["report", str(chaos_run)])
        assert result.exit_code == 0, result.output
        # Table headers may soft-wrap at narrow widths; the section title
        # and node rows must render regardless.
        assert "Per-node audit" in result.output
        as_json = CliRunner().invoke(app, ["report", str(chaos_run), "--json"])
        assert as_json.exit_code == 0, as_json.output
        rep = json.loads(as_json.output)
        assert len(rep["taps"]["rejections"]) == 8
        assert rep["faults"]["quarantined_rounds"][2] >= 1


class TestTapRecompileContract:
    def test_tap_toggling_across_rounds_zero_recompiles(self, tmp_path):
        """MUR402 end-to-end: a taps-enabled run under tpu.recompile_guard,
        with tap RECORDING toggled between train() calls — the tapped
        executable must be reused (recording is host-side only).  The IR
        twin runs in `murmura check --ir` (analysis/ir.py)."""
        cfg = _base_cfg(
            telemetry=_tel(tmp_path, audit_taps=True),
            tpu={"recompile_guard": True},
        )
        net = build_network_from_config(cfg)
        net.train(rounds=2)  # warmup + one guarded recording round
        net.telemetry.record_taps = False
        net.train(rounds=1)  # guarded, taps ignored
        net.telemetry.record_taps = True
        net.train(rounds=1)  # guarded, taps recorded again
        # No RecompileError raised; post-warmup rounds compiled nothing.
        assert net.last_compile_report is not None
        assert all(c == 0 for _label, c in net.last_compile_report)

    def test_check_ir_telemetry_rules_clean(self):
        """MUR400/MUR402 hold for the committed package (memoized sweep,
        shared with the tier-1 check gate)."""
        from murmura_tpu.analysis.ir import check_ir

        bad = [f for f in check_ir() if f.rule in ("MUR400", "MUR402")]
        assert not bad, bad


def test_telemetry_example_config_validates():
    cfg = load_config(EXAMPLES / "telemetry_audit_report.yaml")
    assert cfg.telemetry.enabled and cfg.telemetry.audit_taps
    assert cfg.faults.enabled and cfg.aggregation.algorithm == "krum"


@pytest.mark.slow
def test_fused_profile_window_opens_mid_chunk(tmp_path):
    """A profile window starting strictly INSIDE a fused chunk must still
    capture: the chunk dispatches rounds [0, 4) as one program, so overlap
    — not containment of the chunk's first round — opens the window."""
    cfg = _base_cfg(
        telemetry=_tel(
            tmp_path, profile_start_round=1, profile_rounds=1,
            profile_dir=str(tmp_path / "trace"),
        )
    )
    build_network_from_config(cfg).train(rounds=4, rounds_per_dispatch=4)
    prof = events_of_type(tmp_path / "run", "profile")
    assert {e["status"] for e in prof} == {"started", "stopped"}
    assert any((tmp_path / "trace").rglob("*")), "no trace files captured"
