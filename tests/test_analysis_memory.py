"""Static memory contracts (analysis/memory.py, MUR1500-1503) — ISSUE 17.

Tier-1 pins the pure halves (budget comparison logic against fabricated
measurements, the MUR1502 alias walk on fabricated HLO, the MUR1503
def-use prover on the doctored combine) plus one representative compiled
cell per contract; the full 108-cell grid sweep is the slow gate (also
run as the package check, `murmura check --memory`).
"""

import json

import pytest

from murmura_tpu.analysis import memory


FAKE_CELL = "fedavg/dense/plain"
FAKE_MEASURED = {
    FAKE_CELL: {
        "temp_bytes": 1000.0,
        "argument_bytes": 2000.0,
        "output_bytes": 1500.0,
        "alias_bytes": 1400.0,
        "generated_bytes": 100.0,
        "peak_bytes": 3200.0,
    },
}


def _fake_sweep(monkeypatch, measured=None):
    monkeypatch.setattr(
        memory, "measure_all",
        lambda force=False: {
            k: dict(v) for k, v in (measured or FAKE_MEASURED).items()
        },
    )


def _write_budgets(tmp_path, budgets, tolerance=None):
    doc = {"budgets": budgets}
    if tolerance is not None:
        doc["tolerance"] = tolerance
    p = tmp_path / "MEMORY.json"
    p.write_text(json.dumps(doc))
    return p


class TestNormalize:
    def test_object_and_none(self):
        class Stats:
            temp_size_in_bytes = 10
            argument_size_in_bytes = 20
            output_size_in_bytes = 8
            alias_size_in_bytes = 6
            generated_code_size_in_bytes = 2

        m = memory.normalize_memory_analysis(Stats())
        assert m["temp_bytes"] == 10.0
        # peak = args + outs - alias + temp + generated
        assert m["peak_bytes"] == 20 + 8 - 6 + 10 + 2
        empty = memory.normalize_memory_analysis(None)
        assert empty["peak_bytes"] == 0.0


class TestMemoryBudgets:
    """MUR1500: the committed residency envelope is a footprint gate."""

    def test_drifted_budget_fails(self, tmp_path, monkeypatch):
        # A deliberate +20% peak change against the committed budget
        # trips the ±10% tolerance and names the metric.
        _fake_sweep(monkeypatch)
        committed = {
            FAKE_CELL: {
                m: v for m, v in FAKE_MEASURED[FAKE_CELL].items()
                if m in memory._GATED_METRICS
            }
        }
        committed[FAKE_CELL]["peak_bytes"] /= 1.20
        p = _write_budgets(tmp_path, committed)
        fs, summaries = memory.memory_budget_findings(p)
        drifted = [f for f in fs if f.rule == "MUR1500"]
        assert drifted and any("peak_bytes" in f.message for f in drifted)
        assert any(
            f.data and f.data.get("key") == FAKE_CELL
            and f.data["delta"] > 0.10
            for f in drifted
        )
        assert summaries and not summaries[0]["within_tolerance"]

    def test_missing_budget_entry_fails(self, tmp_path, monkeypatch):
        _fake_sweep(monkeypatch)
        p = _write_budgets(tmp_path, {})
        fs, _ = memory.memory_budget_findings(p)
        assert any(
            f.rule == "MUR1500" and FAKE_CELL in f.message
            and "--update-memory" in f.message
            for f in fs
        )

    def test_stale_budget_entry_fails(self, tmp_path, monkeypatch):
        _fake_sweep(monkeypatch)
        committed = {
            FAKE_CELL: {
                m: v for m, v in FAKE_MEASURED[FAKE_CELL].items()
                if m in memory._GATED_METRICS
            },
            "ghost_rule/dense/plain": {
                m: 1.0 for m in memory._GATED_METRICS
            },
        }
        p = _write_budgets(tmp_path, committed)
        fs, _ = memory.memory_budget_findings(p)
        assert any(
            f.rule == "MUR1500" and "ghost_rule" in f.message
            and "stale" in f.message
            for f in fs
        )

    def test_file_tolerance_governs(self, tmp_path, monkeypatch):
        # The committed file's "tolerance" field is the reviewable knob —
        # a widened tolerance absorbs drift the module default would flag.
        _fake_sweep(monkeypatch)
        committed = {
            FAKE_CELL: {
                m: v for m, v in FAKE_MEASURED[FAKE_CELL].items()
                if m in memory._GATED_METRICS
            }
        }
        committed[FAKE_CELL]["peak_bytes"] /= 1.20
        p = _write_budgets(tmp_path, committed, tolerance=0.5)
        fs, summaries = memory.memory_budget_findings(p)
        assert fs == []
        assert all(s["within_tolerance"] for s in summaries)

    def test_error_cell_is_a_finding(self, tmp_path, monkeypatch):
        _fake_sweep(monkeypatch, {FAKE_CELL: {"error": "boom"}})
        p = _write_budgets(tmp_path, {})
        fs, summaries = memory.memory_budget_findings(p)
        assert any(
            f.rule == "MUR1500" and "failed to compile" in f.message
            for f in fs
        )
        assert summaries == []

    def test_update_memory_refuses_error_cells(self, tmp_path, monkeypatch):
        # A cell that failed to compile must never be committed as a
        # budget — it would later read as an infinite-drift finding.
        _fake_sweep(monkeypatch, {FAKE_CELL: {"error": "boom"}})
        with pytest.raises(RuntimeError, match="refusing to rewrite"):
            memory.update_memory(tmp_path / "MEMORY.json")

    def test_update_memory_roundtrip(self, tmp_path, monkeypatch):
        # update -> check against the file just written: zero drift.
        _fake_sweep(monkeypatch)
        p = memory.update_memory(tmp_path / "MEMORY.json")
        fs, summaries = memory.memory_budget_findings(p)
        assert fs == []
        assert all(
            s[f"{m}_delta"] == 0.0
            for s in summaries for m in memory._GATED_METRICS
        )

    def test_representative_cell_matches_committed(self):
        # One real compiled cell of the grid against the committed file —
        # the tier-1 drift canary (the full sweep is the slow gate).
        committed = memory.load_memory()
        key = memory.memory_key("fedavg", "dense", "plain")
        assert key in committed, "MEMORY.json is missing the canary cell"
        measured = memory.measure_cell("fedavg", "dense", "plain")
        tol = memory.TOLERANCE
        for metric in memory._GATED_METRICS:
            assert abs(
                memory._rel_delta(measured[metric], committed[key][metric])
            ) <= tol, (metric, measured[metric], committed[key][metric])


class TestShardedScaling:
    """MUR1501: per-device peak obeys the P/shards law (8 forced CPU
    devices via conftest)."""

    def test_scaling_cell_clean(self):
        fs = memory.scaling_cell_findings("fedavg", "circulant")
        assert fs == [], "\n".join(f.message for f in fs)

    def test_peaks_actually_shrink(self):
        peaks = {
            s: memory.sharded_cell_peak("fedavg", "circulant", s)
            for s in memory.SCALING_SHARDS
        }
        assert peaks[1] > peaks[2] > peaks[4]
        # The deltas isolate the sharded [N, P] class: d12 ~ 2 x d24.
        ratio = (peaks[1] - peaks[2]) / (peaks[2] - peaks[4])
        assert abs(ratio - 2.0) <= 2.0 * memory._RATIO_TOL, peaks


class TestDonationCompleteness:
    """MUR1502: every carried leaf donated, by leaf."""

    HLO = (
        "HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
        "{1}: (2, {}, must-alias) }\n"
        "ENTRY %main () -> f32[] {\n}\n"
    )

    def test_alias_header_parse(self):
        assert memory.aliased_param_numbers(self.HLO) == frozenset({0, 2})
        assert memory.aliased_param_numbers("HloModule m\n") == frozenset()

    def test_unaliased_leaf_is_flagged_with_key_group(self):
        donated = [
            (0, "[0]['w']"),                      # params leaf — aliased
            (1, "[1]['compress_residual']"),      # EF leaf — NOT aliased
            (2, "[1]['trust']"),                  # rule state — aliased
        ]
        fs = memory.donation_gap_findings(
            self.HLO, donated, "fedavg", "dense", "int8_ef"
        )
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "MUR1502"
        assert "compress_residual" in f.message
        assert f.data["group"] == "COMPRESS_STATE_KEYS"

    def test_pruned_leaf_is_exempt(self):
        # param number None = XLA pruned the arg as dead before the alias
        # header was built — no buffer exists to alias.
        fs = memory.donation_gap_findings(
            self.HLO, [(None, "[1]['pipe_adj']")],
            "fedavg", "circulant", "pipeline",
        )
        assert fs == []

    def test_params_leaf_classified_as_params(self):
        fs = memory.donation_gap_findings(
            "HloModule m\nENTRY %main () -> f32[] {\n}\n",
            [(0, "[0]['b']")], "fedavg", "dense", "plain",
        )
        assert len(fs) == 1 and fs[0].data["group"] == "params"

    def test_representative_cell_donation_holds(self):
        # The real compiled canary cell (shared memoized compile) walks
        # clean: params + carried agg state all aliased.
        assert memory.donation_cell_findings("fedavg", "dense", "plain") == []

    def test_ef_cell_donation_holds(self):
        fs = memory.donation_cell_findings("fedavg", "dense", "int8_ef")
        assert fs == [], "\n".join(f.message for f in fs)


class TestOverlapDependence:
    """MUR1503: no train -> buffered-aggregation def-use path."""

    def test_doctored_combine_is_flagged(self):
        # The negative control: a combine that reads this round's
        # training output MUST show a dependence path.
        res = memory.scope_dependence_path(
            memory.doctored_combine_hlo(),
            memory._TRAIN_SCOPE, memory._AGG_SCOPE,
        )
        assert res is not None
        nsrc, ndst, found = res
        assert nsrc > 0 and ndst > 0 and found

    def test_missing_scope_returns_none(self):
        res = memory.scope_dependence_path(
            "HloModule m\nENTRY %main () -> f32[] {\n"
            "  ROOT %c = f32[] constant(0)\n}\n",
            memory._TRAIN_SCOPE, memory._AGG_SCOPE,
        )
        assert res is None

    def test_pipelined_cell_has_no_path_and_serialized_does(self):
        # The contract on a real cell pair (shared grid compiles): the
        # pipelined buffered aggregation is dataflow-independent of this
        # round's training; the serialized program is the positive
        # control.
        piped = memory.scope_dependence_path(
            memory.cell_hlo("fedavg", "dense", "pipeline"),
            memory._TRAIN_SCOPE, memory._AGG_SCOPE,
        )
        plain = memory.scope_dependence_path(
            memory.cell_hlo("fedavg", "dense", "plain"),
            memory._TRAIN_SCOPE, memory._AGG_SCOPE,
        )
        assert piped is not None and plain is not None
        assert plain[2], "serialized control lost its train->agg path"
        assert not piped[2], "pipelined aggregation depends on training"

    def test_overlap_cell_findings_clean(self):
        fs = memory.overlap_cell_findings("fedavg", "dense")
        assert fs == [], "\n".join(f.message for f in fs)


class TestWiring:
    """CLI / run_check_detailed / coverage wiring."""

    def test_run_check_detailed_memory_pass(self, tmp_path, monkeypatch):
        from murmura_tpu import analysis
        from murmura_tpu.analysis.lint import Finding

        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        marker = Finding("MUR1500", "x.py", 1, "marker finding")
        monkeypatch.setattr(
            memory, "check_memory", lambda force=False: [marker]
        )
        monkeypatch.setattr(
            memory, "memory_summaries",
            lambda: [{"kind": "memory_summary", "key": "k"}],
        )
        findings, records = analysis.run_check_detailed(
            [clean], contracts=False, ir=False,
            flow=False, durability=False, adaptive=False, staleness=False,
            pipeline=False, sharded=False, compose=False, memory=True,
        )
        assert marker in findings
        assert {"kind": "memory_summary", "key": "k"} in records
        # memory=False skips the pass entirely.
        findings, records = analysis.run_check_detailed(
            [clean], contracts=False, ir=False,
            flow=False, durability=False, adaptive=False, staleness=False,
            pipeline=False, sharded=False, compose=False, memory=False,
        )
        assert marker not in findings and records == []

    def test_json_records_keep_memory_summary_kind(self):
        from murmura_tpu.analysis import format_findings_json

        out = format_findings_json(
            [], [{"kind": "memory_summary", "key": "k", "peak_bytes": 1.0}]
        )
        rec = json.loads(out)
        assert rec["kind"] == "memory_summary" and rec["key"] == "k"

    def test_cli_update_memory_flag(self, tmp_path, monkeypatch):
        from click.testing import CliRunner

        from murmura_tpu import cli

        target = tmp_path / "MEMORY.json"
        monkeypatch.setattr(memory, "update_memory", lambda: target)
        result = CliRunner().invoke(cli.app, ["check", "--update-memory"])
        assert result.exit_code == 0, result.output
        assert "MEMORY.json" in result.output

    def test_lint_rules_registered(self):
        from murmura_tpu.analysis.lint import RULES

        assert RULES["MUR1500"] == "memory-budget"
        assert RULES["MUR1501"] == "sharded-memory-scaling"
        assert RULES["MUR1502"] == "donation-completeness"
        assert RULES["MUR1503"] == "overlap-dependence"

    def test_check_coverage_sees_memory_families(self):
        # Every @_family in analysis/memory.py must be reachable from
        # check_memory — ir.check_coverage guards the wiring.
        from murmura_tpu.analysis import ir

        assert set(memory.MEMORY_CHECK_FAMILIES) == {
            "check_memory_budgets",
            "check_sharded_memory_scaling",
            "check_donation_completeness",
            "check_overlap_dependence",
        }
        assert ir.check_coverage() == []

    def test_network_step_memory_analysis(self):
        # The runtime twin: same normalized fields off the shared AOT
        # compile, on a tiny simulation network.
        from murmura_tpu.config import Config
        from murmura_tpu.utils.factories import build_network_from_config

        cfg = Config.model_validate({
            "experiment": {"name": "mem-twin", "seed": 0, "rounds": 1},
            "topology": {"type": "ring", "num_nodes": 4},
            "aggregation": {"algorithm": "fedavg", "params": {}},
            "training": {"local_epochs": 1, "batch_size": 4, "lr": 0.05},
            "data": {"adapter": "synthetic",
                     "params": {"num_samples": 16, "input_shape": [6],
                                "num_classes": 3}},
            "model": {"factory": "mlp",
                      "params": {"input_dim": 6, "hidden_dims": [8],
                                 "num_classes": 3}},
            "backend": "simulation",
        })
        net = build_network_from_config(cfg)
        mem = net.step_memory_analysis()
        assert set(mem) >= {
            "temp_bytes", "argument_bytes", "output_bytes", "peak_bytes",
        }
        assert mem["argument_bytes"] > 0
        # Shared compile: cost analysis reuses the same executable.
        cost = net.step_cost_analysis()
        assert cost.get("flops", 0) >= 0
        assert net._step_compiled() is net._aot_compiled


@pytest.mark.slow
class TestFullGate:
    """The package gate: the full grid sweep + every family, clean."""

    def test_check_memory_clean(self):
        fs = memory.check_memory()
        assert fs == [], "\n".join(
            f"{f.path}:{f.line}: {f.rule} {f.message}" for f in fs
        )

    def test_update_memory_roundtrip_real(self, tmp_path):
        p = memory.update_memory(tmp_path / "MEMORY.json")
        fs, summaries = memory.memory_budget_findings(p)
        assert fs == []
        assert summaries and all(s["within_tolerance"] for s in summaries)
