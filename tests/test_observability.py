"""The fleet observability plane (ISSUE 19): the metrics registry and
its OpenMetrics render/parse round trip, the offline event-stream fold,
trace spans and their Chrome/Perfetto export, the cross-run registry,
``murmura top``'s renderer, the serve lifecycle events + enriched
ping/list ops, the dispatch envelope's RetryStats, and the MUR1700-1703
verdict helpers — each contract negative-tested with doctored inputs.

Tier-1 runs ONE tiny drained daemon (module-scoped fixture: 5-node ring,
2 tenants, 2 rounds) and projects every read-path assertion off it; the
full in-daemon MUR1700-1703 family (including the scraped-vs-reference
interference soak) runs in the package gate (``murmura check
--observe``), exercised here under ``-m slow``.
"""

import json
import shutil
import time
import types

import pytest
from click.testing import CliRunner

from murmura_tpu.analysis.observe import (
    interference_problems,
    metrics_ledger_parity,
    schema_discipline_problems,
)
from murmura_tpu.cli import app
from murmura_tpu.config import Config
from murmura_tpu.durability.dispatch import (
    RetryPolicy,
    RetryStats,
    run_with_retry,
)
from murmura_tpu.serve.daemon import ServeDaemon
from murmura_tpu.telemetry import top as top_mod
from murmura_tpu.telemetry.metrics import (
    METRICS_SNAPSHOT_FILE,
    MetricsRegistry,
    fold_run_events,
    parse_openmetrics,
    render_openmetrics,
    write_openmetrics_snapshot,
)
from murmura_tpu.telemetry.registry import (
    find_latest,
    index_runs,
    render_rows,
)
from murmura_tpu.telemetry.schema import MANIFEST_SCHEMA_VERSION
from murmura_tpu.telemetry.spans import (
    LANE_LIFECYCLE,
    LANE_ROUNDS,
    build_spans,
    to_chrome_trace,
    validate_spans,
    write_chrome_trace,
)
from murmura_tpu.telemetry.writer import events_of_type, read_manifest


def _tenant(seed, rounds=2):
    return {
        "experiment": {"name": f"tenant-{seed}", "seed": seed,
                       "rounds": rounds},
        "topology": {"type": "ring", "num_nodes": 5},
        "aggregation": {"algorithm": "fedavg"},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 40, "input_shape": [6],
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 6, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "simulation",
    }


@pytest.fixture(scope="module")
def drained(tmp_path_factory):
    """One drained two-tenant daemon shared by every read-path test."""
    tmp = tmp_path_factory.mktemp("obs")
    raw = _tenant(0)
    raw["serve"] = {"state_dir": str(tmp / "state"), "capacity": 2,
                    "checkpoint_every": 1}
    daemon = ServeDaemon(Config.model_validate(raw))
    ids = [daemon.submit_config(_tenant(5))["id"],
           daemon.submit_config(_tenant(6))["id"]]
    daemon.drain()
    return daemon, ids


def _run_dir(daemon, sub_id):
    return daemon.state_dir / "telemetry" / sub_id


def _v1_run(path):
    """A hand-built schema-v1 run dir: no per-event ``t``, no serve
    events — the MUR1703 old-streams-still-render probe."""
    path.mkdir(parents=True)
    (path / "manifest.json").write_text(json.dumps({
        "schema_version": 1, "kind": "run", "run_id": "v1-probe",
        "created_unix": 1000.0, "finalized": True,
        "finalized_unix": 1004.0, "counters": {},
        "history": {"round": [1, 2], "mean_accuracy": [0.5, 0.6],
                    "mean_loss": [1.0, 0.9]},
    }))
    events = [
        {"type": "run", "seq": 0, "status": "started"},
        {"type": "round", "seq": 1, "round": 1,
         "metrics": {"accuracy": [0.5]}},
        {"type": "phase_times", "seq": 2, "round": 0,
         "mode": "per_round", "wall_s": 0.5},
        {"type": "round", "seq": 3, "round": 2,
         "metrics": {"accuracy": [0.6]}},
        {"type": "phase_times", "seq": 4, "round": 1,
         "mode": "per_round", "wall_s": 0.5},
    ]
    (path / "events.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in events)
    )
    return path


class TestMetricsRegistry:
    def test_render_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.inc("c", 2.0, labels={"tenant": "a"})
        reg.inc("c", 3.0, labels={"tenant": "b"})
        reg.set_gauge("g", 1.5)
        reg.observe("h", 0.02, labels={"mode": "per_round"})
        reg.observe("h", 7.0, labels={"mode": "per_round"})
        text = render_openmetrics(reg)
        assert text.endswith("# EOF\n")
        parsed = parse_openmetrics(text)
        assert parsed[("c_total", (("tenant", "a"),))] == 2.0
        assert parsed[("c_total", (("tenant", "b"),))] == 3.0
        assert parsed[("g", ())] == 1.5
        assert parsed[("h_count", (("mode", "per_round"),))] == 2
        assert parsed[("h_sum", (("mode", "per_round"),))] == 7.02
        # Cumulative buckets: the 10s bucket holds both observations.
        assert parsed[("h_bucket", (("le", "10"), ("mode", "per_round")))] == 2
        assert parsed[("h_bucket", (("le", "+Inf"), ("mode", "per_round")))] == 2

    def test_counter_monotone_and_types_exclusive(self):
        reg = MetricsRegistry()
        reg.inc("c")
        with pytest.raises(ValueError):
            reg.inc("c", -1.0)
        with pytest.raises(ValueError):
            reg.set_gauge("c", 1.0)

    def test_max_gauge_keeps_peak(self):
        reg = MetricsRegistry()
        reg.max_gauge("peak", 10.0)
        reg.max_gauge("peak", 4.0)
        assert reg.value("peak") == 10.0


class TestFoldRunEvents:
    def test_drained_tenant_folds(self, drained):
        daemon, ids = drained
        reg = MetricsRegistry()
        fold_run_events(reg, _run_dir(daemon, ids[0]),
                        labels={"tenant": ids[0]})
        assert reg.value("murmura_rounds", {"tenant": ids[0]}) == 2
        for name in ("submitted", "admitted", "generation_start",
                     "generation_done"):
            assert reg.value(
                "murmura_serve_events", {"tenant": ids[0], "event": name},
            ) == 1, name
        parsed = parse_openmetrics(render_openmetrics(reg))
        assert parsed[(
            "murmura_round_wall_seconds_count", (("mode", "gang_per_round"),
                                                 ("tenant", ids[0])),
        )] == 2

    def test_snapshot_written_durably(self, drained, tmp_path):
        daemon, ids = drained
        reg = MetricsRegistry()
        fold_run_events(reg, _run_dir(daemon, ids[0]))
        path = write_openmetrics_snapshot(tmp_path / "snap", reg)
        assert path.name == METRICS_SNAPSHOT_FILE
        assert path.read_text().endswith("# EOF\n")


class TestMetricsLedgerParityMUR1700:
    def test_drained_daemon_is_parity_clean(self, drained):
        daemon, _ = drained
        assert metrics_ledger_parity(daemon) == []

    def test_doctored_scrape_detected(self, drained):
        daemon, _ = drained
        text = render_openmetrics(daemon.metrics_registry())
        doctored = text.replace(
            'murmura_serve_lifetime_total{counter="admissions"} 2',
            'murmura_serve_lifetime_total{counter="admissions"} 7',
        )
        assert doctored != text  # the sample we doctor must exist
        problems = metrics_ledger_parity(daemon, text=doctored)
        assert any("admissions" in p for p in problems)

    def test_dropped_event_detected(self, drained, tmp_path):
        # Scrape, THEN drop a round event from a copy of the durable
        # state: the scrape now shows a count the replay cannot
        # reconstruct — the MUR1700 negative.
        daemon, ids = drained
        text = render_openmetrics(daemon.metrics_registry())
        copy = tmp_path / "state"
        shutil.copytree(daemon.state_dir, copy)
        stream = copy / "telemetry" / ids[0] / "events.jsonl"
        kept = [
            line for line in stream.read_text().splitlines()
            if json.loads(line).get("type") != "round"
        ]
        stream.write_text("".join(line + "\n" for line in kept))
        stub = types.SimpleNamespace(state_dir=copy)
        problems = metrics_ledger_parity(stub, text=text)
        assert any("round" in p and ids[0] in p for p in problems)


class TestScrapeInterferenceMUR1701:
    def test_clean_verdict(self):
        hist = {"round": [1, 2], "mean_accuracy": [0.5, 0.6]}
        assert interference_problems(0, [("s", hist, dict(hist))]) == []

    def test_compiles_during_scrape_detected(self):
        assert any(
            "compilation" in p for p in interference_problems(2, [])
        )

    def test_history_divergence_detected(self):
        a = {"round": [1], "mean_accuracy": [0.5]}
        b = {"round": [1], "mean_accuracy": [0.5000001]}
        problems = interference_problems(0, [("s", a, b)])
        assert any("diverges" in p for p in problems)


class TestSpansMUR1702:
    def test_drained_tenant_spans_validate(self, drained):
        daemon, ids = drained
        for sub_id in ids:
            run_dir = _run_dir(daemon, sub_id)
            spans = build_spans(run_dir)
            phase_total = sum(
                float(e.get("wall_s", 0.0))
                for e in events_of_type(run_dir, "phase_times")
            )
            assert validate_spans(spans, phase_total=phase_total) == []
            names = {s["name"] for s in spans}
            assert {"run", "queued", "generation"} <= names
            rounds = [s for s in spans if s["tid"] == LANE_ROUNDS]
            assert len(rounds) == 2
            # The accounted timeline reconciles exactly, not just within
            # tolerance.
            assert sum(s["end"] - s["start"] for s in rounds) == pytest.approx(
                phase_total
            )

    def test_unclosed_span_detected(self):
        bad = [{"name": "x", "trace_id": "t", "tid": LANE_ROUNDS,
                "start": 2.0, "end": 1.0, "parent": None, "id": "t/x",
                "args": {}}]
        assert any("not closed" in p for p in validate_spans(bad))

    def test_orphan_parent_detected(self):
        bad = [{"name": "x", "trace_id": "t", "tid": LANE_ROUNDS,
                "start": 0.0, "end": 1.0, "parent": "nope", "args": {}}]
        assert any("unknown id" in p for p in validate_spans(bad))

    def test_lane_overlap_detected(self):
        root = {"name": "run", "trace_id": "t", "tid": LANE_LIFECYCLE,
                "start": 0.0, "end": 9.0, "parent": None, "id": "t/run",
                "args": {}}
        a = {"name": "round 0", "trace_id": "t", "tid": LANE_ROUNDS,
             "start": 0.0, "end": 2.0, "parent": "t/run", "args": {}}
        b = {"name": "round 1", "trace_id": "t", "tid": LANE_ROUNDS,
             "start": 1.0, "end": 3.0, "parent": "t/run", "args": {}}
        assert any("starts" in p for p in validate_spans([root, a, b]))

    def test_phase_total_mismatch_detected(self, drained):
        daemon, ids = drained
        spans = build_spans(_run_dir(daemon, ids[0]))
        problems = validate_spans(spans, phase_total=1e6)
        assert any("inventing or losing" in p for p in problems)

    def test_chrome_trace_export(self, drained, tmp_path):
        daemon, ids = drained
        dirs = [_run_dir(daemon, s) for s in ids]
        n = write_chrome_trace(tmp_path / "trace.json", dirs)
        blob = json.loads((tmp_path / "trace.json").read_text())
        xs = [e for e in blob["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == n > 0
        # One pid per run, named by trace id via metadata events.
        meta = {e["args"]["name"] for e in blob["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert len({e["pid"] for e in xs}) == 2
        assert meta == {json.loads(
            (d / "manifest.json").read_text())["run_id"] for d in dirs}
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)


class TestSchemaDisciplineMUR1703:
    def test_current_schema_has_migration_note(self):
        from pathlib import Path
        docs = Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md"
        assert MANIFEST_SCHEMA_VERSION >= 2
        assert schema_discipline_problems(
            MANIFEST_SCHEMA_VERSION, docs.read_text()
        ) == []

    def test_unbumped_version_detected(self):
        problems = schema_discipline_problems(1, "### v1\n")
        assert any("schema bump" in p for p in problems)

    def test_missing_note_detected(self):
        problems = schema_discipline_problems(2, "### v1\n")
        assert any("migration" in p for p in problems)

    def test_v1_stream_still_renders(self, tmp_path):
        from murmura_tpu.telemetry.report import build_report

        run = _v1_run(tmp_path / "v1run")
        rep = build_report(run)
        assert rep["accuracy"]["rounds_recorded"] == 2
        spans = build_spans(run)
        assert validate_spans(spans, phase_total=1.0) == []
        reg = MetricsRegistry()
        fold_run_events(reg, run)
        assert reg.value("murmura_rounds") == 2


class TestServeLifecycleEvents:
    def test_tenant_stream_carries_lifecycle(self, drained):
        daemon, ids = drained
        for sub_id in ids:
            events = events_of_type(_run_dir(daemon, sub_id), "serve")
            order = [e["event"] for e in events]
            assert order == ["submitted", "admitted", "generation_start",
                             "generation_done"]
            # submitted is backdated to the ledger's queue time.
            by_name = {e["event"]: e for e in events}
            assert by_name["submitted"]["t"] <= by_name["admitted"]["t"]
            assert by_name["submitted"]["t"] == pytest.approx(
                daemon._ledger[sub_id]["submitted_at"]
            )
            assert by_name["generation_done"]["outcome"] == "done"

    def test_every_event_line_stamped(self, drained):
        daemon, ids = drained
        from murmura_tpu.telemetry.writer import iter_events

        events = list(iter_events(_run_dir(daemon, ids[0])))
        assert events and all(
            isinstance(e.get("t"), float) for e in events
        )

    def test_generation_compiles_folded_into_manifest(self, drained):
        daemon, ids = drained
        manifest = read_manifest(_run_dir(daemon, ids[0]))
        assert manifest["finalized"]
        # The first generation compiled the bucket; the probe's delta
        # lands as a manifest counter the offline fold can scrape.
        assert manifest["counters"].get("serve_compiles", 0) >= 1


class TestDaemonReadOps:
    def test_ping_enriched(self, drained):
        daemon, _ = drained
        resp = daemon.handle_request({"op": "ping"})
        assert resp["ok"]
        assert resp["uptime_s"] > 0
        from murmura_tpu import __version__

        assert resp["version"] == __version__
        assert resp["schema_version"] == MANIFEST_SCHEMA_VERSION
        assert resp["counters"]["admissions"] == 2
        assert resp["counters"]["generations"] == 1
        assert resp["counters"]["compiles"] >= 1
        (bucket,) = resp["buckets"].values()
        assert bucket["batch"] == 2 and bucket["running"] == 0

    def test_list_enriched(self, drained):
        daemon, ids = drained
        resp = daemon.handle_request({"op": "list"})
        assert resp["counters"]["admissions"] == 2
        assert resp["uptime_s"] > 0
        rows = {r["id"]: r for r in resp["submissions"]}
        for sub_id in ids:
            assert rows[sub_id]["gen"] == 1
            assert rows[sub_id]["rounds"] == 2
            assert rows[sub_id]["lane"] in (0, 1)

    def test_metrics_op_renders_openmetrics(self, drained):
        daemon, ids = drained
        resp = daemon.handle_request({"op": "metrics"})
        assert resp["ok"]
        assert resp["content_type"].startswith("application/openmetrics-text")
        parsed = parse_openmetrics(resp["text"])
        assert parsed[("murmura_serve_lifetime_total",
                       (("counter", "admissions"),))] == 2
        assert parsed[("murmura_serve_submissions",
                       (("state", "done"),))] == 2
        for sub_id in ids:
            assert parsed[("murmura_rounds_total",
                           (("tenant", sub_id),))] == 2


class TestRetryStats:
    def test_accumulates_and_keys_for_counters(self):
        stats = RetryStats()
        stats.hook(TimeoutError("deadline"), 1, 0.25)
        stats.hook(ConnectionResetError("peer"), 2, 0.5)
        assert stats.retries == 2
        assert stats.backoff_s == pytest.approx(0.75)
        assert "ConnectionResetError" in stats.last_reason
        assert stats.counters() == {
            "dispatch_retries": 2, "dispatch_backoff_s": 0.75,
        }

    def test_rides_run_with_retry(self):
        stats = RetryStats()
        calls = []

        def attempt(i):
            calls.append(i)
            if i < 2:
                raise TimeoutError("transient")
            return "ok"

        out = run_with_retry(
            attempt,
            policy=RetryPolicy(max_retries=3, base_delay_s=0.0,
                               max_delay_s=0.0, jitter=0.0, seed=0),
            on_retry=stats.hook, sleep=lambda _s: None,
        )
        assert out == "ok" and calls == [0, 1, 2]
        assert stats.retries == 2


class TestCrossRunRegistry:
    def test_indexes_runs_and_ledger(self, drained):
        daemon, ids = drained
        rows = index_runs([daemon.state_dir])
        by_kind = {}
        for r in rows:
            by_kind.setdefault(r["kind"], []).append(r)
        assert len(by_kind["run"]) == 2
        assert len(by_kind["submission"]) == 2
        for r in by_kind["run"]:
            assert r["status"] == "finalized"
            assert r["rounds"] == 2
            assert r["schema_version"] == MANIFEST_SCHEMA_VERSION
            assert not r["torn_tail"]
        for r in by_kind["submission"]:
            assert r["status"] == "done"
            assert r["fingerprint"]
            assert r["best_accuracy"] is not None

    def test_torn_tail_flagged_not_hidden(self, drained, tmp_path):
        daemon, ids = drained
        copy = tmp_path / "torn"
        shutil.copytree(_run_dir(daemon, ids[0]), copy)
        with open(copy / "events.jsonl", "a") as fh:
            fh.write('{"type": "round", "seq"')  # a crash mid-append
        (row,) = [r for r in index_runs([tmp_path]) if r["kind"] == "run"]
        assert row["torn_tail"]
        assert row["rounds"] == 2  # the valid prefix still counts
        assert "TORN" in render_rows([row])

    def test_find_latest_skips_ledger_rows(self, drained):
        daemon, ids = drained
        row = find_latest([daemon.state_dir])
        assert row is not None and row["kind"] == "run"
        assert row["run_id"] in ids


class TestTopRenderer:
    def _snapshot(self, daemon):
        return {
            "t": time.time(),
            "ping": daemon.handle_request({"op": "ping"}),
            "list": daemon.handle_request({"op": "list"}),
            "metrics": parse_openmetrics(
                daemon.handle_request({"op": "metrics"})["text"]
            ),
        }

    def test_render_snapshot(self, drained):
        daemon, ids = drained
        frame = top_mod.render_snapshot(self._snapshot(daemon))
        assert frame.startswith("murmura top")
        assert "admissions 2" in frame
        for sub_id in ids:
            assert sub_id in frame
        # Per-tenant rounds come from the metrics leg, not the ledger.
        row = next(line for line in frame.splitlines() if ids[0] in line)
        assert " 2 " in f" {row} "

    def test_run_top_bounded_iterations(self, drained, monkeypatch):
        daemon, _ = drained
        snap = self._snapshot(daemon)
        monkeypatch.setattr(top_mod, "gather", lambda _p: snap)
        frames = []
        top_mod.run_top("unused.sock", interval_s=0.0, iterations=2,
                        echo=frames.append, clear=False)
        assert len(frames) == 2
        assert all(f.startswith("murmura top") for f in frames)


class TestCLI:
    def test_metrics_on_run_dir(self, drained):
        daemon, ids = drained
        result = CliRunner().invoke(
            app, ["metrics", str(_run_dir(daemon, ids[0]))],
        )
        assert result.exit_code == 0, result.output
        parsed = parse_openmetrics(result.output)
        assert parsed[("murmura_rounds_total", ())] == 2
        assert "# EOF" in result.output

    def test_runs_json(self, drained):
        daemon, _ = drained
        result = CliRunner().invoke(
            app, ["runs", str(daemon.state_dir), "--json"],
        )
        assert result.exit_code == 0, result.output
        rows = [json.loads(line) for line in result.output.splitlines()]
        assert {r["kind"] for r in rows} == {"run", "submission"}

    def test_report_latest_and_trace(self, drained, tmp_path, monkeypatch):
        daemon, _ = drained
        monkeypatch.chdir(daemon.state_dir)
        out = tmp_path / "trace.json"
        result = CliRunner().invoke(
            app, ["report", "--latest", "--trace", str(out)],
        )
        assert result.exit_code == 0, result.output
        blob = json.loads(out.read_text())
        assert any(e["ph"] == "X" for e in blob["traceEvents"])

    def test_report_frontier_json_round_trip(self):
        # Satellite: the committed frontier artifact renders to JSON and
        # back — the machine-readable path tested against real data.
        from pathlib import Path

        frontier = Path(__file__).resolve().parents[1] / "frontier.json"
        result = CliRunner().invoke(
            app, ["report", "--frontier", str(frontier), "--json"],
        )
        assert result.exit_code == 0, result.output
        blob = json.loads(result.output)
        assert blob["grid"] and blob["summary"]
        committed = json.loads(frontier.read_text())
        assert blob["grid"] == committed["grid"]

    def test_report_grid_json_round_trip(self, tmp_path):
        from murmura_tpu.serve import scheduler as sched

        config = Config.model_validate({
            **_tenant(7),
            "grid": {"rules": ["fedavg"], "attacks": ["gaussian"],
                     "topologies": ["dense"], "strengths": [0.0, 1.0],
                     "seeds": [7]},
        })
        art = sched.run_grid(config)
        path = sched.write_grid(art, tmp_path / "grid.json")
        result = CliRunner().invoke(
            app, ["report", "--grid", str(path), "--json"],
        )
        assert result.exit_code == 0, result.output
        blob = json.loads(result.output)
        assert blob["total_cells"] == art["total_cells"] == 2
        assert blob["total_compiles"] == art["total_compiles"] == 1
        assert blob["buckets"] == art["buckets"]


@pytest.mark.slow
def test_check_observe_family_clean():
    """The full MUR1700-1703 package gate (in-daemon parity, the scraped
    vs unscraped interference soak, span reconciliation, schema
    discipline) must pass on the live tree."""
    from murmura_tpu.analysis.observe import check_observe

    findings = check_observe(force=True)
    assert findings == [], "\n".join(
        f"{f.rule} {f.path}:{f.line} {f.message}" for f in findings
    )
