"""CLI surface via click's test runner (reference: murmura/cli.py:34-308)."""

import json
from pathlib import Path

import yaml
from click.testing import CliRunner

from murmura_tpu.cli import app


def _write_cfg(tmp_path, **overrides):
    cfg = {
        "experiment": {"name": "cli-test", "seed": 3, "rounds": 2},
        "topology": {"type": "ring", "num_nodes": 4},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.1},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 200, "input_dim": 8,
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 8, "hidden_dims": [16],
                             "num_classes": 3}},
        "backend": "simulation",
    }
    cfg.update(overrides)
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml.safe_dump(cfg))
    return p


def test_run_writes_history_json(tmp_path):
    cfg = _write_cfg(tmp_path)
    out = tmp_path / "hist.json"
    result = CliRunner().invoke(app, ["run", str(cfg), "-o", str(out)])
    assert result.exit_code == 0, result.output
    hist = json.loads(out.read_text())
    # Reference history schema (murmura/core/network.py:47-58).
    for key in ("round", "mean_accuracy", "std_accuracy", "mean_loss"):
        assert key in hist
    assert hist["round"] == [1, 2]


def test_run_fused_dispatch_from_config(tmp_path):
    cfg = _write_cfg(tmp_path, tpu={"rounds_per_dispatch": 2})
    out = tmp_path / "hist.json"
    result = CliRunner().invoke(app, ["run", str(cfg), "-o", str(out)])
    assert result.exit_code == 0, result.output
    hist = json.loads(out.read_text())
    assert hist["round"] == [1, 2]


def test_run_renders_wiring_error_cleanly(tmp_path):
    # data 8-dim vs model 16-dim: ConfigError message, no traceback.
    cfg = _write_cfg(
        tmp_path,
        model={"factory": "mlp",
                "params": {"input_dim": 16, "hidden_dims": [16],
                           "num_classes": 3}},
    )
    result = CliRunner().invoke(app, ["run", str(cfg)])
    assert result.exit_code == 1
    assert "data/model mismatch" in result.output
    assert "Traceback" not in result.output


def test_run_renders_parse_error_cleanly(tmp_path):
    p = tmp_path / "broken.yaml"
    p.write_text("experiment: {name: x\n  nope")
    result = CliRunner().invoke(app, ["run", str(p)])
    assert result.exit_code == 1
    assert "Cannot parse config" in result.output
    assert "Traceback" not in result.output


def test_run_resume_requires_checkpoint_dir(tmp_path):
    cfg = _write_cfg(tmp_path)
    result = CliRunner().invoke(app, ["run", str(cfg), "--resume"])
    assert result.exit_code != 0
    assert "--checkpoint-dir" in result.output


def test_list_components():
    result = CliRunner().invoke(app, ["list-components"])
    assert result.exit_code == 0
    for frag in ("fedavg", "krum", "evidential_trust", "gaussian",
                 "simulation", "ring"):
        assert frag in result.output


def test_check_flags_seeded_violation(tmp_path):
    """`murmura check <file>`: non-zero exit + greppable finding lines on a
    file seeding a traced-branch and a host-sync violation."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return float(x)\n"
        "    return x\n"
    )
    result = CliRunner().invoke(app, ["check", str(bad), "--no-contracts"])
    assert result.exit_code == 1
    assert "MUR001" in result.output
    assert "MUR003" in result.output
    assert f"{bad}:5:" in result.output  # path:line: greppable format


def test_check_clean_file_exits_zero(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * 2\n"
    )
    result = CliRunner().invoke(app, ["check", str(good), "--no-contracts"])
    assert result.exit_code == 0
    assert "clean" in result.output


def test_check_package_is_clean():
    """The committed package must pass its own analyzer (with contracts)."""
    import murmura_tpu

    pkg = str(Path(murmura_tpu.__file__).resolve().parent)
    result = CliRunner().invoke(app, ["check", pkg])
    assert result.exit_code == 0, result.output


def test_run_with_telemetry_then_report_smoke(tmp_path):
    """Tier-1 `murmura report` smoke (ISSUE 4 satellite): a telemetry run
    renders end-to-end, and --json exposes the same report dict."""
    run_dir = tmp_path / "run"
    cfg = _write_cfg(
        tmp_path,
        aggregation={"algorithm": "krum", "params": {"num_compromised": 1}},
        telemetry={"enabled": True, "dir": str(run_dir), "audit_taps": True},
    )
    result = CliRunner().invoke(app, ["run", str(cfg)])
    assert result.exit_code == 0, result.output
    assert "Telemetry run written" in result.output

    rendered = CliRunner().invoke(app, ["report", str(run_dir)])
    assert rendered.exit_code == 0, rendered.output
    assert "murmura report" in rendered.output
    assert "Accuracy" in rendered.output

    as_json = CliRunner().invoke(app, ["report", str(run_dir), "--json"])
    assert as_json.exit_code == 0, as_json.output
    rep = json.loads(as_json.output)
    assert rep["accuracy"]["rounds_recorded"] == 2
    assert len(rep["taps"]["selected_by"]) == 4
    assert rep["time"]["by_mode"]["per_round"]["rounds"] == 2


def test_report_rejects_non_run_dir(tmp_path):
    result = CliRunner().invoke(app, ["report", str(tmp_path)])
    assert result.exit_code == 1
    assert "manifest" in result.output


def test_run_profile_flag_rejected_on_distributed(tmp_path):
    cfg = _write_cfg(tmp_path, backend="distributed")
    result = CliRunner().invoke(app, ["run", str(cfg), "--profile"])
    assert result.exit_code != 0
    assert "--profile" in result.output


def test_frontier_cli_writes_artifact_and_report_renders(tmp_path):
    # The `murmura frontier` -> `murmura report --frontier` round trip on
    # a single tiny cell (docs/ROBUSTNESS.md "The robustness frontier").
    cfg = _write_cfg(
        tmp_path,
        aggregation={"algorithm": "krum", "params": {"num_compromised": 1}},
        attack={"enabled": True, "type": "gaussian", "percentage": 0.3,
                "params": {"noise_std": 5.0}},
        frontier={"rules": ["krum"], "attacks": ["gaussian"],
                  "topologies": ["dense"], "points": 2, "stages": 1,
                  "rounds": 2, "strength_lo": 0.5, "strength_hi": 4.0},
    )
    out = tmp_path / "frontier.json"
    result = CliRunner().invoke(app, ["frontier", str(cfg), "-o", str(out)])
    assert result.exit_code == 0, result.output
    artifact = json.loads(out.read_text())
    (cell,) = artifact["cells"]
    assert cell["rule"] == "krum" and cell["compiles"] <= 2
    rendered = CliRunner().invoke(app, ["report", "--frontier", str(out)])
    assert rendered.exit_code == 0, rendered.output
    assert "krum" in rendered.output
    as_json = CliRunner().invoke(
        app, ["report", "--frontier", str(out), "--json"]
    )
    assert as_json.exit_code == 0
    assert json.loads(as_json.output)["summary"][0]["rule"] == "krum"


def test_report_without_run_dir_or_frontier_errors():
    result = CliRunner().invoke(app, ["report"])
    assert result.exit_code == 1
    assert "RUN_DIR" in result.output


def test_frontier_cli_renders_unknown_rule_cleanly(tmp_path):
    cfg = _write_cfg(
        tmp_path, frontier={"rules": ["krum", "nope"]},
    )
    result = CliRunner().invoke(app, ["frontier", str(cfg)])
    assert result.exit_code == 1
    assert "Config error" in result.output and "nope" in result.output
