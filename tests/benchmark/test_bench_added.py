"""A dry run of the next ``model_config`` PR: a token model arrives as new
files only (``bench_added.py``) and runs through ``Cell`` and ``run_cell``
with the copy first on the import path, as ``benchmark/run.py`` has it."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench_added import lay_out
from bench_tiny import BENCH, REPO

DRIVE = """
import json, sys
from benchmark import cells, harness
assert cells.ROOT.samefile(sys.argv[1]), cells.ROOT
cell = cells.Cell("lstm_tokens_er_n8")
result = harness.run_cell(cell, seed=2**31 + 29, seconds=0.5, trace=False)
print(json.dumps({"result": result, "layer": [m["name"] for m in cell.metrics("per_layer")],
                  "reader": cell.layer_metric("recurrence_ms")["reader"]}))
"""


def _digests(directory):
    return {str(p.relative_to(directory)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(directory.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def dry_run(tmp_path_factory):
    root = lay_out(tmp_path_factory.mktemp("added"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), str(REPO)])}
    out = subprocess.run([sys.executable, "-c", DRIVE, str(root)], cwd=root, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return root, json.loads(out.stdout.splitlines()[-1]), out.stdout


def test_no_file_of_the_copy_was_edited(dry_run):
    root, _, _ = dry_run
    mine, copy = _digests(BENCH), _digests(root / "benchmark")
    assert {k: copy[k] for k in mine} == mine
    assert sorted(set(copy) - set(mine)) == [
        "configs/char_lstm.py", "configs/tiny_char_lstm.json",
        "layer_metrics/recurrence_ms.json", "reference/char_lstm.py",
        "workloads/tiny_tokens_er.json"]
    old = json.loads((REPO / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key, value in old.items():
        assert new[key] == value or new[key][:len(value)] == value, key


def test_the_token_cell_runs_and_follows_its_reference(dry_run):
    _, out, printed = dry_run
    r = out["result"]
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"round_ms", "round_ms_p95", "setup_s"}
    assert r["checks"]["inputs_off"]["value"] == 0.0
    assert 0 < r["checks"]["first_update"]["value"] < 0.1
    # 7 honest nodes (one of 8 attacks) x 4 batches of 8, 12 positions each.
    assert "(224 samples, 2688 tokens a round)" in printed
    assert out["reader"] == "leaf_scope_ms" and "recurrence_ms" in out["layer"]
