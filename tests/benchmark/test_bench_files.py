"""The benchmark's data files: every name in ``BENCHMARK.json`` resolves
to a file, every file to code, and the counting functions count right."""

import importlib
import json
import re

import jax
import pytest

from benchmark.cells import Cell, load_benchmark, load_peaks

from bench_tiny import BENCH, REPO, make_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCHMARK = load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]
LAYER_METRICS = [m["name"] for m in BENCHMARK["per_layer"]]
CONFIG_FILES = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


def test_benchmark_json_keeps_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert all((REPO / p).is_dir() for p in b["paths"])
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    used = {w["config"] for w in b["workloads"]}
    assert used == set(CONFIGS)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
    assert len(json.dumps(b)) < 64 * 1024


def _resolves(cell):
    from murmura_tpu.config import Config

    config = Config.model_validate(cell.program_config(seed=2**31 + 11))
    assert config.backend == "tpu" and config.tpu.rounds_per_dispatch == 1
    assert config.topology.num_nodes == cell.job["topology"]["num_nodes"]
    ref = "benchmark.reference."
    importlib.import_module(ref + cell.config["reference"])
    importlib.import_module(ref + "rule_" + cell.job["aggregation"]["algorithm"])
    importlib.import_module(ref + "attack_" + cell.job["attack"]["type"])
    rule = cell.module("roofline", cell.job["aggregation"]["algorithm"])
    seconds, bound = rule.least_seconds(cell, load_peaks("TPU v5 lite"), "bfloat16")
    assert seconds > 0 and bound in ("flops", "bytes")
    limits = set(cell.job["correct"]["limits"])
    assert {"first_update"} <= limits <= {
        "loss", "eval_loss", "first_update", "first_update_largest", "change"
    }
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert {"setup_s", "round_ms", "round_ms_p95"} <= reported


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    _resolves(Cell(name))


TRAFFIC_FILES = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_a_call_covers_one_period_of_what_recurs(name):
    """``round_ms_p95`` is a percentile of calls: every call has to be
    alike, so a chunk holds whole periods of the job's evaluation."""
    dispatch = json.loads((BENCH / "workloads" / f"{name}.json").read_text())["dispatch"]
    every, chunk = int(dispatch.get("eval_every", 1)), int(dispatch.get("chunk", 1))
    assert chunk >= 1 and every >= 1
    if every > 1:
        assert chunk % every == 0, (chunk, every)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_configuration_names_its_generator_and_its_loss(name):
    doc = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    generator = importlib.import_module(f"benchmark.data.{doc['data']['generator']}")
    assert callable(generator.make)
    loss = importlib.import_module(
        f"benchmark.reference.loss_{doc.get('loss', 'label')}")
    assert callable(loss.training) and callable(loss.evaluation)


def test_every_file_belongs_to_a_cell():
    """No workload, configuration, rule or roofline is kept that no cell of
    ``BENCHMARK.json`` reaches."""
    traffic = {w["traffic"] for w in BENCHMARK["workloads"]}
    assert {p.stem for p in (BENCH / "workloads").glob("*.json")} == traffic
    assert set(CONFIG_FILES) == set(CONFIGS)
    rules = {Cell(n).job["aggregation"]["algorithm"] for n in CELLS}
    assert {p.stem for p in (BENCH / "roofline").glob("*.py")} == rules | {
        "shapes", "__init__"}
    assert {p.stem[5:] for p in (BENCH / "reference").glob("rule_*.py")} == rules
    metrics = {p.stem for p in (BENCH / "layer_metrics").glob("*.json")}
    assert metrics == set(LAYER_METRICS)


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_layer_metric_resolves_by_name(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"]
    )
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)


def _forward_flops_from_shapes(shapes, side=None):
    """2 x multiply-adds from the program's own parameter shapes."""
    total = 0.0
    for path, shape in shapes:
        if len(shape) == 4:  # HWIO conv, SAME, then a 2x2 pool
            kh, kw, cin, cout = shape
            total += 2.0 * side * side * kh * kw * cin * cout
            side //= 2
        elif len(shape) == 2 and "embed" not in path:
            total += 2.0 * shape[0] * shape[1]
    return total


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_flops_function_against_the_models_shapes(name):
    from murmura_tpu.models.registry import build_model
    from murmura_tpu.ops.flatten import model_dimension

    doc = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert doc["name"] == name and doc["reduced"] == []
    for entry in BENCHMARK["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == [] and entry["source"] == doc["source"]
    counts = importlib.import_module(f"benchmark.configs.{doc['flops']}")
    model = build_model(doc["model"]["factory"], dict(doc["model"]["params"]))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model_dimension(tree) == doc["num_parameters"] == counts.parameter_count(doc)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    shapes = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in flat]
    want = _forward_flops_from_shapes(shapes, side=doc["image_size"])
    assert counts.forward_flops_per_sample(doc) == pytest.approx(want, rel=1e-12)
    assert counts.train_flops_per_sample(doc) == pytest.approx(3 * want, rel=1e-12)


def test_femnist_flops_are_the_issues_numbers():
    doc = json.loads((BENCH / "configs" / "femnist_cnn.json").read_text())
    from benchmark.configs import femnist_cnn

    # 1.25 + 20.07 + 12.85 + 0.25 MFLOP (ISSUE 25)
    assert femnist_cnn.forward_flops_per_sample(doc) == pytest.approx(34.42e6, rel=1e-3)


def test_rooflines_against_hand_values():
    from benchmark.roofline import shapes, sketchguard

    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    # 4 nodes of degree 2, 10 parameters of 2 bytes: 4*10*(4 + 2*2 + 3) =
    # 440 operations, 3*4*10*2 = 240 bytes -> 4.4 s against 24 s: bytes bind.
    assert sketchguard.work(4, 2.0, 10, 2) == (440.0, 240.0)
    assert shapes.least(440.0, 240.0, peaks) == (24.0, "bytes")
    assert shapes.least(4800.0, 240.0, peaks) == (48.0, "flops")
    cell = Cell("cnn_sketchguard_er_n64")
    assert shapes.shapes(cell, "bfloat16") == (64, 0.3 * 63, 6603710, 2)
    assert shapes.shapes(cell, "float32")[3] == 4
    least_s, bound = sketchguard.least_seconds(
        cell, load_peaks("TPU v5 lite"), "bfloat16"
    )
    assert bound == "bytes"
    assert least_s == pytest.approx(3 * 64 * 6603710 * 2 / 819e9)


@pytest.mark.parametrize("topology,degree", [
    ({"type": "k-regular", "num_nodes": 12, "k": 4}, 4.0),
    ({"type": "ring", "num_nodes": 12}, 2.0),
    ({"type": "fully", "num_nodes": 12}, 11.0),
])
def test_the_mean_degree_follows_the_graph(topology, degree):
    from types import SimpleNamespace

    from benchmark.roofline import shapes

    cell = SimpleNamespace(job={"topology": topology},
                           config={"num_parameters": 10})
    assert shapes.shapes(cell, "float32") == (12, degree, 10, 4)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks recorded"):
        load_peaks("cpu")


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new cell, configuration and per-layer metric of an existing reader
    kind: new files and new entries, no edit to a file that is there."""
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    job = json.loads((root / "benchmark/workloads/tiny_sketchguard.json").read_text())
    job["aggregation"] = {"algorithm": "sketchguard", "params": {"sketch_size": 64}}
    (root / "benchmark/workloads/added_mix.json").write_text(json.dumps(job))
    doc = json.loads((root / "benchmark/configs/tiny_cnn.json").read_text())
    doc.update(name="added_cnn", dense_units=[64])
    (root / "benchmark/configs/added_cnn.json").write_text(json.dumps(doc))
    (root / "benchmark/layer_metrics/added_scope_ms.json").write_text(json.dumps({
        "layer": "round program", "unit": "ms", "moves": "round_ms",
        "reader": "scope_device_ms", "args": {"scopes": ["murmura.stale"]},
    }))
    bench["configs"].append({"name": "added_cnn", "source": "x", "reduced": [],
                             "file": "benchmark/configs/added_cnn.json", "why": "t"})
    bench["workloads"].append({"name": "added", "config": "added_cnn",
                               "traffic": "added_mix", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "added_scope_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "round program", "moves": "round_ms",
        "workloads": ["added"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell("added", root=root)
    assert cell.config["dense_units"] == [64]
    assert cell.program_config(3)["aggregation"]["params"] == {"sketch_size": 64}
    assert "added_scope_ms" in [m["name"] for m in cell.metrics("per_layer")]
    assert "added_scope_ms" not in [
        m["name"] for m in Cell("tiny_sketchguard", root=root).metrics("per_layer")
    ]
    assert cell.layer_metric("added_scope_ms")["reader"] == "scope_device_ms"
