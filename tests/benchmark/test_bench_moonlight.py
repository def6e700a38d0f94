"""The decoder's configuration through the harness on the CPU at a tiny
size: the committed reference, loss, generator, rule and roofline files,
found by name from a root whose data files are a cut-down copy of
``moonlight_16b_a3b_ep8.json`` and ``fedavg_full_n3_s4096.json`` (hidden 64,
8 experts with 4 held, 1 dense + 2 expert layers, 16 positions).  The cell
runs `correct`; the control (fp8 operands) and two faults of the model's own
(the held experts' weights renormalised over the held ones alone; the bias
step left out) each fail a limit.  The limits are this size's own.

What PR 30's dry run of this PR (``test_bench_added.py``, no longer
collected: ``tests/conftest.py``) held besides, here on the tiny root: the
configuration stated wrongly fails ``check_configuration``; an added
``kernel_roofline`` module is read through an added per-layer entry; the
root's copies of the benchmark's files are the files.
"""

import dataclasses
import json
import traceback
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_files as files
from benchmark import harness
from benchmark import inputs as cell_inputs
from benchmark.cells import Cell, load_peaks
from benchmark.configs import moonlight_16b_a3b_ep8
from benchmark.readers import kernel_roofline
from benchmark.reference import deepseek_v3, rule_fedavg
from benchmark.reference import round as ref_round
from benchmark.roofline import grouped_product

from bench_tiny import BENCH, REPO

SIZES = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_experts_per_tok=2, num_attention_heads=2, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, seq_len=16,
)
LIMITS = {"loss": 2e-5, "eval_loss": 1e-5, "first_update": 0.02,
          "first_update_largest": 0.01, "change": 0.02}


def make_root(tmp):
    root = tmp
    for part in ("configs", "workloads", "layer_metrics"):
        (root / "benchmark" / part).mkdir(parents=True)
    doc = json.loads((BENCH / "configs/moonlight_16b_a3b_ep8.json").read_text())
    doc.update(SIZES, name="tiny_moonlight", num_layers=3, n_routed_experts=4,
               published={"num_layers": 27, "n_routed_experts": 8, "vocab_size": 163840},
               compute_dtype="float32", param_dtype="float32")
    doc["num_parameters"] = moonlight_16b_a3b_ep8.parameter_count(doc)
    doc["model"]["params"].update(
        SIZES, num_hidden_layers=3, n_routed_experts=8, ep_size=2, ep_rank=0)
    doc["data"].update(seq_len=16, vocab_size=96, samples_per_node=6, held_out_per_node=2)
    doc["data"]["params"].update(seq_len=16, vocab_size=96)
    (root / "benchmark/configs/tiny_moonlight.json").write_text(json.dumps(doc))
    job = json.loads((BENCH / "workloads/fedavg_full_n3_s4096.json").read_text())
    job["training"].update(batch_size=2, lr=0.05)
    job["correct"].update(rounds=2, node_block=2, limits=LIMITS)
    job["dispatch"]["chunk"] = 2
    (root / "benchmark/workloads/tiny_fedavg.json").write_text(json.dumps(job))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny_moonlight", "source": doc["source"],
                         "file": "benchmark/configs/tiny_moonlight.json",
                         "reduced": doc["reduced"], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny_moonlight_fedavg", "config": "tiny_moonlight",
                           "traffic": "tiny_fedavg", "chips": 1, "why": "a test's cell"}]
    for metric in bench["per_layer"]:
        (root / "benchmark/layer_metrics" / f"{metric['name']}.json").write_text(
            (BENCH / "layer_metrics" / f"{metric['name']}.json").read_text())
        metric.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("moonlight"))


@pytest.fixture(scope="module")
def timed_run(root):
    return harness.run_cell(Cell("tiny_moonlight_fedavg", root=root), seed=2**31 + 41,
                            seconds=0.5, trace=False)


def test_the_committed_files_state_the_issues_cell():
    doc = json.loads((BENCH / "configs/moonlight_16b_a3b_ep8.json").read_text())
    job = json.loads((BENCH / "workloads/fedavg_full_n3_s4096.json").read_text())
    assert doc["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert doc["published"] == {"num_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    assert (doc["num_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (5, 8, 20480)
    assert doc["param_dtype"] == doc["compute_dtype"] == "bfloat16"
    assert doc["num_parameters"] == 568_484_608
    assert (job["topology"], job["aggregation"]["algorithm"]) == (
        {"type": "fully", "num_nodes": 3}, "fedavg")
    assert "attack" not in job and job["training"]["batch_size"] == 1
    assert job["correct"]["rounds"] * job["correct"]["node_block"] >= 1


# Every number of the published config.json (the catalog's ``config``), which
# the file holds under the same key unless ``reduced`` names it.
PUBLISHED = {
    "ep_size": 1, "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 11264, "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_number_is_held_or_stated_as_cut(key):
    doc = json.loads((BENCH / "configs/moonlight_16b_a3b_ep8.json").read_text())
    if key in doc["reduced"]:
        assert doc["published"][key] == PUBLISHED[key] != doc[key]
    else:
        assert doc[key] == PUBLISHED[key]
    params = doc["model"]["params"]
    if key in params and key not in ("ep_size", "num_hidden_layers", "vocab_size"):
        assert params[key] == PUBLISHED[key]  # every width the program is given


def test_the_tiny_cell_runs_and_follows_its_reference(timed_run):
    r = timed_run
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"round_ms", "round_ms_p95", "setup_s"}
    assert r["checks"]["inputs_off"]["value"] == 0.0
    assert r["checks"]["window_compiles"]["value"] == 0.0
    assert 0 < r["checks"]["first_update"]["value"] < LIMITS["first_update"]


@pytest.fixture(scope="module")
def followed(root):
    """The cell's inputs, the reference's own run, and a stand-in's numbers
    against it (the reference put in the program's place, as ``study.py``
    does on the chip)."""
    cell = Cell("tiny_moonlight_fedavg", root=root)
    spans = harness.Spans()
    network, inputs, captured, _ = harness.first_calls(cell, 77, spans)
    job = harness.reference_job(cell, inputs)
    harness.free(network)
    cell_inputs.draw_again(inputs, cell)
    reference = ref_round.run(inputs, job, rounds=2)
    program = harness.compare(harness.program_numbers(captured, inputs), reference,
                              inputs, job)

    def stand_in(other):
        run = ref_round.run(inputs, other, rounds=2, keep_first=True)
        del run["trained_first"]
        return harness.compare(run, reference, inputs, job)

    return job, program, stand_in


def _failed(numbers):
    return sorted(k for k, limit in LIMITS.items() if not numbers[k] <= limit)


def test_the_program_is_inside_every_limit(followed):
    _, program, _ = followed
    assert _failed(program) == [], program


def test_fp8_operands_fail_a_limit(followed):
    job, _, stand_in = followed
    numbers = stand_in(dataclasses.replace(job, compute_dtype="float8_e4m3fn"))
    assert _failed(numbers), numbers


def test_weights_renormalised_over_the_held_experts_fail_a_limit(followed, monkeypatch):
    """The fault a share invites: the chosen experts' weights normalised
    over those held here, as if the absent ones had not been chosen."""
    job, _, stand_in = followed
    moe = deepseek_v3._moe

    def renormalised(p, x, doc, dtype):
        y, counts, balance = moe(p, x, doc, dtype)
        sc = jax.nn.sigmoid(jnp.dot(x, p["router"]["w"],
                                    precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(sc + p["router"]["bias"], int(doc["num_experts_per_tok"]))
        picked = jnp.take_along_axis(sc, chosen, axis=-1)
        held = chosen < int(doc["n_routed_experts"])
        over_all = picked.sum(-1, keepdims=True) + 1e-20
        over_held = (picked * held).sum(-1, keepdims=True) + 1e-20
        shared = deepseek_v3._swiglu(p["shared"], x, dtype)
        return shared + (y - shared) * (over_all / over_held), counts, balance

    monkeypatch.setattr(deepseek_v3, "_moe", renormalised)
    numbers = stand_in(job)
    assert _failed(numbers), numbers


def test_the_bias_step_left_out_fails_a_limit(followed, monkeypatch):
    job, _, stand_in = followed
    monkeypatch.setattr(deepseek_v3, "after_step", lambda params, counts, doc: params)
    numbers = stand_in(job)
    assert "first_update" in _failed(numbers) or "change" in _failed(numbers), numbers


def test_the_rule_in_blocks_is_the_rule(monkeypatch):
    rng = np.random.default_rng(0)
    own = jnp.asarray(rng.normal(size=(3, 50)).astype(np.float32)).astype(jnp.bfloat16)
    adj = np.ones((3, 3), np.float32) - np.eye(3, dtype=np.float32)
    whole, _, stats = rule_fedavg.aggregate(own, own, adj, 0.0, {}, {}, 2, {})
    monkeypatch.setattr(rule_fedavg, "BLOCK", 16)
    blocks, _, _ = rule_fedavg.aggregate(own, own, adj, 0.0, {}, {}, 2, {})
    assert whole.dtype == jnp.bfloat16 and whole.shape == (3, 50)
    np.testing.assert_array_equal(np.asarray(whole, np.float32), np.asarray(blocks, np.float32))
    mean = np.asarray(own, np.float32).mean(axis=0, keepdims=True)
    np.testing.assert_allclose(np.asarray(whole, np.float32), np.repeat(mean, 3, 0),
                               atol=2.0 ** -8 * np.abs(mean).max())
    assert np.asarray(stats["num_neighbors"]).tolist() == [2.0, 2.0, 2.0]
    weights = rule_fedavg.recover(None, adj > 0, {})
    np.testing.assert_allclose(weights, np.full((3, 3), 1 / 3), rtol=1e-6)


@pytest.mark.parametrize("name,error", [
    ("as_stated", None), ("published_missing", KeyError),
    ("reduced_differs", AssertionError)])
def test_the_configuration_stated_wrongly_fails_its_check(tmp_path, name, error):
    """``published`` missing, or ``reduced`` not what ``BENCHMARK.json``
    says: refused by the check of a configuration."""
    root = make_root(tmp_path)
    path = root / "benchmark/configs/tiny_moonlight.json"
    doc = json.loads(path.read_text())
    if name == "published_missing":
        del doc["published"]
    elif name == "reduced_differs":
        doc["reduced"] = doc["reduced"] + ["seq_len"]
        doc["published"]["seq_len"] = 8192
    path.write_text(json.dumps(doc))
    if error is None:
        files.check_configuration(root, "tiny_moonlight")
        files.check_cell(Cell("tiny_moonlight_fedavg", root=root))
        return
    with pytest.raises(error) as caught:
        files.check_configuration(root, "tiny_moonlight")
    assert traceback.extract_tb(caught.value.__traceback__)[-1].name == "check_configuration"


def test_the_roots_copies_are_the_benchmarks_files(root):
    """The tiny root adds a configuration and a job and edits nothing: its
    per-layer files are the committed ones, and its ``BENCHMARK.json`` the
    committed one but for its configurations, its cells and the lists of
    cells a metric is read in."""
    for path in sorted((root / "benchmark/layer_metrics").iterdir()):
        assert path.read_bytes() == (BENCH / "layer_metrics" / path.name).read_bytes()
    assert sorted(p.name for p in (root / "benchmark").rglob("*.json")
                  if p.parent.name != "layer_metrics") == [
        "tiny_fedavg.json", "tiny_moonlight.json"]
    old = json.loads((REPO / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert set(new) == set(old)
    for key in set(old) - {"configs", "workloads", "per_layer"}:
        assert new[key] == old[key], key
    strip = lambda m: {k: v for k, v in m.items() if k != "workloads"}
    assert new["per_layer"] == [strip(m) for m in old["per_layer"]]


def test_an_added_kernel_roofline_is_read_through_its_entry(root):
    """``grouped_product_roofline`` arrives as an entry, a
    ``layer_metrics`` file and ``roofline/grouped_product.py``: the cell
    finds the reader and the module by the names the file gives, and the
    reader divides the kernel's own least time by the innermost operations'
    that carry the kernel's name (the loop's one outer event does not)."""
    cell = Cell("tiny_moonlight_fedavg", root=root)
    assert "grouped_product_roofline" in [m["name"] for m in cell.metrics("per_layer")]
    spec = cell.layer_metric("grouped_product_roofline")
    assert (spec["reader"], spec["args"]["module"]) == ("kernel_roofline", "grouped_product")
    files.check_layer_metric(root, "grouped_product_roofline")
    peaks = load_peaks("TPU v5 lite")
    context = {"cell": cell, "traced_rounds": 2, "peaks": peaks, "param_dtype": "float32",
               "trace": types.SimpleNamespace(
                   op_s={"while.3": 1.0},
                   leaf_op_s={"ragged-dot-none.5": 3e-3, "ragged-dot-metadata.1": 1e-3,
                              "fusion.2": 0.5})}
    least, _ = grouped_product.least_seconds(cell, peaks, "float32")
    assert kernel_roofline.read(context, **spec["args"]) == pytest.approx(
        100.0 * least / 2e-3)
    context["trace"] = types.SimpleNamespace(op_s={"while.3": 1.0}, leaf_op_s={"fusion.2": 0.5})
    assert kernel_roofline.read(context, **spec["args"]) is None


def test_the_grouped_products_own_work_against_hand_values():
    """The real cell: 4,096 x 6 x 8 / 64 = 3,072 rows a sample a layer, three
    products of 2 x 2048 x 1408 a row, four expert layers; a round is 3
    nodes x (2 trained samples x 3 passes + 1 evaluated) = 21 passes."""
    cell = Cell("moonlight_fedavg_full_n3")
    flops, bytes_ = grouped_product.work(cell.config, 2)
    assert flops == 4 * 2.0 * 3072 * 3 * 2048 * 1408
    assert bytes_ == 4 * 2 * (8 * 3 * 2048 * 1408 + 3072 * (2 * 2048 + 3 * 1408))
    peaks = load_peaks("TPU v5 lite")
    seconds, bound = grouped_product.least_seconds(cell, peaks, "bfloat16")
    assert bound == "flops" and seconds == pytest.approx(21 * flops / peaks["flops_bf16"])
