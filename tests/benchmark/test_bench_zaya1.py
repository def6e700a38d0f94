"""The ZAYA1 configuration through the harness on the CPU at a tiny size:
the committed reference, loss, generator, rule and roofline files, found
by name from a root whose data files are a cut-down copy of
``zaya1_8b_ep2.json`` and ``fedavg_full_n3_s4096_cca.json`` (hidden 64, 4
query and 2 key/value heads of 16, 8 experts with 4 held and top-1, a
router 16 wide, 2 layers, 16 positions).  The cell runs `correct`; the
control (fp8 operands) and three faults of the model's own (the value
shift left out; the convolutions' second tap left out; the depth average
left out) each fail a limit.  The limits are this size's own.
"""

import dataclasses
import json

import pytest

import test_bench_files as files
from benchmark import harness
from benchmark import inputs as cell_inputs
from benchmark.cells import Cell
from benchmark.configs import zaya1_8b_ep2
from benchmark.reference import round as ref_round
from benchmark.reference import zaya1

from bench_tiny import BENCH, REPO

SIZES = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, router_hidden_size=16, seq_len=16,
)
LIMITS = {"loss": 2e-5, "eval_loss": 1e-5, "first_update": 0.02,
          "first_update_largest": 0.01, "change": 0.02}


def make_root(tmp):
    root = tmp
    for part in ("configs", "workloads", "layer_metrics"):
        (root / "benchmark" / part).mkdir(parents=True)
    doc = json.loads((BENCH / "configs/zaya1_8b_ep2.json").read_text())
    doc.update(SIZES, name="tiny_zaya", num_layers=2, num_experts=4,
               published={"num_layers": 40, "num_experts": 8, "vocab_size": 262272},
               compute_dtype="float32", param_dtype="float32")
    doc["num_parameters"] = zaya1_8b_ep2.parameter_count(doc)
    doc["model"]["params"].update(SIZES, num_hidden_layers=2, num_experts=8)
    doc["data"].update(seq_len=16, vocab_size=96, samples_per_node=6, held_out_per_node=2)
    doc["data"]["params"].update(seq_len=16, vocab_size=96)
    (root / "benchmark/configs/tiny_zaya.json").write_text(json.dumps(doc))
    job = json.loads((BENCH / "workloads/fedavg_full_n3_s4096_cca.json").read_text())
    job["training"].update(batch_size=2, lr=0.05)
    job["correct"].update(rounds=2, node_block=2, limits=LIMITS)
    job["dispatch"]["chunk"] = 2
    (root / "benchmark/workloads/tiny_fedavg_cca.json").write_text(json.dumps(job))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny_zaya", "source": doc["source"],
                         "file": "benchmark/configs/tiny_zaya.json",
                         "reduced": doc["reduced"], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny_zaya_fedavg", "config": "tiny_zaya",
                           "traffic": "tiny_fedavg_cca", "chips": 1, "why": "a test's cell"}]
    for metric in bench["per_layer"]:
        (root / "benchmark/layer_metrics" / f"{metric['name']}.json").write_text(
            (BENCH / "layer_metrics" / f"{metric['name']}.json").read_text())
        metric.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("zaya"))


def test_the_committed_files_state_the_cell():
    doc = json.loads((BENCH / "configs/zaya1_8b_ep2.json").read_text())
    job = json.loads((BENCH / "workloads/fedavg_full_n3_s4096_cca.json").read_text())
    assert doc["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert doc["published"] == {"num_layers": 40, "num_experts": 16, "vocab_size": 262272}
    assert (doc["num_layers"], doc["num_experts"], doc["vocab_size"]) == (4, 8, 32784)
    assert doc["param_dtype"] == doc["compute_dtype"] == "bfloat16"
    assert doc["num_parameters"] == 494_777_444
    # 4 layers x (19.5 CCA + 1.3 router + 12.6 held experts) + 134.3 head, MFLOP a token
    assert zaya1_8b_ep2.forward_flops_per_sample(doc) / 4096 == pytest.approx(268.04e6, rel=1e-4)
    assert (job["topology"], job["aggregation"]["algorithm"]) == (
        {"type": "fully", "num_nodes": 3}, "fedavg")
    assert "attack" not in job and job["training"]["batch_size"] == 1
    assert job["correct"]["rounds"] * job["correct"]["node_block"] >= 1


def _catalog():
    """Every number of the published config.json (the catalog's ``config``),
    which the file holds under the same key unless ``reduced`` names it."""
    return {
        "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_size": 2048,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256, "vocab_size": 262272,
    }


@pytest.mark.parametrize("key", sorted(_catalog()))
def test_a_published_number_is_held_or_stated_as_cut(key):
    published = _catalog()
    doc = json.loads((BENCH / "configs/zaya1_8b_ep2.json").read_text())
    if key in doc["reduced"]:
        assert doc["published"][key] == published[key] != doc[key]
    else:
        assert doc[key] == published[key]
    params = doc["model"]["params"]
    if key in params and key not in ("num_hidden_layers", "vocab_size"):
        assert params[key] == published[key]  # every width the program is given
    assert doc["rope_parameters"]["hybrid"]["rope_theta"] == params["rope_theta"] == 5000000


def test_the_tiny_cell_runs_and_follows_its_reference(root):
    r = harness.run_cell(Cell("tiny_zaya_fedavg", root=root), seed=2**31 + 43,
                         seconds=0.5, trace=False)
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
    cell = Cell("tiny_zaya_fedavg", root=root)
    spans = harness.Spans()
    network, inputs, captured, _ = harness.first_calls(cell, 79, spans)
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


# The faults of the model's own, each one step of the mechanism left out of
# the reference put in the program's place.
FAULTS = {
    "fp8_operands": None,
    "value_shift_left_out": ("_values", lambda original: lambda v: v),
    "second_tap_left_out": (
        "_convolved",
        lambda original: lambda z, a, b, dtype: original(z, a[:1], b[:1], dtype)),
    "depth_average_left_out": (
        "_depth_average", lambda original: lambda r, carried, gamma, first: r),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_control_and_each_fault_fail_a_limit(followed, fault, monkeypatch):
    job, _, stand_in = followed
    if FAULTS[fault] is None:
        job = dataclasses.replace(job, compute_dtype="float8_e4m3fn")
    else:
        name, make = FAULTS[fault]
        monkeypatch.setattr(zaya1, name, make(getattr(zaya1, name)))
    numbers = stand_in(job)
    assert _failed(numbers), numbers


def test_the_configuration_checks_pass_on_the_tiny_root(root):
    files.check_configuration(root, "tiny_zaya")
    files.check_cell(Cell("tiny_zaya_fedavg", root=root))
    files.check_generator_and_loss(root, "tiny_zaya")
