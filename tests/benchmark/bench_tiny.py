"""A tiny copy of the benchmark's data files for the CPU tests: the same
harness, readers and reference, on cells a test run can hold."""

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"


def _load(path):
    return json.loads(Path(path).read_text())


def make_root(tmp: Path) -> Path:
    """A root with its own ``BENCHMARK.json`` and data files: tiny models
    and few nodes.  The limits are these cells' own, between what the
    program and what the control and the faults read on the CPU at this
    size (float32 resident parameters, a few thousand of them): the chip's
    readings, which the real cells' limits come from, do not carry over."""
    root = Path(tmp)
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir(parents=True)
    shutil.copytree(BENCH / "layer_metrics", root / "benchmark" / "layer_metrics")
    bench = _load(REPO / "BENCHMARK.json")

    cnn = _load(BENCH / "configs" / "femnist_cnn.json")
    cnn.update(name="tiny_cnn", conv_channels=[8, 16], dense_units=[256],
               num_parameters=220318,
               model={"factory": "leaf.femnist.tiny", "params": {}})
    cnn["data"].update(samples_per_node=40, held_out_per_node=8)
    (root / "benchmark" / "configs" / "tiny_cnn.json").write_text(json.dumps(cnn))

    jobs = {
        "tiny_sketchguard": ({"num_nodes": 16, "p": 0.5},
                             {"loss": 2e-4, "eval_loss": 3e-6, "first_update": 0.1,
                              "change": 0.1}),
        "tiny_sketchguard_kreg": ({"type": "k-regular", "num_nodes": 10, "k": 4},
                                  {"loss": 2e-4, "eval_loss": 3e-6,
                                   "first_update": 0.1, "change": 0.1}),
    }
    workloads = []
    for name, (topo, limits) in jobs.items():
        job = _load(BENCH / "workloads" / "sketchguard_er_n64.json")
        job["topology"].update(topo)
        job["correct"]["limits"] = limits
        job["training"]["batch_size"] = 8
        job["correct"]["node_block"] = 4
        # The cell's own mode (rounds dispatched ahead, two to a call) in
        # one job, a fetch every round, one round to a call, in the other.
        job["dispatch"].update(
            {"chunk": 1, "defer_metrics": False} if "kreg" in name else {"chunk": 2}
        )
        assert job["dispatch"]["defer_metrics"] is ("kreg" not in name)
        job["trace_rounds"] = 2
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(json.dumps(job))
        workloads.append({"name": name, "config": "tiny_cnn", "traffic": name,
                          "chips": 1, "why": "a test's cell"})
    bench["configs"] = [
        {"name": d["name"], "source": d["source"],
         "file": f"benchmark/configs/{d['name']}.json", "reduced": [], "why": "tiny"}
        for d in (cnn,)
    ]
    bench["workloads"] = workloads
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
