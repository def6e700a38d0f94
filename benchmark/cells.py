"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is an entry of ``workloads``: a configuration (``configs[].file``,
the model's sizes as they are run) under a traffic mix
(``benchmark/workloads/<traffic>.json``, the job: graph, rule, attack,
training, dispatch).  A per-layer metric is ``layer_metrics/<name>.json``,
which names a reader of ``readers/`` and its arguments.  Nothing here knows
a cell, a configuration or a metric by name.
"""

import importlib
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _read(Path(root) / "BENCHMARK.json")


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and job."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / HERE.name
        self.bench = load_benchmark(self.root)
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json (has: {sorted(entries)})"
            )
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = _read(self.root / configs[self.entry["config"]]["file"])
        self.job = _read(self.dir / "workloads" / f"{self.entry['traffic']}.json")

    def metrics(self, kind: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [
            m for m in self.bench[kind]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    @property
    def chunk(self) -> int:
        return int(self.job["dispatch"].get("chunk", 1))

    def train_kwargs(self) -> Dict[str, Any]:
        """One call of the window: ``chunk`` rounds.  With
        ``dispatch.defer_metrics`` the program dispatches them all ahead and
        fetches their metrics afterwards (``Network.train``'s throughput
        mode); the call returns when the last of them has run."""
        d = self.job["dispatch"]
        return {
            "rounds": self.chunk,
            "eval_every": int(d.get("eval_every", 1)),
            "rounds_per_dispatch": int(d.get("rounds_per_dispatch", 1)),
            "defer_metrics": bool(d.get("defer_metrics", False)),
        }

    def program_config(self, seed: int) -> Dict[str, Any]:
        """The YAML a user would write for this cell, as a dict: what
        ``murmura run`` validates and ``build_network_from_config`` wires."""
        n = int(self.job["topology"]["num_nodes"])
        data = self.config["data"]
        data_params = dict(data["params"])
        data_params["num_samples"] = int(data["samples_per_node"]) * n
        tpu = {"compute_dtype": self.config["compute_dtype"], **self.job["tpu"]}
        if self.config.get("param_dtype"):
            tpu["param_dtype"] = self.config["param_dtype"]
        raw = {
            "experiment": {
                "name": f"bench-{self.name}", "seed": int(seed),
                "rounds": int(self.job["experiment"]["rounds"]),
            },
            "topology": {**self.job["topology"], "seed": int(seed)},
            "aggregation": self.job["aggregation"],
            "training": self.job["training"],
            "data": {"adapter": data["adapter"], "params": data_params},
            "model": self.config["model"],
            "backend": "tpu",
            "tpu": tpu,
        }
        if self.job.get("attack"):
            raw["attack"] = self.job["attack"]
        return raw

    def layer_metric(self, name: str) -> Dict[str, Any]:
        return _read(self.dir / "layer_metrics" / f"{name}.json")

    def module(self, kind: str, name: str):
        """``benchmark.<kind>.<name>``: a reader, a roofline or a
        configuration's FLOPs function, found by the name a file gives."""
        return importlib.import_module(f"benchmark.{kind}.{name}")


def load_peaks(device_kind: str) -> Dict[str, Any]:
    peaks = _read(HERE / "peaks.json")
    if device_kind not in peaks["devices"]:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            "benchmark/peaks.json with its source"
        )
    return peaks["devices"][device_kind]
