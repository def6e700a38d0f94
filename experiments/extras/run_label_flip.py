#!/usr/bin/env python3
"""Data-poisoning evidence: label_flip vs state-filtering defenses.

The scenario this threat model exists to demonstrate (label_flip.py,
Tolpegin et al. 2020): poisoned nodes train on rotated labels and
broadcast honest-looking states, so Byzantine rules that filter outlier
STATES (krum, trimmed mean) have nothing to reject — unlike the gaussian
/ ALIE scenarios in run_robust_stats.py where they visibly defend.

Expected orderings (asserted, committed to results_label_flip.json):
  1. the poison bites: fedavg poisoned < fedavg clean by a wide margin;
  2. state filters do NOT restore clean accuracy: krum and trimmed_mean
     under label_flip stay well below the clean baseline (the honest
     negative result — a robust-aggregation story that omitted it would
     overclaim);
  3. sanity: every run learns something (> chance).

Usage: python experiments/extras/run_label_flip.py
Writes results_label_flip.json next to this file (committed).
"""

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import yaml

HERE = Path(__file__).parent

BASE = {
    "experiment": {"name": "label-flip-extras", "seed": 42, "rounds": 40},
    "topology": {"type": "fully", "num_nodes": 10},
    "training": {"local_epochs": 2, "batch_size": 32, "lr": 0.01},
    "data": {"adapter": "wearables.uci_har",
             "params": {"partition_method": "dirichlet", "alpha": 0.5}},
    "model": {"factory": "wearables.uci_har", "params": {}},
    "backend": "simulation",
}

ATTACK = {"enabled": True, "type": "label_flip", "percentage": 0.3,
          "params": {"flip_fraction": 1.0}}

# Distance-based rules (expected to FAIL against data poisoning) and
# performance-probe rules (expected to DEFEND: the probe evaluates
# neighbor models on the node's own CLEAN data, and a poisoned model
# scores badly regardless of how honest its parameters look).
RULES = {
    "fedavg": {},
    "krum": {"num_compromised": 3},
    "trimmed_mean": {"trim_ratio": 0.3},
    "ubar": {"rho": 0.7},
    "evidential_trust": {},
}

CHANCE = 1.0 / 6.0  # UCI HAR: 6 classes


def run_cfg(cfg: dict, tag: str) -> dict:
    with tempfile.TemporaryDirectory() as td:
        cfg_path = Path(td) / f"{tag}.yaml"
        out_path = Path(td) / f"{tag}.json"
        cfg_path.write_text(yaml.safe_dump(cfg))
        env = dict(os.environ)
        proc = subprocess.run(
            [sys.executable, "-m", "murmura_tpu", "run", str(cfg_path),
             "-o", str(out_path)],
            capture_output=True, text=True, timeout=1800,
            cwd=HERE.parent.parent, env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{tag} failed:\n{(proc.stderr or proc.stdout)[-2000:]}"
            )
        hist = json.loads(out_path.read_text())
        key = "honest_accuracy" if hist.get("honest_accuracy") else "mean_accuracy"
        return {"final_accuracy": hist[key][-1], "metric": key}


def main():
    results = {}

    clean = dict(BASE)
    clean["aggregation"] = {"algorithm": "fedavg", "params": {}}
    results["fedavg_clean"] = run_cfg(clean, "fedavg_clean")
    print("fedavg_clean", results["fedavg_clean"], flush=True)

    for rule, params in RULES.items():
        cfg = dict(BASE)
        cfg["aggregation"] = {"algorithm": rule, "params": dict(params)}
        cfg["attack"] = dict(ATTACK)
        if rule == "evidential_trust":
            cfg["model"] = {"factory": "wearables.uci_har",
                            "params": {"evidential": True}}
        tag = f"{rule}_label_flip"
        results[tag] = run_cfg(cfg, tag)
        print(tag, results[tag], flush=True)

    clean_acc = results["fedavg_clean"]["final_accuracy"]
    checks = {
        "poison_bites_fedavg":
            results["fedavg_label_flip"]["final_accuracy"] < clean_acc - 0.1,
        # The honest negative result: state filters do not restore the
        # clean baseline against data poisoning (within 5% of it would
        # mean they effectively defended).
        "krum_does_not_restore_clean":
            results["krum_label_flip"]["final_accuracy"] < clean_acc - 0.05,
        "trimmed_does_not_restore_clean":
            results["trimmed_mean_label_flip"]["final_accuracy"]
            < clean_acc - 0.05,
        # The other half of the taxonomy: performance-probe rules DO
        # defend — the probe scores poisoned models on clean local data.
        "ubar_defends":
            results["ubar_label_flip"]["final_accuracy"] > clean_acc - 0.05,
        "evidential_trust_defends":
            results["evidential_trust_label_flip"]["final_accuracy"]
            > clean_acc - 0.08,
        "probes_beat_distance_filters":
            min(results["ubar_label_flip"]["final_accuracy"],
                results["evidential_trust_label_flip"]["final_accuracy"])
            > max(results["krum_label_flip"]["final_accuracy"],
                  results["trimmed_mean_label_flip"]["final_accuracy"]) + 0.1,
        "all_learn_above_chance": all(
            r["final_accuracy"] > CHANCE + 0.05 for r in results.values()
        ),
    }
    blob = {
        "note": (
            "label_flip poisons TRAINING DATA of 30% of nodes "
            "(flip_fraction 1.0); broadcast states are untouched, so "
            "state-distance filters have nothing to reject (krum and "
            "trimmed_mean land BELOW plain fedavg: they filter honest "
            "heterogeneity while the poison rides through) — while the "
            "performance-probe rules defend: UBAR's loss probe and "
            "evidential trust's uncertainty probe score poisoned models "
            "on clean local data (ubar even beats the clean fedavg "
            "baseline).  The full defense taxonomy in one scenario."
        ),
        "scenarios": results,
        "checks": checks,
        "all_pass": all(checks.values()),
    }
    (HERE / "results_label_flip.json").write_text(
        json.dumps(blob, indent=2) + "\n"
    )
    print(json.dumps(blob["checks"]))
    if not blob["all_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
