#!/usr/bin/env python3
"""Beyond-parity rule evidence: median / trimmed_mean / geometric_median
on the UCI-HAR synthetic fallback, clean vs 20% gaussian, against the
fedavg contrast.

The committed paper matrix (experiments/paper/) covers the six reference
rules; this compact companion anchors the three robust additions the same
way: each robust rule under attack must stay within 0.25 of its clean
baseline AND beat attacked fedavg by >= 0.15.

Usage: python experiments/extras/run_robust_stats.py
Writes results.json next to this file (committed).
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
    "experiment": {"name": "extras", "seed": 42, "rounds": 50},
    "topology": {"type": "fully", "num_nodes": 10},
    "training": {"local_epochs": 2, "batch_size": 32, "lr": 0.01},
    "data": {"adapter": "wearables.uci_har",
             "params": {"partition_method": "dirichlet", "alpha": 0.5}},
    "model": {"factory": "wearables.uci_har", "params": {}},
    "backend": "simulation",
}

ATTACK = {"enabled": True, "type": "gaussian", "percentage": 0.2,
          "params": {"noise_std": 10.0}}

# The stealth scenario: ALIE hides inside the honest variance envelope
# (alie.py).  z is explicit because the paper's z_max rule degenerates to
# 0 at n=10/m=2 (the quantile construction targets larger coalitions).
ALIE_ATTACK = {"enabled": True, "type": "alie", "percentage": 0.2,
               "params": {"z": 1.5}}

RULES = {
    "fedavg": {},
    "median": {},
    # trim must cover the Byzantine fraction per neighborhood: 20% of 10
    # nodes = 2 Byzantine; candidates = 10 -> trim_ratio 0.3 drops 3/side.
    "trimmed_mean": {"trim_ratio": 0.3},
    "geometric_median": {"max_iters": 8},
}


def run_cfg(cfg: dict, tag: str) -> dict:
    with tempfile.TemporaryDirectory() as td:
        cfg_path = Path(td) / f"{tag}.yaml"
        out_path = Path(td) / f"{tag}.json"
        cfg_path.write_text(yaml.safe_dump(cfg))
        env = dict(os.environ)
        # Same persistent compile cache as the paper runner: runs sharing
        # a program shape compile once (one shape per rule x scenario).
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
    for rule, params in RULES.items():
        for scenario in ("clean", "attacked"):
            tag = f"{rule}_{scenario}"
            cfg = json.loads(json.dumps(BASE))  # deep copy
            cfg["aggregation"] = {"algorithm": rule, "params": params}
            if scenario == "attacked":
                cfg["attack"] = ATTACK
            print(f"[{tag}] ...", file=sys.stderr, flush=True)
            results[tag] = run_cfg(cfg, tag)

    # ALIE evidence: the colluding stealth attack vs plain averaging and
    # the strongest beyond-parity rule.  (The coordinate-wise rules are
    # omitted: ALIE is designed to sit inside the per-coordinate envelope
    # they filter on, and their clean accuracy on this non-IID task is
    # already the limiting factor.)
    for rule in ("fedavg", "geometric_median"):
        tag = f"{rule}_alie"
        cfg = json.loads(json.dumps(BASE))
        cfg["aggregation"] = {"algorithm": rule,
                               "params": RULES.get(rule, {})}
        cfg["attack"] = ALIE_ATTACK
        print(f"[{tag}] ...", file=sys.stderr, flush=True)
        results[tag] = run_cfg(cfg, tag)

    checks = {
        "fedavg_collapses": (
            results["fedavg_attacked"]["final_accuracy"]
            < results["fedavg_clean"]["final_accuracy"] - 0.15
        ),
    }
    for rule in (r for r in RULES if r != "fedavg"):
        att = results[f"{rule}_attacked"]["final_accuracy"]
        clean = results[f"{rule}_clean"]["final_accuracy"]
        # Absolute floor: robust rules may trade clean accuracy for
        # robustness on non-IID shards (the coordinate-wise rules do;
        # geometric_median largely doesn't), but a broken rule
        # (near-constant output ~= chance = 1/6) must not pass on
        # relative checks alone.
        checks[f"{rule}_clean_above_floor"] = clean >= 0.30
        checks[f"{rule}_holds_under_attack"] = att >= clean - 0.25
        checks[f"{rule}_beats_attacked_fedavg"] = (
            att >= results["fedavg_attacked"]["final_accuracy"] + 0.15
        )

    checks["alie_degrades_fedavg"] = (
        results["fedavg_alie"]["final_accuracy"]
        < results["fedavg_clean"]["final_accuracy"] - 0.15
    )
    checks["geometric_median_holds_under_alie"] = (
        results["geometric_median_alie"]["final_accuracy"]
        >= results["geometric_median_clean"]["final_accuracy"] - 0.25
    )
    checks["geometric_median_beats_fedavg_under_alie"] = (
        results["geometric_median_alie"]["final_accuracy"]
        >= results["fedavg_alie"]["final_accuracy"] + 0.03
    )

    blob = {
        # ALIE caveat carried with the numbers, not just the module
        # docstring (round-4 advisor): on the simulation/tpu backends the
        # colluding vector uses the TRUE honest-population mu/sigma — the
        # omniscient variant, strictly STRONGER than Baruch et al.'s
        # coalition-estimated construction (which the ZMQ backend
        # implements).  '*_alie' rows are an upper bound on the paper
        # attack's effect.
        "alie_note": (
            "ALIE rows use omniscient honest-population statistics "
            "(stronger than the paper's coalition estimator; see "
            "murmura_tpu/attacks/alie.py)"
        ),
        "results": results,
        "checks": checks,
        "all_pass": all(checks.values()),
    }
    (HERE / "results.json").write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob, indent=2))
    return 0 if blob["all_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
