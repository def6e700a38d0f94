#!/usr/bin/env python3
"""Run the 3-condition DMTT experiment and assert its headline ordering.

Conditions (reference: experiments/paper/dmtt/01..03 — the reference ships
these configs but only placeholder results, documentation/
new_murmura_extension/paper.tex:712):

    01 static baseline   — fixed fully-connected graph, 30% topology liars
                           poisoning models, plain fedavg.
    02 dynamic no trust  — mobility G^t, same liars, no protocol.
    03 full DMTT         — same G^t + claim verification, Beta-evidence
                           trust, TopB collaborator selection.

Headline claim: full DMTT keeps honest accuracy above the unprotected
dynamic condition (03 > 02 by a clear margin) because trust gating cuts the
poisoned states out of aggregation.

Writes results_dmtt.json next to this file and exits non-zero if the
ordering fails.  Usage:
    python experiments/paper/dmtt/run_dmtt.py [--device cpu|tpu]
"""

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

DMTT_DIR = Path(__file__).parent
REPO = DMTT_DIR.parent.parent.parent
CONDITIONS = ["01_baseline_static", "02_dynamic_no_trust", "03_dmtt"]


def run_one(name: str, device: str, timeout: float) -> dict:
    out = DMTT_DIR / "results" / f"{name}.json"
    out.parent.mkdir(exist_ok=True)
    env = dict(os.environ)
    cmd = [sys.executable, "-m", "murmura_tpu", "run",
           str(DMTT_DIR / f"{name}.yaml"), "-o", str(out), "--quiet"]
    if device:
        cmd += ["--device", device]
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, cwd=REPO, env=env)
    except subprocess.TimeoutExpired:
        return {"condition": name, "ok": False,
                "error": f"timeout after {timeout}s",
                "wall_s": round(time.time() - t0, 1)}
    rec = {"condition": name, "ok": proc.returncode == 0,
           "wall_s": round(time.time() - t0, 1)}
    if proc.returncode != 0:
        rec["error"] = proc.stderr[-1500:]
        return rec
    hist = json.loads(out.read_text())
    honest = hist.get("honest_accuracy") or hist.get("mean_accuracy")
    rec.update(
        final_honest_accuracy=honest[-1],
        peak_honest_accuracy=max(honest),
        final_mean_accuracy=hist["mean_accuracy"][-1],
        rounds=len(hist["mean_accuracy"]),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--device", choices=["cpu", "tpu"], default=None)
    ap.add_argument("--timeout", type=float, default=1200.0)
    args = ap.parse_args()

    records = [run_one(c, args.device, args.timeout) for c in CONDITIONS]
    by = {r["condition"]: r for r in records}

    failures = []
    if all(r.get("ok") for r in records):
        dmtt = by["03_dmtt"]["final_honest_accuracy"]
        no_trust = by["02_dynamic_no_trust"]["final_honest_accuracy"]
        static = by["01_baseline_static"]["final_honest_accuracy"]
        if not dmtt >= no_trust + 0.1:
            failures.append(
                f"full DMTT ({dmtt:.4f}) should beat dynamic-no-trust "
                f"({no_trust:.4f}) by >= 0.1"
            )
        if not dmtt >= static:
            failures.append(
                f"full DMTT ({dmtt:.4f}) should not trail the poisoned "
                f"static baseline ({static:.4f})"
            )
    else:
        failures.append("not all conditions ran ok")

    blob = {"records": records, "ordering_failures": failures}
    (DMTT_DIR / "results_dmtt.json").write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob, indent=2))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
