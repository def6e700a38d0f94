#!/usr/bin/env python3
"""Run the paper experiment matrix and summarize results
(reference: experiments/paper/run_comprehensive.py:1-40).

Improvement over the reference: the CLI writes history JSON directly
(`murmura run cfg -o out.json`), so results are read structurally instead of
regex-scraping stdout (reference: run_comprehensive.py:58-69).

Usage:
    python experiments/paper/run_comprehensive.py                  # everything
    python experiments/paper/run_comprehensive.py --category attacks
    python experiments/paper/run_comprehensive.py --dataset uci_har
    python experiments/paper/run_comprehensive.py --summary-only
"""

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

PAPER_DIR = Path(__file__).parent
CONFIG_DIR = PAPER_DIR / "configs"
RESULTS_DIR = PAPER_DIR / "results"
CATEGORIES = ["baseline", "heterogeneity", "attacks", "topologies",
              "ablation", "ablation_attacked"]


def run_one(cfg_path: Path, out_json: Path, timeout: float,
            device: str = None) -> dict:
    """Run one experiment through the CLI; returns a result record."""
    t0 = time.time()
    record = {"config": str(cfg_path.relative_to(CONFIG_DIR))}
    # Each child applies the one compile-cache rule itself
    # (factories.apply_compilation_cache): the matrix reuses a handful of
    # program shapes across hundreds of subprocesses, so all but the first
    # few runs skip compilation entirely.
    env = dict(os.environ)
    cmd = [sys.executable, "-m", "murmura_tpu", "run", str(cfg_path),
           "-o", str(out_json), "--quiet"]
    if device:
        cmd += ["--device", device]
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=timeout,
            cwd=PAPER_DIR.parent.parent,
            env=env,
        )
    except subprocess.TimeoutExpired:
        record.update(ok=False, error=f"timeout after {timeout}s",
                      wall_s=round(time.time() - t0, 1))
        return record
    record.update(ok=proc.returncode == 0, wall_s=round(time.time() - t0, 1))
    if proc.returncode != 0:
        record["error"] = proc.stderr[-2000:]
        return record

    hist = json.loads(out_json.read_text())
    acc = hist.get("mean_accuracy", [])
    record.update(
        final_accuracy=acc[-1] if acc else None,
        peak_accuracy=max(acc) if acc else None,
        final_std=(hist.get("std_accuracy") or [None])[-1],
        honest_accuracy=(hist.get("honest_accuracy") or [None])[-1],
        rounds=len(acc),
    )
    if hist.get("mean_vacuity"):
        record["final_vacuity"] = hist["mean_vacuity"][-1]
    return record


def summarize(records: list) -> str:
    """RESULTS_SUMMARY.md: final accuracy per dataset x algorithm per
    category (reference: experiments/paper/RESULTS_SUMMARY.md)."""
    lines = [
        "# Results summary",
        "",
        "## Reading these numbers (synthetic-regime expectations)",
        "",
        "This matrix runs on shape-identical **synthetic stand-ins** for the",
        "wearable datasets (zero-egress environment), evaluated on per-node",
        "holdouts from each node's own partition. Absolute accuracies are",
        "therefore not comparable to the published tables; the orderings are",
        "(asserted by `assert_orderings.py`, 15 families). Two places where",
        "the synthetic regime *visibly changes* the picture, and why:",
        "",
        "- **Krum's clean-run accuracies (~0.16-0.31 on `fully`) are",
        "  expected, not a defect.** Krum outputs a *single selected state*.",
        "  Under strongly non-IID per-node label distributions with",
        "  per-node evaluation, one neighbor's model cannot serve every",
        "  node's personalized holdout, so the selected state scores low",
        "  everywhere — and the more candidates there are (`fully`), the",
        "  likelier the selection lands far from any given node (see the",
        "  krum-connectivity-weakness ordering: krum/ring beats",
        "  krum/fully). The published 38.8-54.5 % figures are on real data",
        "  against a shared test distribution, which rewards any central",
        "  state. The reference reports the same qualitative collapse",
        "  (krum 46.8 vs fedavg 85.3 on UCI HAR).",
        "- **The heterogeneity (alpha) direction flips.** Published Table II",
        "  accuracy rises with alpha; here lower alpha = fewer classes per",
        "  node = an *easier personalized* task under per-node holdouts, so",
        "  robust-rule accuracy falls as alpha grows (asserted as the",
        "  alpha-direction family).",
        "",
    ]
    by_cat = {}
    for r in records:
        if not r.get("ok"):
            continue
        cat = r["config"].split("/", 1)[0]
        by_cat.setdefault(cat, []).append(r)
    for cat in CATEGORIES:
        if cat not in by_cat:
            continue
        lines += [f"## {cat}", "", "| config | final acc | peak acc | honest acc |",
                  "|---|---|---|---|"]
        for r in sorted(by_cat[cat], key=lambda r: r["config"]):
            fmt = lambda v: f"{v:.4f}" if isinstance(v, float) else "—"
            lines.append(
                f"| {Path(r['config']).stem} | {fmt(r['final_accuracy'])} "
                f"| {fmt(r['peak_accuracy'])} | {fmt(r.get('honest_accuracy'))} |"
            )
        lines.append("")
    failed = [r for r in records if not r.get("ok")]
    if failed:
        lines += ["## Failures", ""] + [f"- {r['config']}" for r in failed]
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--category", choices=CATEGORIES, default=None)
    ap.add_argument("--dataset", default=None,
                    help="Substring filter on config names")
    ap.add_argument("--summary-only", action="store_true")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--limit", type=int, default=None,
                    help="Run at most N configs (smoke testing)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="Concurrent experiment subprocesses (use ~nproc; "
                         "each experiment is single-threaded on CPU)")
    ap.add_argument("--device", choices=["cpu", "tpu"], default=None,
                    help="Force the JAX platform for every run (a single "
                         "TPU chip runs the matrix serially: --jobs 1)")
    args = ap.parse_args()
    if args.jobs > 1 and args.device != "cpu":
        # A chip belongs to one process: concurrent children may only run
        # where the platform is pinned to the CPU.
        sys.exit("--jobs > 1 requires --device cpu (one process per chip)")

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    results_file = RESULTS_DIR / "results.json"
    records = (
        json.loads(results_file.read_text()) if results_file.exists() else []
    )

    if not args.summary_only:
        if not CONFIG_DIR.exists():
            sys.exit("No configs found — run generate_all_configs.py first")
        cfgs = sorted(CONFIG_DIR.glob("**/*.yaml"))
        if args.category:
            cfgs = [c for c in cfgs if c.parent.name == args.category]
        if args.dataset:
            cfgs = [c for c in cfgs if args.dataset in c.name]
        if args.limit:
            cfgs = cfgs[: args.limit]
        done = {r["config"] for r in records if r.get("ok")}
        todo = [c for c in cfgs if str(c.relative_to(CONFIG_DIR)) not in done]

        def out_path(rel: str) -> Path:
            out = RESULTS_DIR / "histories" / rel.replace("/", "_").replace(
                ".yaml", ".json"
            )
            out.parent.mkdir(parents=True, exist_ok=True)
            return out

        if args.jobs <= 1:
            for i, cfg in enumerate(todo):
                rel = str(cfg.relative_to(CONFIG_DIR))
                print(f"[{i + 1}/{len(todo)}] {rel}", flush=True)
                records = [r for r in records if r["config"] != rel]
                records.append(
                    run_one(cfg, out_path(rel), args.timeout, args.device)
                )
                results_file.write_text(json.dumps(records, indent=2))
        else:
            from concurrent.futures import ThreadPoolExecutor, as_completed

            with ThreadPoolExecutor(max_workers=args.jobs) as pool:
                futs = {
                    pool.submit(
                        run_one, cfg,
                        out_path(str(cfg.relative_to(CONFIG_DIR))),
                        args.timeout, args.device,
                    ): str(cfg.relative_to(CONFIG_DIR))
                    for cfg in todo
                }
                for i, fut in enumerate(as_completed(futs)):
                    rel = futs[fut]
                    print(f"[{i + 1}/{len(todo)}] {rel}", flush=True)
                    records = [r for r in records if r["config"] != rel]
                    records.append(fut.result())
                    results_file.write_text(json.dumps(records, indent=2))

    (PAPER_DIR / "RESULTS_SUMMARY.md").write_text(summarize(records))
    ok = sum(1 for r in records if r.get("ok"))
    print(f"{ok}/{len(records)} experiments ok; summary in RESULTS_SUMMARY.md")


if __name__ == "__main__":
    main()
