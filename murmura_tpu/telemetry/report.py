"""``murmura report <run_dir>``: render a run manifest + event stream.

Reads only the telemetry schema (schema.py) — any producer's run directory
works: a CLI run, a Monitor-folded distributed run, or a serve tenant's.
Sections render only when their data exists, so a minimal manifest still
produces a useful summary instead of a wall of empty tables.
"""

import math
from typing import Any, Dict, List, Optional

from murmura_tpu.telemetry.writer import iter_events, read_manifest


def _fmt(v: Any, nd: int = 4) -> str:
    if isinstance(v, float):
        if math.isnan(v):
            return "nan"
        return f"{v:.{nd}f}"
    return str(v)


def _mean(xs: List[float]) -> float:
    finite = [x for x in xs if isinstance(x, (int, float)) and math.isfinite(x)]
    return sum(finite) / len(finite) if finite else float("nan")


def build_report(run_dir) -> Dict[str, Any]:
    """Machine-readable report dict (the renderer's single source; tests
    assert on this instead of scraping table text)."""
    manifest = read_manifest(run_dir)
    if manifest is None:
        raise FileNotFoundError(
            f"no readable manifest.json under {run_dir} — not a telemetry "
            "run directory (docs/OBSERVABILITY.md)"
        )
    events = list(iter_events(run_dir))
    report: Dict[str, Any] = {"manifest": manifest, "run_dir": str(run_dir)}

    history = manifest.get("history") or {}
    if history.get("round"):
        finite_acc = [
            a for a in history["mean_accuracy"]
            if isinstance(a, (int, float)) and math.isfinite(a)
        ]
        acc: Dict[str, Any] = {
            "rounds_recorded": len(history["round"]),
            "final_round": history["round"][-1],
            "final_mean_accuracy": history["mean_accuracy"][-1],
            # max over finite entries only: a partial-flush NaN row (an
            # all-skipped distributed round) must not poison the best.
            "best_mean_accuracy": max(finite_acc, default=float("nan")),
            "final_mean_loss": history["mean_loss"][-1],
        }
        if history.get("honest_accuracy"):
            acc["final_honest_accuracy"] = history["honest_accuracy"][-1]
        if history.get("compromised_accuracy"):
            acc["final_compromised_accuracy"] = history["compromised_accuracy"][-1]
        report["accuracy"] = acc

        robustness = {
            k: {"mean": _mean(v), "last": v[-1] if v else None}
            for k, v in history.items()
            if k.startswith("agg_") and not k.startswith("agg_tap_")
        }
        for k in ("skipped_nodes", "reporting_nodes"):
            if history.get(k):
                robustness[k] = {"mean": _mean(history[k]), "last": history[k][-1]}
        if robustness:
            report["robustness"] = robustness

    # ---- time breakdown -------------------------------------------------
    phase = [e for e in events if e.get("type") == "phase_times"]
    if phase:
        by_mode: Dict[str, List[float]] = {}
        for e in phase:
            by_mode.setdefault(e.get("mode", "?"), []).append(e.get("wall_s", 0.0))
        report["time"] = {
            "rounds_timed": len(phase),
            "total_s": sum(sum(v) for v in by_mode.values()),
            "by_mode": {
                m: {
                    "rounds": len(v),
                    "mean_s": _mean(v),
                    "max_s": max(v),
                }
                for m, v in by_mode.items()
            },
        }
        # Critical-path decomposition under overlap (exchange.pipeline;
        # docs/PERFORMANCE.md "Pipelined rounds"): pipelined rounds run
        # train and the delayed exchange+aggregate CONCURRENTLY inside
        # one dispatch, so each wall_s above is the round's critical
        # path and the per-phase named_scope brackets (murmura.train /
        # murmura.aggregate) overlap in profiler-trace time — a
        # per-phase sum would double-count the hidden exchange.  This
        # section makes the overlap explicit instead of letting readers
        # add brackets; serialized runs (no ``overlap`` marker) emit no
        # section and their time report is byte-identical to previous
        # releases (pinned by tests/test_pipeline.py).
        overlapped = [e for e in phase if e.get("overlap")]
        if overlapped:
            walls = [e.get("wall_s", 0.0) for e in overlapped]
            report["time"]["critical_path"] = {
                "overlap": overlapped[0].get("overlap"),
                "rounds": len(overlapped),
                "mean_s": _mean(walls),
                "total_s": sum(walls),
                "concurrent_phases": [
                    "murmura.train",
                    "murmura.aggregate (delayed, round r-1)",
                ],
                "note": (
                    "wall_s is the per-round critical path; the "
                    "exchange+aggregate bracket runs concurrently with "
                    "training and must not be added to it"
                ),
            }
    ckpt = [e for e in events if e.get("type") == "checkpoint"]
    if ckpt:
        saves = [e for e in ckpt if e.get("action") == "save"]
        report["checkpoints"] = {
            "saves": len(saves),
            "restores": len(ckpt) - len(saves),
            "total_save_s": sum(e.get("duration_s", 0.0) for e in saves),
        }
    mem = [
        e for e in events
        if e.get("type") == "memory" and isinstance(e.get("stats"), dict)
    ]
    if mem:
        peaks = [
            e["stats"].get("peak_bytes_in_use") or e["stats"].get("bytes_in_use")
            for e in mem
        ]
        peaks = [p for p in peaks if isinstance(p, (int, float))]
        if peaks:
            report["memory"] = {
                "samples": len(mem),
                "peak_bytes_in_use": max(peaks),
                "device_kind": mem[-1].get("device_kind"),
            }
    prof = [e for e in events if e.get("type") == "profile"]
    if prof:
        report["profile"] = prof

    # ---- faults (per-node quarantine/alive from round events) -----------
    rounds = [e for e in events if e.get("type") == "round"]
    faults: Dict[str, Any] = {}
    for key, out in (
        ("agg_tap_quarantined", "quarantined_rounds"),
        ("agg_tap_attack_scrubbed", "scrubbed_rounds"),
        ("agg_tap_alive", "alive_rounds"),
    ):
        per_node = _per_node_sum(rounds, key)
        if per_node is not None:
            faults[out] = per_node
    if faults:
        report["faults"] = faults

    # ---- audit taps: per-node acceptance/rejection ----------------------
    taps = _tap_report(rounds)
    if taps:
        report["taps"] = taps

    # ---- bounded staleness (core/stale.py; docs/ROBUSTNESS.md) ----------
    # ``agg_tap_stale_used`` counts, per round, how many of node i's
    # in-edges were served from the payload cache; ``agg_tap_stale_age``
    # is the age of each SERVED sender's cached payload (0 = fresh or
    # unserved).  The histogram answers "how stale did the exchange
    # actually run" next to the configured max_staleness bound.
    stale = _stale_report(rounds)
    if stale:
        report["staleness"] = stale

    # ---- declared influence contract ------------------------------------
    # The rule's InfluenceDecl (aggregation/base.py; verified statically by
    # `murmura check --flow` MUR800-802) doubles as runtime documentation:
    # rendered next to the observed audit-tap rejection counts so "how much
    # could a bad neighbor have moved me" sits beside "who actually got
    # rejected".
    influence = _declared_influence(manifest)
    if influence:
        report["influence"] = influence

    counters = manifest.get("counters") or {}
    if counters:
        report["counters"] = counters
    return report


def _declared_influence(manifest: dict) -> Optional[Dict[str, Any]]:
    """The configured rule's declared Byzantine influence contract, built
    from the manifest's config snapshot.  Best-effort: a manifest without
    a config snapshot and pre-influence runs have no (usable) aggregation
    config."""
    cfg = manifest.get("config") or {}
    agg_cfg = cfg.get("aggregation") or {}
    algo = agg_cfg.get("algorithm")
    if not algo:
        return None
    try:
        from murmura_tpu.aggregation import build_aggregator

        agg = build_aggregator(
            algo, dict(agg_cfg.get("params") or {}), model_dim=1,
            total_rounds=1,
        )
    except Exception:  # noqa: BLE001 — stale config snapshots stay renderable
        return None
    decl = agg.influence
    if decl is None:
        return None
    return {
        "rule": algo,
        "kind": decl.kind,
        "declared": decl.describe(),
        "note": decl.note,
    }


def _per_node_sum(rounds: List[dict], key: str) -> Optional[List[float]]:
    rows = [
        e["metrics"][key] for e in rounds
        if isinstance(e.get("metrics"), dict)
        and isinstance(e["metrics"].get(key), list)
    ]
    if not rows:
        return None
    n = max(len(r) for r in rows)
    out = [0.0] * n
    for r in rows:
        for i, v in enumerate(r):
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[i] += v
    return out


def _stale_report(rounds: List[dict]) -> Optional[Dict[str, Any]]:
    """Per-node stale-edge totals + the served-age histogram from the
    bounded-staleness audit taps (agg_tap_stale_used / agg_tap_stale_age
    — core/stale.py)."""
    used = _per_node_sum(rounds, "agg_tap_stale_used")
    if used is None:
        return None
    out: Dict[str, Any] = {
        "stale_in_edges": used,
        "total_stale_edges": sum(used),
    }
    hist: Dict[str, int] = {}
    for e in rounds:
        metrics = e.get("metrics")
        row = metrics.get("agg_tap_stale_age") if isinstance(metrics, dict) else None
        if not isinstance(row, list):
            continue
        for a in row:
            if isinstance(a, (int, float)) and math.isfinite(a) and a > 0:
                hist[str(int(a))] = hist.get(str(int(a)), 0) + 1
    if hist:
        out["age_histogram"] = dict(sorted(hist.items(), key=lambda kv: int(kv[0])))
    return out


def _tap_report(rounds: List[dict]) -> Optional[Dict[str, Any]]:
    """Per-node selection/rejection totals from the in-jit audit taps.

    ``agg_tap_selected_by`` counts, per round, how many peers selected or
    accepted node i's broadcast; ``agg_tap_considered_by`` how many peers
    had it as a candidate (the round's effective in-degree under faults).
    Rejections = considered - selected, summed over recorded rounds — the
    "why did the Byzantine rule reject node 3" view (docs/OBSERVABILITY.md).
    """
    selected = _per_node_sum(rounds, "agg_tap_selected_by")
    if selected is None:
        return None
    considered = _per_node_sum(rounds, "agg_tap_considered_by")
    out: Dict[str, Any] = {"selected_by": selected}
    if considered is not None:
        out["considered_by"] = considered
        out["rejections"] = [
            max(0.0, c - s) for c, s in zip(considered, selected)
        ]
    return out


# ----------------------------------------------------------------------
# rendering


def render_report(run_dir, console=None) -> Dict[str, Any]:
    """Render the report with rich; returns the report dict."""
    from rich.console import Console
    from rich.table import Table

    console = console or Console()
    report = build_report(run_dir)
    m = report["manifest"]
    cfg = m.get("config") or {}
    exp = cfg.get("experiment") or {}
    console.print(
        f"[bold cyan]murmura report[/bold cyan] — run "
        f"[bold]{exp.get('name', m.get('run_id'))}[/bold] "
        f"(kind={m.get('kind')}, schema=v{m.get('schema_version')}, "
        f"run_id={m.get('run_id')}, "
        f"{'finalized' if m.get('finalized') else 'IN PROGRESS'})"
    )

    def kv_table(title: str, mapping: Dict[str, Any]) -> None:
        t = Table(title=title)
        t.add_column("metric", style="cyan")
        t.add_column("value", justify="right")
        for k, v in mapping.items():
            t.add_row(k, _fmt(v))
        console.print(t)

    if "accuracy" in report:
        kv_table("Accuracy", report["accuracy"])
    if "robustness" in report:
        t = Table(title="Robustness / rule statistics (over recorded rounds)")
        t.add_column("stat", style="cyan")
        t.add_column("mean", justify="right")
        t.add_column("last", justify="right")
        for k, v in sorted(report["robustness"].items()):
            t.add_row(k, _fmt(v["mean"]), _fmt(v["last"]))
        console.print(t)
    if "time" in report:
        t = Table(title="Time breakdown")
        t.add_column("dispatch mode", style="cyan")
        t.add_column("rounds", justify="right")
        t.add_column("mean s/round", justify="right")
        t.add_column("max s", justify="right")
        for mode, v in report["time"]["by_mode"].items():
            t.add_row(mode, str(v["rounds"]), _fmt(v["mean_s"]), _fmt(v["max_s"]))
        console.print(t)
        console.print(
            f"  total timed: {_fmt(report['time']['total_s'], 2)}s over "
            f"{report['time']['rounds_timed']} round records"
        )
        cp = report["time"].get("critical_path")
        if cp:
            console.print(
                f"  [cyan]critical path[/cyan] ({cp['overlap']}): "
                f"{cp['rounds']} rounds at {_fmt(cp['mean_s'])}s/round — "
                f"{' + '.join(cp['concurrent_phases'])} run "
                "concurrently; per-phase brackets must not be summed"
            )
    if "checkpoints" in report:
        kv_table("Checkpoints", report["checkpoints"])
    if "memory" in report:
        kv_table("Device memory", report["memory"])
    if "influence" in report:
        inf = report["influence"]
        console.print(
            f"  [cyan]declared influence[/cyan] ({inf['rule']}): "
            f"{inf['declared']}"
        )
    if "staleness" in report:
        stale = report["staleness"]
        hist = stale.get("age_histogram") or {}
        hist_txt = (
            "  ages " + "  ".join(
                f"{a}r:{c}" for a, c in hist.items()
            )
            if hist else ""
        )
        console.print(
            f"  [cyan]bounded staleness[/cyan]: "
            f"{_fmt(stale['total_stale_edges'], 0)} stale edge-serves "
            f"over recorded rounds{hist_txt}"
        )
    if "taps" in report or "faults" in report or "staleness" in report:
        taps = report.get("taps") or {}
        faults = report.get("faults") or {}
        stale_cols = {
            k: v for k, v in (report.get("staleness") or {}).items()
            if k == "stale_in_edges"
        }
        n = max(
            [len(v) for v in taps.values()]
            + [len(v) for v in faults.values()]
            + [len(v) for v in stale_cols.values()]
        )
        t = Table(title="Per-node audit (totals over recorded rounds)")
        t.add_column("node", justify="right")
        cols = []
        for key, src in (
            ("selected_by", taps), ("considered_by", taps),
            ("rejections", taps), ("quarantined_rounds", faults),
            ("scrubbed_rounds", faults), ("alive_rounds", faults),
            ("stale_in_edges", stale_cols),
        ):
            if key in src:
                t.add_column(key, justify="right")
                cols.append(src[key])
        for i in range(n):
            t.add_row(
                str(i), *[_fmt(c[i], 1) if i < len(c) else "-" for c in cols]
            )
        console.print(t)
    if "counters" in report:
        kv_table("Distributed counters", report["counters"])
    extra = [e for e in iter_events(run_dir) if e.get("type") == "extra"]
    if extra:
        console.print(
            f"[yellow]{len(extra)} forward-compat 'extra' event(s) — keys "
            "this version does not understand were preserved, not "
            "dropped[/yellow]"
        )
    return report


# ----------------------------------------------------------------------
# frontier rendering (`murmura report --frontier`; docs/ROBUSTNESS.md
# "The robustness frontier")


def _bar(frac: float, width: int = 16) -> str:
    """Accuracy-fraction bar for the curve rows (unicode blocks)."""
    if not math.isfinite(frac):
        return "?" * width
    filled = int(round(max(0.0, min(1.0, frac)) * width))
    return "█" * filled + "·" * (width - filled)


def render_frontier(artifact: Dict[str, Any], console=None) -> None:
    """Render a ``frontier.json`` artifact (murmura_tpu/frontier.py): one
    summary table of empirical breaking point vs MUR800 declared bound
    per (rule x attack x topology) cell, then each cell's honest-accuracy
    curve over attack strength.

    The two columns to read together: ``declared`` is what the flow
    analyzer PROVES the rule can admit per coordinate (`murmura check
    --flow`, MUR800); ``broken at`` is where a closed-loop adversary
    actually pushed the rule off its honest-accuracy cliff.  A bounded
    rule breaking at low strength is a robustness gap the static bound
    cannot see; an unbounded rule holding to high strength is averaging
    luck, not a guarantee.
    """
    from rich.console import Console
    from rich.table import Table

    from murmura_tpu.frontier import frontier_break_summary

    console = console or Console()
    grid = artifact.get("grid") or {}
    console.print(
        f"[bold cyan]murmura frontier[/bold cyan] — "
        f"[bold]{artifact.get('experiment', '?')}[/bold] "
        f"(nodes={grid.get('num_nodes', '?')}, "
        f"rounds={grid.get('rounds', '?')}, seeds={grid.get('seeds', '?')}, "
        f"break < {grid.get('break_fraction', '?')} x benign)"
    )
    t = Table(title="Breaking point vs declared influence bound (per cell)")
    t.add_column("rule", style="cyan")
    t.add_column("attack")
    t.add_column("topology")
    t.add_column("pct", justify="right")
    t.add_column("deg", justify="right")
    t.add_column("benign acc", justify="right")
    t.add_column("held ≤", justify="right")
    t.add_column("broken at", justify="right")
    t.add_column("declared (MUR800)")
    t.add_column("compiles", justify="right")
    for row in frontier_break_summary(artifact):
        held = row["last_held"]
        broken = row["first_broken"]
        kind = row["declared_kind"]
        # Compact contract cell; the full InfluenceDecl.describe() text
        # stays in the artifact's declared_influence payload.
        declared = (
            "undeclared" if kind is None
            else f"bounded ≤ {row['declared_bound']}" if kind == "bounded"
            else str(kind)
        )
        pct = row.get("percentage")
        t.add_row(
            str(row["rule"]), str(row["attack"]), str(row["topology"]),
            "-" if pct is None else f"{pct:g}",
            str(row["degree"]), _fmt(row["benign_accuracy"], 3),
            "-" if held is None else f"{held:.3g}",
            "[bold red]never[/bold red]" if broken is None
            else f"[bold]{broken:.3g}[/bold]",
            declared,
            str(row["compiles"]),
        )
    console.print(t)
    for cell in artifact.get("cells", []):
        benign = cell.get("benign_accuracy") or float("nan")
        title = (
            f"{cell['rule']} x {cell['attack']} x {cell['topology']} — "
            f"honest accuracy vs strength (benign {_fmt(benign, 3)})"
        )
        ct = Table(title=title)
        ct.add_column("strength", justify="right")
        ct.add_column("mean acc", justify="right")
        ct.add_column("std", justify="right")
        ct.add_column("vs benign")
        ct.add_column("attacker state")
        for row in cell.get("curve", []):
            frac = (
                row["mean"] / benign
                if benign and math.isfinite(benign) and benign > 0
                else float("nan")
            )
            adaptive = row.get("adaptive") or {}
            summary = ""
            if adaptive:
                # Mean converged state over seeds: the attacker's own
                # account of the margin it found (atk_lo / atk_z).
                keys = sorted({k for d in adaptive.values() for k in d})
                show = [
                    k for k in ("atk_lo", "atk_z", "atk_accept_ema")
                    if k in keys
                ]
                summary = "  ".join(
                    f"{k}={_fmt(_mean([d.get(k, float('nan')) for d in adaptive.values()]), 2)}"
                    for k in show
                )
            ct.add_row(
                f"{row['strength']:.3g}", _fmt(row["mean"], 3),
                _fmt(row.get("std", float("nan")), 3), _bar(frac), summary,
            )
        console.print(ct)


def render_grid(artifact: Dict[str, Any], console=None) -> None:
    """Render a ``grid.json`` manifest (murmura_tpu/serve/scheduler.py):
    one bucket table (cells per compile-compatible bucket, its ONE
    compile, wall time), then the per-cell accuracy grid.

    The number to read first is ``total_compiles`` vs ``total_cells``:
    the scheduler's whole job is making the first much smaller than the
    second (the README 50-cell grid runs in 5 compiles).  A bucket whose
    ``compiles`` exceeds 1 means a cell smuggled a structural difference
    past the skeleton key — exactly what `murmura check --serve`
    (MUR1600/1601) exists to refuse.
    """
    from rich.console import Console
    from rich.table import Table

    console = console or Console()
    grid = artifact.get("grid") or {}
    console.print(
        f"[bold cyan]murmura grid[/bold cyan] — "
        f"[bold]{artifact.get('experiment', '?')}[/bold] "
        f"(nodes={grid.get('num_nodes', '?')}, "
        f"rounds={grid.get('rounds', '?')}, seeds={grid.get('seeds', '?')}): "
        f"[bold]{artifact.get('total_cells', '?')}[/bold] cells in "
        f"[bold]{len(artifact.get('buckets', []))}[/bold] buckets, "
        f"[bold]{artifact.get('total_compiles', '?')}[/bold] compiles"
    )
    bt = Table(title="Compile-compatible buckets (one gang = one compile)")
    bt.add_column("bucket", style="cyan")
    bt.add_column("rule")
    bt.add_column("attack")
    bt.add_column("topology")
    bt.add_column("cells", justify="right")
    bt.add_column("lanes", justify="right")
    bt.add_column("compiles", justify="right")
    bt.add_column("wall s", justify="right")
    for b in artifact.get("buckets", []):
        compiles = b.get("compiles")
        bt.add_row(
            str(b.get("key")), str(b.get("rule")), str(b.get("attack")),
            str(b.get("topology")), str(len(b.get("cells", []))),
            f"{b.get('gang_size', '?')}/{b.get('batch', '?')}",
            f"[bold red]{compiles}[/bold red]"
            if (compiles or 0) > 1 else str(compiles),
            _fmt(b.get("wall_s", float("nan")), 2),
        )
    console.print(bt)
    ct = Table(title="Cells (accuracy by rule x attack x strength x seed)")
    ct.add_column("cell", style="cyan")
    ct.add_column("bucket")
    ct.add_column("strength", justify="right")
    ct.add_column("seed", justify="right")
    ct.add_column("final acc", justify="right")
    ct.add_column("honest acc", justify="right")
    ct.add_column("mean round s", justify="right")
    for c in artifact.get("cells", []):
        phase = c.get("phase_times") or {}
        ct.add_row(
            str(c.get("id")), str(c.get("bucket")),
            f"{c.get('strength', float('nan')):g}", str(c.get("seed")),
            _fmt(c.get("final_accuracy"), 3),
            _fmt(c.get("honest_accuracy"), 3),
            _fmt(phase.get("mean_round_s", float("nan")), 3),
        )
    console.print(ct)
