#!/bin/bash
# Full TPU bench battery, run sequentially with per-step timeouts.
# Usage: ./run_tpu_battery.sh [outdir]  (default: chiprun_out/battery/ in
# the repo; bench_breakdown.json and bench_scaling.json are additionally
# rewritten at the repo root by their own scripts)
# Every bench is chip-or-fail: it asks jax.devices() once, in its own
# process, exits 2 unless that is a TPU, and stamps platform, device_kind
# and device count into its JSON.  The shell itself never touches JAX and
# runs the benches one after another, so each process has the chip to
# itself; the pre-flights are explicit CPU checks (JAX_PLATFORMS=cpu).
set -u
CHAOS=0
PROFILE=0
GANG=0
POPULATION=0
COMPRESS=0
RESUME=0
FRONTIER=0
STALE=0
PIPELINE=0
SHARDED=0
COMPOSE=0
MEMORY=0
SERVE=0
OBS=0
while :; do
  case "${1:-}" in
    --chaos) CHAOS=1; shift;;
    --profile) PROFILE=1; shift;;
    --gang) GANG=1; shift;;
    --population) POPULATION=1; shift;;
    --compress) COMPRESS=1; shift;;
    --resume) RESUME=1; shift;;
    --frontier) FRONTIER=1; shift;;
    --stale) STALE=1; shift;;
    --pipeline) PIPELINE=1; shift;;
    --sharded) SHARDED=1; shift;;
    --compose) COMPOSE=1; shift;;
    --memory) MEMORY=1; shift;;
    --serve) SERVE=1; shift;;
    --obs) OBS=1; shift;;
    *) break;;
  esac
done
cd "$(dirname "$0")"
OUT="${1:-$PWD/chiprun_out/battery}"
mkdir -p "$OUT"
# One persistent XLA compile cache for the whole battery, by the one rule
# of factories.apply_compilation_cache: JAX_COMPILATION_CACHE_DIR when the
# caller sets it, else .jax_cache/ in this checkout.
run() {
  local name=$1 tmo=$2; shift 2
  echo "=== $name ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  timeout "$tmo" "$@" > "$OUT/$name.out" 2>&1
  local rc=$?
  echo "$name rc=$rc" | tee -a "$OUT/battery.log"
  tail -1 "$OUT/$name.out" >> "$OUT/battery.log"
}
# Pre-flight gate: the static analyzer (docs/ANALYSIS.md) must be clean
# before any bench touches the chip — a traced-branch/host-sync/recompile
# hazard in the round path invalidates every number the battery produces.
# --ir adds the jaxpr/HLO contracts and the committed AOT cost budgets
# (MUR200-206): an undeclared collective or a >10% FLOPs drift in any
# aggregator aborts the battery before a single chip-second is spent.
# --flow adds the jaxpr dataflow contracts (MUR800-804): a leaked
# influence bound, a scrub-dominance break, or a zero-capable denominator
# in any rule/codec likewise aborts before the chip is touched.
# CPU-pinned so the gate itself cannot wedge the single-tenant TPU.
echo "=== preflight: murmura check --ir --flow ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
if ! timeout 600 env JAX_PLATFORMS=cpu python -m murmura_tpu check --ir --flow murmura_tpu/ \
    > "$OUT/preflight_check.out" 2>&1; then
  echo "preflight murmura check FAILED — aborting battery" | tee -a "$OUT/battery.log"
  cat "$OUT/preflight_check.out" | tee -a "$OUT/battery.log"
  exit 1
fi
echo "preflight check clean" | tee -a "$OUT/battery.log"
# Optional chaos pre-flight (./run_tpu_battery.sh --chaos [outdir]): the
# full operational-fault gauntlet — 20% Markov churn, link drops,
# stragglers, one NaN-injecting node, gaussian Byzantine noise — must
# complete end-to-end (docs/ROBUSTNESS.md) before the battery spends chip
# time: a regression in the fault masks or the NaN sentinel invalidates
# the robustness story every bench number rides on.  CPU-pinned like the
# static gate.
if [ "$CHAOS" = 1 ]; then
  echo "=== preflight: chaos smoke ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 900 env JAX_PLATFORMS=cpu python -m murmura_tpu run \
      examples/configs/chaos_churn.yaml --quiet \
      -o "$OUT/chaos_history.json" > "$OUT/preflight_chaos.out" 2>&1; then
    echo "preflight chaos smoke FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_chaos.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight chaos smoke clean" | tee -a "$OUT/battery.log"
fi
# Optional profiling pre-flight (./run_tpu_battery.sh --profile [outdir]):
# a tiny CPU-pinned run with the telemetry profiler window armed must
# produce a non-empty trace capture (docs/OBSERVABILITY.md) — if trace
# plumbing is broken, find out before a chip session depends on it.
if [ "$PROFILE" = 1 ]; then
  echo "=== preflight: telemetry profile capture ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  PROF_RUN="$OUT/profile_preflight"
  rm -rf "$PROF_RUN"
  if ! timeout 600 env JAX_PLATFORMS=cpu MURMURA_TELEMETRY_DIR="$PROF_RUN" python - > "$OUT/preflight_profile.out" 2>&1 <<'PYEOF'
import os, sys
from pathlib import Path
import yaml
cfg = yaml.safe_load(Path("examples/configs/telemetry_audit_report.yaml").read_text())
cfg["experiment"]["rounds"] = 3
cfg["telemetry"]["dir"] = os.environ["MURMURA_TELEMETRY_DIR"]
cfg["telemetry"]["profile_rounds"] = 2
cfg["telemetry"]["profile_start_round"] = 1
tmp = Path(os.environ["MURMURA_TELEMETRY_DIR"] + ".yaml")
tmp.parent.mkdir(parents=True, exist_ok=True)
tmp.write_text(yaml.safe_dump(cfg))
from click.testing import CliRunner
from murmura_tpu.cli import app
r = CliRunner().invoke(app, ["run", str(tmp), "--quiet"])
print(r.output)
if r.exit_code:
    sys.exit(r.exit_code)
run_dir = Path(os.environ["MURMURA_TELEMETRY_DIR"])
trace = run_dir / "trace"
captured = list(trace.rglob("*")) if trace.is_dir() else []
if not any(p.is_file() and p.stat().st_size > 0 for p in captured):
    print(f"no non-empty trace files under {trace}")
    sys.exit(1)
import json
events = [json.loads(l) for l in (run_dir / "events.jsonl").read_text().splitlines()]
prof = [e for e in events if e.get("type") == "profile"]
if not any(e.get("status") == "started" for e in prof) or not any(
    e.get("status") == "stopped" for e in prof
):
    print(f"profile window events incomplete: {prof}")
    sys.exit(1)
print(f"trace capture ok: {sum(1 for p in captured if p.is_file())} file(s)")
PYEOF
  then
    echo "preflight profile capture FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_profile.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight profile capture clean" | tee -a "$OUT/battery.log"
fi
# Optional gang pre-flight (./run_tpu_battery.sh --gang [outdir]): a
# CPU-pinned 2-seed gang (docs/PERFORMANCE.md) must (a) byte-match both
# members' single-run histories and (b) compile exactly one program for
# the whole gang — if gang batching breaks parity or the compile
# amortization, the gang bench numbers below are meaningless.
if [ "$GANG" = 1 ]; then
  echo "=== preflight: gang parity + single compile ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 600 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_gang.out" 2>&1 <<'PYEOF'
import sys
import yaml
from pathlib import Path
from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_gang_from_config, build_network_from_config
from murmura_tpu.analysis.sanitizers import track_compiles

raw = yaml.safe_load(Path("examples/configs/sweep_seeds.yaml").read_text())
raw["experiment"]["rounds"] = 4
base_seed = raw["experiment"]["seed"]
seeds = [base_seed, base_seed + 1]

gang = build_gang_from_config(Config.model_validate(raw), seeds=seeds)
with track_compiles() as tracker:
    histories = gang.train(rounds=4, eval_every=2, rounds_per_dispatch=4)
    gang_compiles = tracker.total
# The fused gang program (train + in-scan eval) must be the gang's ONE
# compile — S members share it.
if gang_compiles != 1:
    print(f"gang train compiled {gang_compiles} program(s), expected exactly 1")
    sys.exit(1)
for i, seed in enumerate(seeds):
    sraw = yaml.safe_load(Path("examples/configs/sweep_seeds.yaml").read_text())
    sraw["experiment"]["rounds"] = 4
    sraw["experiment"]["seed"] = seed
    sraw.pop("sweep", None)
    single = build_network_from_config(Config.model_validate(sraw)).train(
        rounds=4, eval_every=2, rounds_per_dispatch=4
    )
    mismatched = [
        k for k in single
        if single[k] and histories[i].get(k) != single[k]
    ]
    if mismatched:
        print(f"gang member seed={seed} diverged from its single run in {mismatched}")
        print("gang:", {k: histories[i].get(k) for k in mismatched})
        print("single:", {k: single[k] for k in mismatched})
        sys.exit(1)
print(f"gang parity ok for seeds {seeds}; whole gang compiled once")
PYEOF
  then
    echo "preflight gang FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_gang.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight gang clean" | tee -a "$OUT/battery.log"
fi
# Optional frontier pre-flight (./run_tpu_battery.sh --frontier [outdir]):
# a CPU-pinned 2-strength x 2-seed mini-frontier on krum
# (docs/ROBUSTNESS.md "The robustness frontier") must (a) cost exactly
# ONE compile for the whole bucket across both successive-halving stages
# under tpu.recompile_guard — the reset_run re-aim is value-only over the
# warm executables — and (b) produce a monotone (non-increasing)
# accuracy-vs-strength curve; if either breaks, a full frontier sweep
# would burn its budget recompiling or chart noise.
if [ "${FRONTIER:-0}" = 1 ]; then
  echo "=== preflight: frontier mini-sweep (1 compile/bucket + monotone curve) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 900 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_frontier.out" 2>&1 <<'PYEOF'
import sys

from murmura_tpu.config import Config
from murmura_tpu.frontier import run_frontier

raw = {
    "experiment": {"name": "frontier-preflight", "seed": 7, "rounds": 2,
                   "verbose": False},
    "topology": {"type": "ring", "num_nodes": 5},
    "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
    "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
    "data": {"adapter": "synthetic",
             "params": {"num_samples": 40, "input_shape": [6],
                        "num_classes": 3}},
    "model": {"factory": "mlp",
              "params": {"input_dim": 6, "hidden_dims": [8],
                         "num_classes": 3}},
    "backend": "simulation",
    # recompile_guard arms CompileTracker inside the gang: any compile
    # after the bucket's warmup raises instead of silently re-lowering.
    "tpu": {"recompile_guard": True, "num_devices": 1,
            "compute_dtype": "float32"},
    "frontier": {"rules": ["krum"], "attacks": ["gaussian"],
                 "topologies": ["dense"], "points": 2, "stages": 2,
                 "seeds": [7, 11], "rounds": 2,
                 "strength_lo": 0.5, "strength_hi": 4.0},
}
artifact = run_frontier(Config.model_validate(raw))
(cell,) = artifact["cells"]
print(f"compiles={cell['compiles']} stages={cell['stages']}")
if cell["compiles"] != 1:
    print(f"FAIL: bucket cost {cell['compiles']} compiles, expected "
          "exactly 1 (the successive-halving stages must reuse the warm "
          "gang executables)")
    sys.exit(1)
curve = cell["curve"]
for row in curve:
    print(f"  strength {row['strength']:.3g}: mean {row['mean']:.4f}")
benign = curve[0]["mean"]
for row in curve[1:]:
    if row["mean"] > benign + 0.05:
        print(f"FAIL: accuracy at strength {row['strength']:.3g} "
              f"({row['mean']:.4f}) exceeds benign ({benign:.4f}) — the "
              "curve is not monotone non-increasing")
        sys.exit(1)
means = [row["mean"] for row in curve]
for a, b in zip(means, means[1:]):
    if b > a + 0.05:
        print("FAIL: accuracy-vs-strength curve is not monotone "
              f"non-increasing: {means}")
        sys.exit(1)
print("frontier preflight ok")
PYEOF
  then
    echo "preflight frontier FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_frontier.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight frontier clean" | tee -a "$OUT/battery.log"
fi
# Optional staleness pre-flight (./run_tpu_battery.sh --stale [outdir]):
# the ISSUE-13 gates — a krum run under a 30% straggler + 30% link-drop
# schedule on non-IID shards with bounded staleness armed must (a) run
# with ZERO post-warmup recompiles under tpu.recompile_guard (the cache
# and ages are carried state, the fault masks input values — MUR1101),
# (b) actually serve stale edges (a dead stale layer would pass every
# accuracy bar vacuously), and (c) recover at least HALF the accuracy
# gap between the fault-free and drop-sync-faulted baselines — the
# acceptance bar of docs/ROBUSTNESS.md "Bounded staleness".  CPU-pinned
# like the static gate.
if [ "${STALE:-0}" = 1 ]; then
  echo "=== preflight: bounded-staleness recovery (stale-on vs stale-off vs fault-free) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 900 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_stale.out" 2>&1 <<'PYEOF'
import sys

import numpy as np

from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config

ROUNDS = 12


def run(faults=None, exchange=None):
    raw = {
        "experiment": {"name": "stale-preflight", "seed": 3,
                       "rounds": ROUNDS},
        "topology": {"type": "k-regular", "num_nodes": 8, "k": 4},
        "aggregation": {"algorithm": "krum"},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 240, "input_dim": 16,
                            "num_classes": 8,
                            "partition_method": "dirichlet",
                            "alpha": 0.3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 16, "hidden_dims": [16],
                             "num_classes": 8}},
        "backend": "simulation",
        # recompile_guard arms CompileTracker inside the round loop: any
        # compile after warmup raises instead of silently re-lowering.
        "tpu": {"recompile_guard": True, "num_devices": 1,
                "compute_dtype": "float32"},
    }
    if faults:
        raw["faults"] = faults
    if exchange:
        raw["exchange"] = exchange
    h = build_network_from_config(Config.model_validate(raw)).train(
        rounds=ROUNDS
    )
    return h, float(np.mean(h["mean_accuracy"][-2:]))


FAULTS = {"enabled": True, "straggler_prob": 0.3, "link_drop_prob": 0.3,
          "seed": 11}
_, acc_clean = run()
_, acc_drop = run(faults=FAULTS)
h_stale, acc_stale = run(faults=FAULTS, exchange={"max_staleness": 2})
gap = acc_clean - acc_drop
recovered = acc_stale - acc_drop
print(f"clean={acc_clean:.4f} drop-sync={acc_drop:.4f} "
      f"stale={acc_stale:.4f} gap={gap:.4f} recovered={recovered:.4f}")
served = sum(h_stale.get("agg_stale_used", []))
print(f"stale edge-serves: {served}")
if served <= 0:
    print("FAIL: the stale layer served zero edges under a 30% "
          "straggler/link-drop schedule — the accuracy comparison is "
          "vacuous")
    sys.exit(1)
if gap > 0.01 and recovered < 0.5 * gap:
    print(f"FAIL: staleness recovered {recovered:.4f} of a {gap:.4f} "
          "accuracy gap — the acceptance bar is >= half")
    sys.exit(1)
print("stale preflight ok (zero post-warmup recompiles by guard)")
PYEOF
  then
    echo "preflight stale FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_stale.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight stale clean" | tee -a "$OUT/battery.log"
fi
# Optional pipelined-rounds pre-flight (./run_tpu_battery.sh --pipeline
# [outdir]): the ISSUE-14 gates — (a) a pipelined krum run under a
# straggler/link-drop schedule must be BIT-IDENTICAL to the explicit
# one-round-delayed averaging reference (core/pipeline.
# run_delayed_reference drives the serialized program through the
# delayed recursion) on CPU, with ZERO post-warmup recompiles under
# tpu.recompile_guard (the double buffer is carried state — MUR1201) and
# a buffer that actually reports valid (a dead pipeline would pass the
# parity vacuously); then (b) when a TPU is attached, the
# bench_breakdown pipeline cell must show the exchange+aggregate segment
# >= 80% hidden behind local training — the docs/PERFORMANCE.md
# acceptance bar (skipped with a loud note on CPU-only hosts: XLA CPU
# schedules the concurrent stages sequentially).
if [ "${PIPELINE:-0}" = 1 ]; then
  echo "=== preflight: pipelined rounds (delayed-averaging bit-parity, CPU) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 900 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_pipeline.out" 2>&1 <<'PYEOF'
import sys

import jax
import numpy as np

from murmura_tpu.config import Config
from murmura_tpu.core.pipeline import run_delayed_reference
from murmura_tpu.utils.factories import build_network_from_config

ROUNDS = 12


def raw(pipeline):
    r = {
        "experiment": {"name": "pipe-preflight", "seed": 3,
                       "rounds": ROUNDS},
        "topology": {"type": "k-regular", "num_nodes": 8, "k": 4},
        "aggregation": {"algorithm": "krum"},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 240, "input_dim": 16,
                            "num_classes": 8}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 16, "hidden_dims": [16],
                             "num_classes": 8}},
        "backend": "simulation",
        "faults": {"enabled": True, "straggler_prob": 0.3,
                   "link_drop_prob": 0.2, "seed": 11},
        # recompile_guard arms CompileTracker inside the round loop: any
        # compile after warmup raises instead of silently re-lowering.
        "tpu": {"recompile_guard": True, "num_devices": 1,
                "compute_dtype": "float32"},
    }
    if pipeline:
        r["exchange"] = {"pipeline": True}
    return Config.model_validate(r)


net = build_network_from_config(raw(pipeline=True))
h = net.train(rounds=ROUNDS)
valid = sum(h.get("agg_pipe_valid", []))
print(f"pipelined run: final acc {h['mean_accuracy'][-1]:.4f}, "
      f"valid-buffer rounds {valid:.0f}")
if valid <= 0:
    print("FAIL: agg_pipe_valid never reported a valid buffer — the "
          "pipeline stage is dead and the parity below is vacuous")
    sys.exit(1)
ref_net = build_network_from_config(raw(pipeline=False))
ref_params, ref_hist = run_delayed_reference(ref_net, rounds=ROUNDS)
pl = [np.asarray(x) for x in jax.tree_util.tree_leaves(net.params)]
rl = [np.asarray(x) for x in jax.tree_util.tree_leaves(ref_params)]
if not all(np.array_equal(a, b, equal_nan=True) for a, b in zip(pl, rl)):
    print("FAIL: pipelined params diverge byte-wise from the "
          "one-round-delayed averaging reference")
    sys.exit(1)
if h["mean_accuracy"] != ref_hist["mean_accuracy"]:
    print("FAIL: pipelined accuracy history diverges from the reference")
    sys.exit(1)
print("pipeline preflight ok: bit-identical to the delayed-averaging "
      "reference, zero post-warmup recompiles by guard")
PYEOF
  then
    echo "preflight pipeline FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_pipeline.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight pipeline (CPU bit-parity) clean" | tee -a "$OUT/battery.log"
  echo "=== preflight: pipelined rounds (TPU hidden-fraction) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 1800 python - > "$OUT/preflight_pipeline_tpu.out" 2>&1 <<'PYEOF'
import sys

import jax

if jax.default_backend() != "tpu":
    # The overlap measurement needs the chip: XLA CPU schedules the two
    # independent stages sequentially, so hidden_fraction ~ 0 there by
    # construction.  Not a failure — the CPU half above carried the
    # correctness gate — but say so loudly in the log.
    print(f"SKIP: default backend is {jax.default_backend()}, not tpu — "
          "the >= 80%-hidden acceptance bar only measures on the chip")
    sys.exit(0)

import bench_breakdown

cells = bench_breakdown._pipeline_cells(20)["cells"]
cell = cells["dense/codec_none"]
hf = cell.get("hidden_fraction")
print(f"dense/codec_none: serialized {cell['serialized_ms']} ms, "
      f"pipelined {cell['pipelined_ms']} ms, hidden_fraction {hf}")
if hf is None or hf < 0.8:
    print("FAIL: the exchange+aggregate segment is not >= 80% hidden "
          "behind local training on the chip (docs/PERFORMANCE.md "
          "acceptance bar); inspect the profiler trace — the delayed "
          "aggregation's collectives should overlap murmura.train")
    sys.exit(1)
print("pipeline preflight ok: exchange+aggregate >= 80% hidden on TPU")
PYEOF
  then
    echo "preflight pipeline (TPU hidden-fraction) FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_pipeline_tpu.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  tail -1 "$OUT/preflight_pipeline_tpu.out" | tee -a "$OUT/battery.log"
fi
# Optional param-axis sharding pre-flight (./run_tpu_battery.sh --sharded
# [outdir]): the ISSUE-15 gates on a forced 8-virtual-device CPU mesh —
# (a) the MUR1300-1303 family must be clean (sharded-P collective
# inventory ppermute-only on "nodes" plus one small psum over "param";
# zero recompiles across sharded rounds; shards=1 BIT-parity with the
# unsharded program; sharded execution parity to reassociation
# tolerance), and (b) an end-to-end param-sharded run must hold under
# tpu.recompile_guard with a stale cache + int8 EF residual riding the
# sharded state.  After the gate, the bench_scaling --sharded cells
# (including the >= 50M-param-per-node acceptance point) record the
# per-device resident-params numbers into bench_scaling_sharded.json.
if [ "$SHARDED" = 1 ]; then
  echo "=== preflight: param-axis sharding (MUR1300-1303 + guarded run, CPU) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 1200 env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python - > "$OUT/preflight_sharded.out" 2>&1 <<'PYEOF'
import sys

from murmura_tpu.analysis.sharded import check_sharded

findings = check_sharded()
for f in findings:
    print(f"{f.path}:{f.line}: {f.rule} {f.message}")
if findings:
    print(f"FAIL: {len(findings)} MUR130x finding(s)")
    sys.exit(1)
print("MUR1300-1303 clean")

from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config

cfg = Config.model_validate({
    "experiment": {"name": "sharded-preflight", "seed": 3, "rounds": 6},
    "topology": {"type": "ring", "num_nodes": 8},
    "aggregation": {"algorithm": "krum",
                    "params": {"num_compromised": 1}},
    "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
    "data": {"adapter": "synthetic",
             "params": {"num_samples": 64, "input_shape": [16],
                        "num_classes": 4}},
    "model": {"factory": "mlp",
              "params": {"input_dim": 16, "hidden_dims": [36],
                         "num_classes": 4}},
    "backend": "tpu",
    "faults": {"enabled": True, "straggler_prob": 0.3,
               "link_drop_prob": 0.2, "seed": 11},
    "exchange": {"max_staleness": 2, "staleness_discount": 0.5},
    "compression": {"algorithm": "int8", "block": 8,
                    "error_feedback": True},
    "tpu": {"param_shards": 4, "param_dtype": "float32",
            "compute_dtype": "float32", "recompile_guard": True},
})
net = build_network_from_config(cfg)
h = net.train(rounds=6)
print(f"guarded sharded run ok: mesh {dict(net.mesh.shape)}, "
      f"flat_dim {net.program.flat_dim}, "
      f"final acc {h['mean_accuracy'][-1]:.4f}")
PYEOF
  then
    echo "preflight sharded FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_sharded.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight sharded clean" | tee -a "$OUT/battery.log"
  run bench_scaling_sharded 7200 python bench_scaling.py --sharded
fi
# Optional composition-grid pre-flight (./run_tpu_battery.sh --compose
# [outdir]): the ISSUE-16 gates on a forced 8-virtual-device CPU mesh —
# (a) the MUR1400-1403 family must be clean (lever-manifest/guard
# bijection with the executable refusal census; every
# declared-compatible pair's composed round program recompile-free with
# collective-inventory parity; composed-state/stage-order parity;
# flow-taint preservation through the composed compress+stale and
# sparse+stale cells), and (b) the lifted sharding x sweep cell — a
# gang sweep on the 3-axis ("seed", "nodes", "param") mesh — must hold
# end-to-end under tpu.recompile_guard.
if [ "$COMPOSE" = 1 ]; then
  echo "=== preflight: composition grid (MUR1400-1403 + lifted sharded sweep, CPU) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 1200 env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python - > "$OUT/preflight_compose.out" 2>&1 <<'PYEOF'
import sys

from murmura_tpu.analysis.composition import check_composition

findings = check_composition()
for f in findings:
    print(f"{f.path}:{f.line}: {f.rule} {f.message}")
if findings:
    print(f"FAIL: {len(findings)} MUR140x finding(s)")
    sys.exit(1)
print("MUR1400-1403 clean")

from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_gang_from_config

cfg = Config.model_validate({
    "experiment": {"name": "compose-preflight", "seed": 3, "rounds": 6},
    "topology": {"type": "ring", "num_nodes": 8},
    "aggregation": {"algorithm": "krum",
                    "params": {"num_compromised": 1}},
    "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
    "data": {"adapter": "synthetic",
             "params": {"num_samples": 64, "input_shape": [16],
                        "num_classes": 4}},
    "model": {"factory": "mlp",
              "params": {"input_dim": 16, "hidden_dims": [36],
                         "num_classes": 4}},
    "backend": "tpu",
    "sweep": {"num_seeds": 2},
    "tpu": {"param_shards": 2, "param_dtype": "float32",
            "compute_dtype": "float32", "recompile_guard": True},
})
gang = build_gang_from_config(cfg)
assert tuple(gang.mesh.axis_names) == ("seed", "nodes", "param"), \
    gang.mesh.axis_names
gang.train(rounds=6, verbose=False)
finals = [h["mean_loss"][-1] for h in gang.histories]
assert all(l == l for l in finals), finals  # finite
print(f"guarded lifted sweep ok: mesh {dict(gang.mesh.shape)}, "
      f"final losses {[round(float(l), 4) for l in finals]}")
PYEOF
  then
    echo "preflight compose FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_compose.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight compose clean" | tee -a "$OUT/battery.log"
fi
# Optional memory-contract pre-flight (./run_tpu_battery.sh --memory
# [outdir]): the ISSUE-17 gates on a forced 8-virtual-device CPU mesh —
# the MUR1500-1503 family must be clean end to end: the committed
# memory_analysis() budget grid (analysis/MEMORY.json) over every
# (rule x topology x feature) cell, the sharded per-device-peak scaling
# law across shards {1, 2, 4} (needs the 8-device mesh, hence the forced
# host platform count), donation completeness per carried leaf, and the
# pipelined overlap-dependence proof (buffered aggregation independent
# of the round's training subgraph, with its serialized positive
# control).  A budget drift, an unaliased carry, or a dependence edge
# from train into the pipelined combine aborts the battery before a
# chip-second is spent — the residency numbers the battery records would
# be measuring a different program than the one the budgets describe.
if [ "$MEMORY" = 1 ]; then
  echo "=== preflight: memory contracts (MUR1500-1503, CPU) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 1800 env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python - > "$OUT/preflight_memory.out" 2>&1 <<'PYEOF'
import sys

from murmura_tpu.analysis.memory import (
    check_memory,
    overlap_cell_findings,
    scaling_cell_findings,
)

# The full family: MUR1500 budget grid, MUR1501 scaling law (live on the
# forced 8-device mesh), MUR1502 donation walk, MUR1503 dependence proof
# incl. the doctored-combine negative control.
findings = check_memory()
for f in findings:
    print(f"{f.path}:{f.line}: {f.rule} {f.message}")
if findings:
    print(f"FAIL: {len(findings)} MUR150x finding(s)")
    sys.exit(1)
print("MUR1500-1503 clean")

# Belt-and-braces: re-run one sharded scaling cell and the pipelined
# dependence cell directly so the preflight log names them even if the
# family-level memoization ever changes what the default gate covers.
extra = list(scaling_cell_findings("krum", "circulant"))
extra += list(overlap_cell_findings("fedavg", "dense"))
for f in extra:
    print(f"{f.path}:{f.line}: {f.rule} {f.message}")
if extra:
    print(f"FAIL: {len(extra)} finding(s) in the named cells")
    sys.exit(1)
print("scaling cell (krum/circulant, shards 1-2-4) + "
      "overlap cell (fedavg/dense) clean")
PYEOF
  then
    echo "preflight memory FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_memory.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight memory clean" | tee -a "$OUT/battery.log"
fi
# Optional serving pre-flight (./run_tpu_battery.sh --serve [outdir]):
# the ISSUE-18 gates, CPU-pinned — (a) the committed serve_grid.yaml grid
# through the compile-compatible scheduler under tpu.recompile_guard must
# cover >= 40 cells in <= 5 compiles, asserted from the grid.json
# manifest's CompileTracker counts (docs/ROBUSTNESS.md "Serving"), and
# (b) a daemon mini-soak with a REAL process death: a subprocess daemon
# takes concurrent socket submissions, is SIGKILLed mid-generation (no
# atexit, no finalization), a second subprocess rebinds over the stale
# socket file (the EADDRINUSE transient path), recovers from the durable
# ledger + cadence snapshots, and every submission must finish with a
# history byte-identical to an uninterrupted in-process reference daemon
# (MUR1603 end-to-end, with the kill landing wherever the scheduler
# happened to be).
if [ "$SERVE" = 1 ]; then
  echo "=== preflight: serving (grid <=5 compiles + daemon kill-mid-soak) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  SERVE_DIR="$OUT/serve_preflight"
  rm -rf "$SERVE_DIR"
  if ! timeout 2400 env JAX_PLATFORMS=cpu MURMURA_SERVE_DIR="$SERVE_DIR" python - > "$OUT/preflight_serve.out" 2>&1 <<'PYEOF'
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import yaml

from murmura_tpu.analysis.durability import history_equal
from murmura_tpu.config import Config
from murmura_tpu.serve.daemon import TERMINAL_STATES, ServeDaemon
from murmura_tpu.serve.protocol import send_request
from murmura_tpu.serve.scheduler import run_grid, write_grid

serve_dir = Path(os.environ["MURMURA_SERVE_DIR"])
serve_dir.mkdir(parents=True, exist_ok=True)

# -- (a) the committed grid: >= 40 cells in <= 5 compiles ----------------
raw = yaml.safe_load(Path("examples/configs/serve_grid.yaml").read_text())
# recompile_guard arms CompileTracker inside each bucket's gang: a
# compile after a bucket's fused warmup raises instead of silently
# re-lowering — the manifest's per-bucket counts stay honest.
raw["tpu"] = dict(raw.get("tpu") or {}, recompile_guard=True)
art = run_grid(Config.model_validate(raw), progress=print)
write_grid(art, serve_dir / "grid.json")
print(f"grid: {art['total_cells']} cells, {art['total_compiles']} compiles")
if art["total_cells"] < 40 or art["total_compiles"] > 5:
    print(f"FAIL: grid gate is >= 40 cells in <= 5 compiles, got "
          f"{art['total_cells']} cells / {art['total_compiles']} compiles")
    sys.exit(1)

# -- (b) daemon mini-soak: SIGKILL mid-generation, byte-identical finish -
ROUNDS = 4
SEEDS = (5, 6, 7)


def tenant(seed):
    return {
        "experiment": {"name": f"soak-{seed}", "seed": seed,
                       "rounds": ROUNDS},
        "topology": {"type": "ring", "num_nodes": 5},
        "aggregation": {"algorithm": "krum",
                        "params": {"num_compromised": 1}},
        "training": {"local_epochs": 1, "batch_size": 8, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 40, "input_shape": [6],
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 6, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "simulation",
        "tpu": {"recompile_guard": True, "num_devices": 1,
                "compute_dtype": "float32"},
    }


def daemon_raw(state_dir):
    r = tenant(0)
    r["serve"] = {"state_dir": str(state_dir), "capacity": 2,
                  "checkpoint_every": 1, "poll_interval_s": 0.05}
    return r


# Uninterrupted in-process reference: the byte-identity baseline.
ref = ServeDaemon(Config.model_validate(daemon_raw(serve_dir / "ref")))
for seed in SEEDS:
    ref.submit_config(tenant(seed))
ref.drain()
ref_hist = {}
for rec in ref._ledger.values():
    if rec["state"] != "done":
        print(f"FAIL: reference daemon left {rec['id']} {rec['state']}")
        sys.exit(1)
    ref_hist[rec["config"]["experiment"]["seed"]] = rec["history"]

victim_dir = serve_dir / "victim"
cfg_path = serve_dir / "victim_daemon.json"
cfg_path.write_text(json.dumps(daemon_raw(victim_dir)))
daemon_main = r"""
import json, sys
from pathlib import Path
from murmura_tpu.config import Config
from murmura_tpu.serve.daemon import ServeDaemon
ServeDaemon(
    Config.model_validate(json.loads(Path(sys.argv[1]).read_text()))
).serve_forever()
"""
env = {**os.environ, "JAX_PLATFORMS": "cpu"}


def spawn():
    return subprocess.Popen(
        [sys.executable, "-c", daemon_main, str(cfg_path)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )


def status(sock, sub_id):
    return send_request(sock, {"op": "status", "id": sub_id})["submission"]


def await_daemon(sock, timeout_s=180):
    # A cold subprocess pays the full jax import before binding; poll the
    # ping op (send_request's own retry envelope covers the connect races
    # once the file exists).
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            if send_request(sock, {"op": "ping"}, retries=2)["ok"]:
                return
        except (ConnectionError, TimeoutError, OSError):
            time.sleep(0.5)
    print(f"FAIL: daemon never answered ping at {sock}")
    sys.exit(1)


proc = spawn()
sock = str(victim_dir / "daemon.sock")
await_daemon(sock)
ids = [
    send_request(sock, {"op": "submit", "config": tenant(seed)})["id"]
    for seed in SEEDS
]
print(f"submitted {ids} to daemon pid {proc.pid}")
deadline = time.monotonic() + 300
while time.monotonic() < deadline:
    if any(status(sock, i)["state"] == "running" for i in ids):
        break
    time.sleep(0.05)
else:
    print("FAIL: no submission reached 'running' before the kill window")
    sys.exit(1)
os.kill(proc.pid, signal.SIGKILL)
proc.wait()
if proc.returncode != -signal.SIGKILL:
    print(f"FAIL: daemon did not die by SIGKILL (rc={proc.returncode})")
    sys.exit(1)
print("daemon SIGKILLed mid-generation; restarting over the same "
      "state_dir (stale socket file still on disk)")

proc2 = spawn()
await_daemon(sock)
deadline = time.monotonic() + 600
states = {}
while time.monotonic() < deadline:
    states = {i: status(sock, i)["state"] for i in ids}
    if all(s in TERMINAL_STATES for s in states.values()):
        break
    time.sleep(0.2)
send_request(sock, {"op": "shutdown"})
proc2.wait(timeout=60)
if not all(s == "done" for s in states.values()):
    print(f"FAIL: not every submission finished 'done' after recovery: "
          f"{states}")
    sys.exit(1)

for sub_id in ids:
    rec = json.loads(
        (victim_dir / "submissions" / f"{sub_id}.json").read_text()
    )
    seed = rec["config"]["experiment"]["seed"]
    if not history_equal(rec["history"], ref_hist[seed]):
        print(f"FAIL: {sub_id} (seed {seed}) resumed history diverges "
              "from the uninterrupted reference daemon's")
        sys.exit(1)
print(f"serve preflight ok: {art['total_cells']} cells / "
      f"{art['total_compiles']} compiles; kill-mid-soak recovered "
      f"{len(ids)} submissions byte-identical")
PYEOF
  then
    echo "preflight serve FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_serve.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight serve clean" | tee -a "$OUT/battery.log"
fi
# Optional observability pre-flight (./run_tpu_battery.sh --obs [outdir]):
# the ISSUE-19 gates, CPU-pinned — a live mini-daemon runs a warm second
# generation while a polling thread hammers the read-only metrics/ping/
# list ops mid-soak; the scrape must cause ZERO recompiles
# (CompileTracker), every tenant history must stay byte-identical to an
# unscraped reference daemon (MUR1701), and the final scrape must agree
# with an independent replay of the durable ledger + event streams
# (MUR1700 parity).  Spans built from a drained tenant must validate and
# reconcile with phase_times (MUR1702).
if [ "$OBS" = 1 ]; then
  echo "=== preflight: observability (mid-soak scrape: zero recompiles + ledger parity) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 600 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_obs.out" 2>&1 <<'PYEOF'
import sys
import tempfile
import threading
from pathlib import Path

from murmura_tpu.analysis.observe import (
    interference_problems,
    metrics_ledger_parity,
)
from murmura_tpu.analysis.sanitizers import track_compiles
from murmura_tpu.analysis.serve import _tenant_raw
from murmura_tpu.config import Config
from murmura_tpu.serve.daemon import ServeDaemon
from murmura_tpu.telemetry.spans import build_spans, validate_spans
from murmura_tpu.telemetry.writer import events_of_type

tmp = Path(tempfile.mkdtemp(prefix="murmura-obs-preflight-"))

def daemon(state):
    cfg = Config.model_validate({
        **_tenant_raw(seed=0, rounds=3),
        "serve": {"state_dir": str(state), "capacity": 2,
                  "checkpoint_every": 1},
    })
    return ServeDaemon(cfg)

def soak(state, scrape):
    d = daemon(state)
    d.submit_config(_tenant_raw(seed=5))
    d.submit_config(_tenant_raw(seed=6))
    d.drain()  # generation 1 warms the bucket
    gen2 = [d.submit_config(_tenant_raw(seed=7))["id"],
            d.submit_config(_tenant_raw(seed=8))["id"]]
    stop = threading.Event()
    def poll():
        while not stop.is_set():
            d.handle_request({"op": "metrics"})
            d.handle_request({"op": "ping"})
            d.handle_request({"op": "list"})
    poller = threading.Thread(target=poll, daemon=True)
    if scrape:
        poller.start()
    try:
        with track_compiles() as tracker:
            d.drain()  # generation 2: the mid-soak scrape target
    finally:
        stop.set()
        if scrape:
            poller.join(timeout=10.0)
    return d, gen2, tracker.total

ref, ref_ids, _ = soak(tmp / "ref", scrape=False)
scr, scr_ids, compiles = soak(tmp / "scraped", scrape=True)

pairs = [
    (i, scr._ledger[i].get("history"), ref._ledger[j].get("history"))
    for i, j in zip(scr_ids, ref_ids)
]
problems = interference_problems(compiles, pairs)
problems += metrics_ledger_parity(scr)
for sub_id in scr_ids:
    run_dir = scr.state_dir / "telemetry" / sub_id
    total = sum(float(e.get("wall_s", 0.0))
                for e in events_of_type(run_dir, "phase_times"))
    problems += [
        f"{sub_id}: {p}"
        for p in validate_spans(build_spans(run_dir), phase_total=total)
    ]
if problems:
    print("preflight obs FAILED:")
    for p in problems:
        print(" -", p)
    sys.exit(1)
print(f"preflight obs ok: 0 compiles under scrape, parity clean, "
      f"{len(scr_ids)} tenants span-validated")
PYEOF
  then
    echo "preflight obs FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_obs.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight obs clean" | tee -a "$OUT/battery.log"
fi
# Optional population pre-flight (./run_tpu_battery.sh --population
# [outdir]): the ISSUE-6 engine gates — (a) a 4096-node exponential-graph
# round program must run AND lower with no O(N^2) value (the MUR600
# contract at full acceptance scale), and (b) a virtual_size=100k
# cohort-streaming run must swap cohorts 3 times with ZERO post-warmup
# recompiles (CompileTracker via tpu.recompile_guard) and seed-
# deterministic draws.  CPU-pinned like the other gates.
if [ "$POPULATION" = 1 ]; then
  echo "=== preflight: population (4096-node sparse + 100k cohort swap) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 1200 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_population.out" 2>&1 <<'PYEOF'
import sys
import numpy as np
import jax
from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config

def raw(**over):
    r = {
        "experiment": {"name": "pop-preflight", "seed": 11, "rounds": 3},
        "topology": {"type": "exponential", "num_nodes": 4096},
        "aggregation": {"algorithm": "fedavg", "params": {}},
        "training": {"local_epochs": 1, "batch_size": 2, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 4096 * 2, "input_dim": 10,
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 10, "hidden_dims": [8],
                             "num_classes": 3}},
        "backend": "simulation",
    }
    r.update(over)
    return r

# -- (a) 4096-node exponential smoke + no-[N,N] lowering proof ----------
net = build_network_from_config(Config.model_validate(raw()))
n = net.program.num_nodes
adj = net._adjacency_for_round(0)
assert adj.shape == (len(net.topology.offsets), n), adj.shape
import jax.numpy as jnp
args = [net.params, net.agg_state, jax.random.PRNGKey(0),
        jnp.asarray(adj), jnp.asarray(net.compromised),
        jnp.asarray(0.0, jnp.float32), net._data]
jaxpr = jax.make_jaxpr(net.program.train_step)(*args)
def eqns(jx):
    jx = getattr(jx, "jaxpr", jx)
    for e in jx.eqns:
        yield e
        for sub in e.params.values():
            for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                if hasattr(s, "jaxpr") or hasattr(s, "eqns"):
                    yield from eqns(s)
dense = set()
for e in eqns(jaxpr):
    for v in list(e.invars) + list(e.outvars):
        shape = tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())
        if sum(1 for d in shape if d == n) >= 2:
            dense.add((e.primitive.name, shape))
if dense:
    print(f"4096-node sparse program traces O(N^2) values: {sorted(dense)[:5]}")
    sys.exit(1)
hist = net.train(rounds=2, eval_every=2)
if not np.isfinite(hist["mean_loss"]).all():
    print("4096-node sparse run produced non-finite loss")
    sys.exit(1)
print(f"4096-node exponential smoke ok: degree={len(net.topology.offsets)}, "
      f"acc={hist['mean_accuracy'][-1]:.3f}, no O(N^2) values in the jaxpr")

# -- (b) 100k-user cohort streaming: zero recompiles across 3 swaps -----
r = raw(topology={"type": "exponential", "num_nodes": 16},
        population={"enabled": True, "virtual_size": 100_000,
                    "sampler": "uniform", "seed": 5},
        tpu={"recompile_guard": True})
r["data"]["params"]["num_samples"] = 16 * 8
r["training"]["batch_size"] = 8
net = build_network_from_config(Config.model_validate(r))
# tpu.recompile_guard raises RecompileError on ANY post-warmup compile —
# 3 cohort swaps under the guard ARE the zero-recompile assertion.
net.train(rounds=3, eval_every=1)
if net.cohorts_seen != 3:
    print(f"expected 3 cohort swaps, saw {net.cohorts_seen}")
    sys.exit(1)
from murmura_tpu.population import draw_cohort
a = draw_cohort("uniform", 100_000, 16, 2, 5)
b = draw_cohort("uniform", 100_000, 16, 2, 5)
if not np.array_equal(a, b):
    print("cohort draws are not seed-deterministic")
    sys.exit(1)
print(f"100k cohort streaming ok: 3 swaps, zero post-warmup recompiles, "
      f"{net.bank.activated} users activated, draws deterministic")
PYEOF
  then
    echo "preflight population FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_population.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight population clean" | tee -a "$OUT/battery.log"
fi
# Optional compressed-exchange pre-flight (./run_tpu_battery.sh --compress
# [outdir]): the ISSUE-7 gates — an int8 + error-feedback krum smoke on
# the attack scenario must (a) land honest accuracy within tolerance of
# the uncompressed run, (b) finish with ZERO post-warmup recompiles
# (CompileTracker via tpu.recompile_guard — scales/residuals are traced
# values, never structure), and (c) show the >= 3x analytic exchange-bytes
# reduction the bench variants report.  CPU-pinned like the other gates.
if [ "$COMPRESS" = 1 ]; then
  echo "=== preflight: compressed exchange (int8+EF krum) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  if ! timeout 900 env JAX_PLATFORMS=cpu python - > "$OUT/preflight_compress.out" 2>&1 <<'PYEOF'
import sys
import numpy as np
from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config

def raw(**over):
    r = {
        "experiment": {"name": "compress-preflight", "seed": 11, "rounds": 6},
        "topology": {"type": "k-regular", "num_nodes": 16, "k": 4},
        "aggregation": {"algorithm": "krum",
                        "params": {"num_compromised": 1}},
        "attack": {"enabled": True, "type": "gaussian", "percentage": 0.2,
                   "params": {"noise_std": 10.0}},
        "training": {"local_epochs": 1, "batch_size": 16, "lr": 0.05},
        "data": {"adapter": "synthetic",
                 "params": {"num_samples": 16 * 32, "input_dim": 10,
                            "num_classes": 3}},
        "model": {"factory": "mlp",
                  "params": {"input_dim": 10, "hidden_dims": [16],
                             "num_classes": 3}},
        "backend": "simulation",
    }
    r.update(over)
    return r

def honest_acc(net, hist):
    comp = net.compromised > 0
    return hist.get("honest_accuracy", hist["mean_accuracy"])[-1]

base = build_network_from_config(Config.model_validate(raw()))
h0 = base.train(rounds=6, eval_every=6)
# tpu.recompile_guard raises RecompileError on ANY post-warmup compile —
# the 6 rounds under the guard ARE the zero-recompile assertion.
comp_net = build_network_from_config(Config.model_validate(raw(
    compression={"algorithm": "int8", "error_feedback": True, "block": 256},
    tpu={"recompile_guard": True},
)))
h1 = comp_net.train(rounds=6, eval_every=6)
a0, a1 = honest_acc(base, h0), honest_acc(comp_net, h1)
# One-sided: the codec must not LOSE accuracy (beating the uncompressed
# run — quantization noise sometimes regularizes — is not a failure).
if a1 < a0 - 0.02:
    print(f"int8+EF honest accuracy {a1:.4f} more than 2% below "
          f"uncompressed {a0:.4f}")
    sys.exit(1)
cost = comp_net.exchange_cost_analysis()
if cost["exchange_bytes_reduction"] < 3.0:
    print(f"analytic exchange-bytes reduction "
          f"{cost['exchange_bytes_reduction']:.2f}x < 3x")
    sys.exit(1)
print(f"compressed exchange ok: honest acc {a1:.4f} vs {a0:.4f} "
      f"(uncompressed), zero post-warmup recompiles, "
      f"{cost['exchange_bytes_reduction']:.2f}x fewer exchange bytes "
      f"({cost['payload_bytes_per_edge']:.0f} vs "
      f"{cost['uncompressed_bytes_per_edge']:.0f} per edge)")
PYEOF
  then
    echo "preflight compress FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_compress.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight compress clean" | tee -a "$OUT/battery.log"
fi
# Optional durability pre-flight (./run_tpu_battery.sh --resume [outdir]):
# the ISSUE-10 crash-equivalence gate, with a REAL process death — a
# subprocess trains the resumable example config 3 rounds, snapshots, and
# SIGKILLs itself (no atexit, no finalization; everything past the
# snapshot is genuinely lost).  A fresh process then resumes under
# tpu.recompile_guard and must (a) restore exactly round 3, (b) finish
# with a history byte-identical to an uninterrupted run (MUR901), and
# (c) compile nothing after its warmup round (MUR902) — if kill-and-
# resume drifts by one bit or one compile, every long battery run below
# is unrecoverable and the whole durability story is fiction.  CPU-pinned
# like the other gates.
if [ "$RESUME" = 1 ]; then
  echo "=== preflight: durability kill/resume (crash-equivalence) ($(date +%H:%M:%S)) ===" | tee -a "$OUT/battery.log"
  DUR_DIR="$OUT/resume_preflight"
  rm -rf "$DUR_DIR"
  if ! timeout 900 env JAX_PLATFORMS=cpu MURMURA_DUR_DIR="$DUR_DIR" python - > "$OUT/preflight_resume.out" 2>&1 <<'PYEOF'
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import yaml

from murmura_tpu.analysis.durability import history_equal
from murmura_tpu.config import Config
from murmura_tpu.utils.checkpoint import has_checkpoint
from murmura_tpu.utils.factories import build_network_from_config

dur_dir = Path(os.environ["MURMURA_DUR_DIR"])
ckpt = dur_dir / "ckpt"
raw = yaml.safe_load(Path("examples/configs/resumable_run.yaml").read_text())
raw["experiment"]["rounds"] = 6
raw["experiment"]["verbose"] = False
raw["telemetry"]["enabled"] = False
raw["durability"]["checkpoint_dir"] = str(ckpt)
raw["durability"]["checkpoint_every"] = 3
(dur_dir / "config.json").parent.mkdir(parents=True, exist_ok=True)
(dur_dir / "config.json").write_text(json.dumps(raw))

# -- uninterrupted reference (same build path the victim/resumer use) ----
ref = build_network_from_config(Config.model_validate(raw))
ref.train(rounds=6)
ref_hist = {k: list(v) for k, v in ref.history.items()}

# -- victim: train 3 rounds, snapshot, then die by SIGKILL ---------------
victim = r"""
import json, os, signal, sys
from pathlib import Path
from murmura_tpu.config import Config
from murmura_tpu.utils.factories import build_network_from_config
raw = json.loads(Path(sys.argv[1]).read_text())
net = build_network_from_config(Config.model_validate(raw))
net.train(rounds=3)
net.save_checkpoint(raw["durability"]["checkpoint_dir"])
print("victim: snapshot written, dying", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""
proc = subprocess.run(
    [sys.executable, "-c", victim, str(dur_dir / "config.json")],
    capture_output=True, text=True,
    env={**os.environ, "JAX_PLATFORMS": "cpu"},
)
if proc.returncode != -signal.SIGKILL:
    print(f"victim did not die by SIGKILL (rc={proc.returncode}):\n"
          f"{proc.stdout}\n{proc.stderr}")
    sys.exit(1)
if not has_checkpoint(ckpt):
    print(f"victim died without a snapshot in {ckpt}")
    sys.exit(1)
meta = json.loads((ckpt / "meta.json").read_text())
if meta["round"] != 3:
    print(f"snapshot round {meta['round']} != 3")
    sys.exit(1)

# -- resume: fresh process state, recompile-guarded continuation ---------
raw["tpu"] = dict(raw.get("tpu") or {}, recompile_guard=True)
resumed = build_network_from_config(
    Config.model_validate(raw), checkpoint_dir=str(ckpt)
)
done = resumed.restore_checkpoint(str(ckpt))
if done != 3:
    print(f"restore returned round {done}, expected 3")
    sys.exit(1)
# tpu.recompile_guard raises RecompileError on ANY post-warmup compile —
# the 3 resumed rounds under the guard ARE the zero-recompile assertion.
resumed.train(rounds=3)
res_hist = {k: list(v) for k, v in resumed.history.items()}
if not history_equal(ref_hist, res_hist):
    diverged = sorted(
        k for k in ref_hist
        if not history_equal(ref_hist[k], res_hist.get(k, []))
    )
    print(f"resumed history diverged from uninterrupted run in {diverged}")
    sys.exit(1)
print("kill/resume ok: victim SIGKILLed after round 3, resumed history "
      "byte-identical over 6 rounds, zero post-warmup recompiles")
PYEOF
  then
    echo "preflight resume FAILED — aborting battery" | tee -a "$OUT/battery.log"
    tail -20 "$OUT/preflight_resume.out" | tee -a "$OUT/battery.log"
    exit 1
  fi
  echo "preflight resume clean" | tee -a "$OUT/battery.log"
fi
run bench          2400 python bench.py
run breakdown      2400 python bench_breakdown.py
run breakdown256   2400 python bench_breakdown.py --nodes 256
run sgd_micro      1800 python bench_sgd_micro.py
run rules256       3600 python bench_rules_256.py
run scaling        14400 python bench_scaling.py
run scaling_sparse 7200 python bench_scaling.py --sparse
echo "battery done $(date)" | tee -a "$OUT/battery.log"
