"""Benchmark: FL rounds/sec on the flagship Byzantine scenario.

Scenario (BASELINE.json config #2): Krum aggregation, 20-node k-regular(4)
topology, 20% Gaussian-Byzantine nodes, FEMNIST baseline CNN (~6.5M params),
one local epoch per round.  Data is FEMNIST-shaped synthetic (28x28x1, 62
classes; zero-egress environment).  The whole round — local SGD, attack,
adjacency-masked exchange, Krum selection over the gathered [N, P] tensor —
is one jitted program on the TPU, and the timed block fuses all its rounds
into a single lax.scan dispatch (rounds_per_dispatch) with eval on the
final round only.

Chip or fail: the process that measures asks ``jax.devices()`` once and
exits 2 unless it is a TPU (durability/dispatch.require_tpu).  There is no
CPU fallback and no probe subprocess — a chip belongs to one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
extras (platform, device_kind, device_count, compile time, per-round times,
flops, MFU).  The reference publishes no throughput numbers (BASELINE.md);
vs_baseline is measured against the north-star target of 50 FL rounds/sec
(BASELINE.json).
"""

import json
import sys
import time
from pathlib import Path

# Peak dense bf16 matmul throughput of one chip, keyed by the device_kind
# JAX reports (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16).
# Used only for the MFU estimate; a device that is not in the table is an
# error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def require_chip(script: str):
    """The one device question a measurement script asks, in the process
    that measures: the first device, or exit 2 when it is not a TPU."""
    from murmura_tpu.durability.dispatch import (
        BackendRequirementError,
        require_tpu,
    )

    try:
        require_tpu(source=script)
    except BackendRequirementError as e:
        print(f"{script}: {e}", file=sys.stderr, flush=True)
        raise SystemExit(2)
    import jax

    return jax.devices()[0]


def _peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device_kind!r}; add "
            "it to bench.PEAK_FLOPS with its source"
        )
    return PEAK_FLOPS[device_kind]


def _memory_block(network) -> dict:
    """The per-run static-residency line (XLA ``memory_analysis()`` of the
    round step, free off the cost line's shared AOT compile): same fields
    the MUR1500 budget sweep gates on (analysis/memory.py), so drift
    between committed MEMORY.json and the bench's own footprint is
    visible in one diff."""
    mem = network.step_memory_analysis()
    return {
        "temp_bytes": mem["temp_bytes"],
        "argument_bytes": mem["argument_bytes"],
        "output_bytes": mem["output_bytes"],
        "peak_bytes": mem["peak_bytes"],
    }


def bench_config(num_nodes: int = 20,
                 param_dtype: str = "float32", exchange: str = "allgather",
                 sweep: dict = None, compression: dict = None):
    from murmura_tpu.config import Config

    raw = {
            "experiment": {"name": "bench-krum-femnist", "seed": 7, "rounds": 10},
            "topology": {"type": "k-regular", "num_nodes": num_nodes, "k": 4},
            "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
            "attack": {
                "enabled": True,
                "type": "gaussian",
                "percentage": 0.2,
                "params": {"noise_std": 10.0},
            },
            "training": {"local_epochs": 1, "batch_size": 32, "lr": 0.05},
            "data": {
                "adapter": "synthetic",
                "params": {
                    "num_samples": 160 * num_nodes,
                    "input_shape": [28, 28, 1],
                    "num_classes": 62,
                },
            },
            # The ~6.5M-param baseline CNN.
            "model": {
                "factory": "examples.leaf.LEAFFEMNISTModel",
                "params": {},
            },
            # Single-chip mesh; bfloat16 matmul/conv inputs on the MXU with
            # float32 params/accumulation (models/core.py mixed precision).
            "backend": "tpu",
            "tpu": {
                "num_devices": 1,
                "compute_dtype": "bfloat16",
                "param_dtype": param_dtype,
                "exchange": exchange,
            },
        }
    if sweep is not None:
        raw["sweep"] = sweep
    if compression is not None:
        raw["compression"] = compression
    return Config.model_validate(raw)


def build_network(num_nodes: int = 20,
                  param_dtype: str = "float32", exchange: str = "allgather"):
    from murmura_tpu.utils.factories import build_network_from_config

    return build_network_from_config(
        bench_config(num_nodes, param_dtype, exchange)
    )


def main():
    device = require_chip("bench")
    import jax

    backend = device.platform
    device_kind = device.device_kind
    device_count = len(jax.devices())

    timed_rounds = 20

    def measure(param_dtype: str, num_nodes: int = 20,
                exchange: str = "allgather") -> dict:
        """Three fused blocks on a fresh network; returns the variant's
        numbers.  The timed block is ONE dispatch: all rounds fused into a
        lax.scan program (tpu.rounds_per_dispatch) with the round loop
        device-resident and eval running (under lax.cond) only on the last
        round of the chunk.  First call compiles; the second absorbs the
        steady-state input-layout recompile (the step specialized to the
        layouts of its own outputs); the third is the measurement."""
        network = build_network(num_nodes=num_nodes,
                                param_dtype=param_dtype, exchange=exchange)

        def block():
            t0 = time.perf_counter()
            network.train(rounds=timed_rounds, eval_every=timed_rounds,
                          rounds_per_dispatch=timed_rounds)
            return time.perf_counter() - t0

        compile_s = block()
        warmup_s = block()
        elapsed = block()
        # Cost analysis runs here (AOT, nothing executes) so the network —
        # and its resident [N, P] device state — can be dropped before the
        # next variant builds; holding both variants' buffers would add
        # HBM pressure during the second timed measurement.  flops AND
        # bytes are recorded so every BENCH_r*.json carries the same cost
        # line the `murmura check --ir` budget sweep gates on
        # (analysis/budgets.py) — drift between committed budgets and the
        # bench's own cost line is then visible in one diff.
        cost = network.step_cost_analysis()
        flops = float(cost.get("flops", 0.0)) or None
        bytes_accessed = float(cost.get("bytes accessed", 0.0)) or None
        memory = _memory_block(network)
        return {
            "param_dtype": param_dtype,
            "rounds_per_sec": timed_rounds / elapsed,
            "compile_s": round(compile_s, 2),
            "steady_warmup_s": round(warmup_s, 2),
            "elapsed": elapsed,
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "memory": memory,
        }

    def measure_gang(gang_size: int, gang_rounds: int) -> dict:
        """Gang-batched variant (core/gang.py): the same bench scenario
        stacked over ``gang_size`` seeds and vmapped into ONE fused
        program.  Reports aggregate FL rounds/sec (S x rounds / wall) and
        the amortized compile cost per member — the number that turns an
        S-cell seed sweep from S compiles + S underfilled executions into
        one of each.  CompileTracker counts XLA compiles per block; the
        timed block must run compile-free."""
        from murmura_tpu.analysis.sanitizers import track_compiles
        from murmura_tpu.utils.factories import build_gang_from_config

        cfg = bench_config(sweep={"num_seeds": gang_size})
        gang = build_gang_from_config(cfg)

        def block():
            t0 = time.perf_counter()
            gang.train(rounds=gang_rounds, eval_every=gang_rounds,
                       rounds_per_dispatch=gang_rounds)
            return time.perf_counter() - t0

        with track_compiles() as tracker:
            compile_s = block()
            compile_compiles = tracker.total
            warmup_s = block()
            after_warmup = tracker.total
            elapsed = block()
            timed_compiles = tracker.total - after_warmup
        return {
            "gang_size": gang_size,
            "rounds": gang_rounds,
            "aggregate_rounds_per_sec": gang_size * gang_rounds / elapsed,
            "compile_s": round(compile_s, 2),
            "compile_s_per_run": round(compile_s / gang_size, 2),
            "steady_warmup_s": round(warmup_s, 2),
            "elapsed": round(elapsed, 3),
            # Compiles observed by CompileTracker: the whole gang pays its
            # program compiles once (first block); the timed block must be
            # compile-free regardless of S.
            "warmup_block_compiles": compile_compiles,
            "timed_block_compiles": timed_compiles,
        }

    def measure_compression(num_nodes: int, compression: dict,
                            rounds: int) -> dict:
        """Compressed-exchange variant (ops/compress.py; ISSUE 7): the
        headline krum scenario on the circulant (ppermute) exchange with
        the given ``compression:`` block, at ``num_nodes``.  Reports
        rounds/sec, the measured AOT cost line, and the ANALYTIC exchange
        bytes (edges x what actually crosses an edge:
        Network.exchange_cost_analysis) so the bytes reduction is
        committed history next to the measured numbers."""
        from murmura_tpu.utils.factories import build_network_from_config

        cfg = bench_config(
            num_nodes=num_nodes,
            param_dtype="bfloat16" if num_nodes >= 64 else "float32",
            exchange="ppermute", compression=compression,
        )
        network = build_network_from_config(cfg)

        def block():
            t0 = time.perf_counter()
            network.train(rounds=rounds, eval_every=rounds,
                          rounds_per_dispatch=rounds)
            return time.perf_counter() - t0

        compile_s = block()
        block()  # steady-state layout recompile absorber
        elapsed = block()
        rec = {
            "rounds_per_sec": round(rounds / elapsed, 3),
            "compile_s": round(compile_s, 2),
            "exchange": network.exchange_cost_analysis(),
        }
        cost = network.step_cost_analysis()
        rec["flops"] = float(cost.get("flops", 0.0)) or None
        rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0)) or None
        rec["memory"] = _memory_block(network)
        ce = network.history.get("agg_compress_error")
        if ce:
            rec["compress_error_final"] = round(float(ce[-1]), 6)
        return rec

    # Headline config (float32 resident params) plus the
    # bf16-resident-params lever (tpu.param_dtype, the documented large-N
    # setting: halves the [N, P] state and the SGD update's HBM traffic).
    # The float32 number stays the headline so round-over-round trend
    # tables remain apples-to-apples (round-4 advisor); the lever is
    # reported separately in ``variants``/``bf16_lever_rounds_per_sec``.
    # A failure anywhere below fails the bench: no error field that still
    # exits 0.
    variants = [measure("float32"), measure("bfloat16")]
    best = variants[0]
    rounds_per_sec = best["rounds_per_sec"]

    # MFU: XLA's own flop count for the per-round train program (local SGD
    # + attack + exchange + Krum) vs peak chip flops.  Eval is a separate
    # program on the eval_every cadence and is excluded from round flops.
    # Computed per variant; null only where XLA reports no flops.
    def _mfu(flops, rps):
        if not flops:
            return None
        return round(flops * rps / _peak_flops(device_kind), 4)

    flops = best["flops"]
    mfu = _mfu(flops, rounds_per_sec)
    mfu_variants = {
        v["param_dtype"]: _mfu(v["flops"], v["rounds_per_sec"])
        for v in variants
    }

    # Gang-batched compile amortization (ISSUE 5): aggregate rounds/sec at
    # S in {1, 4, 8} with the compile paid once per gang.  Measured BEFORE
    # the 256-node north star (it shares the 20-node scenario) and emitted
    # into the headline JSON.
    gang_results = {
        str(s_): measure_gang(s_, timed_rounds) for s_ in (1, 4, 8)
    }
    base_rate = gang_results["1"]["aggregate_rounds_per_sec"]
    for rec in gang_results.values():
        rec["speedup_vs_s1"] = round(
            rec["aggregate_rounds_per_sec"] / base_rate, 3
        )
        rec["aggregate_rounds_per_sec"] = round(
            rec["aggregate_rounds_per_sec"], 3
        )

    # Compressed-exchange variants (none / int8+EF / topk+EF) at N=32 and
    # the 256-node north-star scale.  The analytic exchange-bytes column
    # is the acceptance surface (int8 >= 3x vs the uncompressed f32 rows;
    # topk ~25x).
    compress_results = {}
    compress_codecs = {
        "none": {},
        "int8": {"algorithm": "int8", "error_feedback": True},
        "topk": {"algorithm": "topk", "topk_ratio": 0.05,
                 "error_feedback": True},
    }
    for n_ in (32, 256):
        compress_results[str(n_)] = {
            label: measure_compression(n_, codec, timed_rounds)
            for label, codec in compress_codecs.items()
        }

    def emit(north_star, north_star_status):
        payload = {
                    "metric": "fl_rounds_per_sec_krum_femnist_cnn_20node",
                    "value": round(rounds_per_sec, 3),
                    "unit": "rounds/sec",
                    "vs_baseline": round(rounds_per_sec / 50.0, 4),
                    # The device the numbers were measured on, as JAX
                    # reports it in this process.
                    "platform": backend,
                    "device_kind": device_kind,
                    "device_count": device_count,
                    "param_dtype": best["param_dtype"],
                    "compile_s": best["compile_s"],
                    "steady_warmup_s": best["steady_warmup_s"],
                    "round_ms": {
                        # wall mean over the timed single-dispatch fused
                        # block (train() returns only after the chunk's
                        # metrics are fetched, so the wall clock covers
                        # every round).
                        "mean": round(1e3 * best["elapsed"] / timed_rounds, 2),
                    },
                    "variants": {
                        v["param_dtype"]: round(v["rounds_per_sec"], 3)
                        for v in variants
                    },
                    "bf16_lever_rounds_per_sec": next(
                        (round(v["rounds_per_sec"], 3) for v in variants
                         if v["param_dtype"] == "bfloat16"), None
                    ),
                    "north_star_256node": north_star,
                    "north_star_status": north_star_status,
                    # The cost line per run: XLA's own AOT cost model for
                    # the per-round program — the runtime twin of the
                    # committed analysis/BUDGETS.json sweep.
                    "flops_per_round": flops,
                    "bytes_accessed_per_round": best["bytes_accessed"],
                    "mfu": mfu,
                    "mfu_variants": mfu_variants,
                    # Gang-batched compile amortization (core/gang.py):
                    # aggregate fl_rounds_per_sec and compile_s_per_run at
                    # each gang size, CompileTracker compile counts per
                    # block (timed block must be 0).
                    "gang": gang_results,
                    # Compressed-exchange variants (ops/compress.py):
                    # rounds/sec + measured cost + ANALYTIC exchange bytes
                    # per codec at each scale, so the bytes reduction is
                    # visible in every BENCH_*.json.
                    "compression": compress_results,
        }
        # The stdout JSON line is the driver contract (last line wins) and
        # stays; the SAME payload also lands as a kind:bench telemetry
        # manifest (one schema for every artifact — docs/OBSERVABILITY.md).
        # Each emit atomically replaces the manifest, mirroring the
        # last-line-wins semantics.
        print(json.dumps(payload), flush=True)
        from murmura_tpu.telemetry.writer import write_bench_manifest

        # write_bench_manifest also drops a metrics.prom OpenMetrics
        # snapshot next to the manifest (ISSUE 19) — the same
        # serializer the serve daemon's metrics op renders.
        write_bench_manifest(
            Path(__file__).parent / "telemetry_runs" / "bench",
            "bench", payload,
        )

    # The north-star SCALE scenario (BASELINE.json: 256-node Krum FEMNIST):
    # same flagship model at 256 nodes on this one chip, bf16 resident
    # params, both exchange formulations measured (best reported).  The
    # headline is EMITTED FIRST so that a kill here leaves a valid JSON
    # line; on success the enriched line replaces it (last line wins).  A
    # failing variant fails the bench (non-zero exit).
    emit(None, "pending: 256-node run follows")

    # ppermute is the sharded-mesh configuration — its win is O(degree)
    # communication volume over ICI, which a one-chip run cannot exhibit;
    # on a single chip the dense allgather Gram path has won.
    ns_variants = {}
    best_ns = None
    for exch in ("allgather", "ppermute"):
        ns = measure("bfloat16", num_nodes=256, exchange=exch)
        ns_variants[exch] = round(ns["rounds_per_sec"], 3)
        if best_ns is None or ns["rounds_per_sec"] > best_ns[1]["rounds_per_sec"]:
            best_ns = (exch, ns)
    b_exch, b_ns = best_ns
    emit(
        {
            "nodes": 256,
            "exchange": b_exch,
            "param_dtype": "bfloat16",
            "rounds_per_sec": round(b_ns["rounds_per_sec"], 3),
            "compile_s": b_ns["compile_s"],
            "round_ms": round(1e3 * b_ns["elapsed"] / timed_rounds, 2),
            "mfu": _mfu(b_ns["flops"], b_ns["rounds_per_sec"]),
            "exchange_variants": ns_variants,
        },
        "measured",
    )


if __name__ == "__main__":
    main()
