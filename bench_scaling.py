"""Node-scaling benchmark: 8 -> 256 FL nodes on one chip.

The north-star scaling axis (BASELINE.json; SURVEY.md §7 memory-at-scale
note): rounds/sec and peak device memory for
``nodes in {8, 64, 256} x {krum/allgather, balance/ppermute}`` plus
1024-node points and krum/ppermute (circulant delta-vector Krum), all
nodes resident on a single chip.  krum/allgather is the O(N)
dense-exchange worst case (every node sees the full [N, P] tensor and a
global N x N distance matrix); the ppermute points are the O(degree)
circulant path that is the intended large-N configuration.

Each point runs in its OWN subprocess: peak memory stats start clean, and
an OOM kills the point, not the harness.  The parent never touches JAX (a
chip belongs to one process); each point asks ``jax.devices()`` once and
exits 2 unless it is a TPU — no CPU fallback.  The flagship ~6.5M-param
CNN is used with tpu.param_dtype=bfloat16 (the intended large-N setting —
halves the resident [N, P] state).

Writes bench_scaling.json (committed) and prints it.
"""

import argparse
import json
import resource
import subprocess
import sys
import time
from pathlib import Path

POINTS = [
    {"nodes": n, "algo": algo, "exchange": exch}
    for n in (8, 64, 256)
    for algo, exch in (("krum", "allgather"), ("balance", "ppermute"))
] + [
    # 1024-node Krum: the O(N^3)-fix acceptance point (round-2 verdict
    # task 6).  The ~800K-param "small" CNN keeps own+gathered [N, P]
    # state inside one chip's HBM at N=1024 (the flagship 6.5M model's
    # gathered tensor alone would be ~13 GB in bf16).
    {"nodes": 1024, "algo": "krum", "exchange": "allgather",
     "variant": "small"},
    {"nodes": 1024, "algo": "balance", "exchange": "ppermute",
     "variant": "small"},
    # Circulant Krum (delta-vector distances): the O(degree) large-N
    # configuration for the flagship rule — no [N, N] matrices, no Gram.
    {"nodes": 256, "algo": "krum", "exchange": "ppermute"},
    {"nodes": 1024, "algo": "krum", "exchange": "ppermute",
     "variant": "small"},
]

# --sparse variant (ISSUE 6): the exponential-graph edge-mask engine
# (topology/sparse.py, exchange == "sparse") at the three scaling marks.
# Degree is O(log N), the round program's adjacency input is [k, N], and
# MUR600 proves no [N, N] operand in the lowering — 4096 nodes on one
# chip is the acceptance point.  Each cell records cost{flops,bytes,mfu}
# (bench.py's cost line) plus the analytic per-round exchange bytes.
SPARSE_POINTS = [
    {"nodes": 256, "algo": "krum", "exchange": "sparse"},
    {"nodes": 1024, "algo": "krum", "exchange": "sparse",
     "variant": "small"},
    {"nodes": 4096, "algo": "krum", "exchange": "sparse",
     "variant": "small"},
    {"nodes": 4096, "algo": "fedavg", "exchange": "sparse",
     "variant": "small"},
]


# --sharded variant (ISSUE 15): param-axis sharding cells — a big
# per-node MLP on the ("seed", "nodes", "param") CPU/TPU mesh
# (tpu.param_shards; docs/PERFORMANCE.md "Param-axis sharding").  The
# flagship cell is the acceptance point: a >= 50M-param-per-node model at
# N=16 on ONE host, every [N, P] round tensor resident at N x P/shards
# per device.  Each cell records the analytic per-device resident params
# (the number the axis exists to shrink) next to the measured peak RSS.
SHARDED_POINTS = [
    # ~0.9M params: the layout-sweep cell (fast everywhere).
    {"nodes": 16, "shards": 4, "algo": "krum",
     "hidden": [512, 512], "input_dim": 256},
    # >= 50M params per node at N=16: the acceptance cell.  1000 x 7200
    # + 7200 x 6200 + 6200 x 62 (+ biases) = 51.9M params; at shards=8
    # the [N, P] round tensors are resident at 16 x 6.5M floats per
    # device instead of 16 x 51.9M.
    {"nodes": 16, "shards": 8, "algo": "krum",
     "hidden": [7200, 6200], "input_dim": 1000},
]


def run_sharded_point(
    nodes: int, shards: int, algo: str, hidden, input_dim: int
) -> None:
    """Child-process body: one param-sharding point, one JSON line."""
    import jax

    from bench import require_chip

    device = require_chip("bench_scaling --sharded-point")

    from murmura_tpu.config import Config
    from murmura_tpu.parallel.mesh import (
        mesh_node_axis,
        mesh_param_shards,
    )
    from murmura_tpu.utils.factories import build_network_from_config

    classes = 62
    cfg = Config.model_validate(
        {
            "experiment": {"name": f"sharded-{algo}-{nodes}x{shards}",
                           "seed": 7, "rounds": 3},
            "topology": {"type": "k-regular", "num_nodes": nodes, "k": 4},
            "aggregation": {"algorithm": algo,
                            "params": ({"num_compromised": 1}
                                       if algo == "krum" else {})},
            "training": {"local_epochs": 1, "batch_size": 4, "lr": 0.05},
            "data": {
                "adapter": "synthetic",
                "params": {"num_samples": 8 * nodes,
                           "input_shape": [input_dim],
                           "num_classes": classes},
            },
            "model": {"factory": "mlp",
                      "params": {"input_dim": input_dim,
                                 "hidden_dims": list(hidden),
                                 "num_classes": classes}},
            "backend": "tpu",
            "tpu": {
                "param_shards": shards,
                "compute_dtype": "float32",
                "param_dtype": "float32",
            },
        }
    )
    network = build_network_from_config(cfg)
    mesh = network.mesh
    nodes_ax = mesh_node_axis(mesh)
    param_ax = mesh_param_shards(mesh)

    timed = 2
    t0 = time.perf_counter()
    network.train(rounds=1, eval_every=10)
    first_round_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    network.train(rounds=timed, eval_every=10)
    rounds_per_sec = timed / (time.perf_counter() - t0)

    flat = int(network.program.flat_dim)
    mem = {"peak_host_rss_bytes": resource.getrusage(
        resource.RUSAGE_SELF
    ).ru_maxrss * 1024}
    mem["peak_device_bytes"] = int(device.memory_stats()["peak_bytes_in_use"])
    print(json.dumps({
        "nodes": nodes,
        "algo": algo,
        "exchange": "sharded",
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "param_shards_requested": shards,
        "mesh": {"seed": 1, "nodes": nodes_ax, "param": param_ax},
        "model_dim": int(network.program.model_dim),
        "flat_dim": flat,
        # The memory model (docs/PERFORMANCE.md): per-device resident
        # floats for ONE [N, P]-class round tensor, sharded vs not — the
        # max-resident-params-per-device cell of the scaling record.
        "flat_params_per_device": (nodes // nodes_ax) * (flat // param_ax),
        "flat_params_per_device_unsharded": nodes * flat,
        # Training keeps each node's full model resident (the pytree is
        # node-sharded, param-replicated).
        "train_params_per_device": (nodes // nodes_ax) * int(
            network.program.model_dim
        ),
        "rounds_per_sec": round(rounds_per_sec, 4),
        "first_round_s": round(first_round_s, 1),
        "timed_rounds_per_block": timed,
        **mem,
    }))


def run_point(
    nodes: int, algo: str, exchange: str, variant: str = ""
) -> None:
    """Child-process body: one scaling point, one JSON line on stdout."""
    import jax

    from bench import require_chip

    device = require_chip("bench_scaling --point")

    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.config import Config
    from murmura_tpu.utils.factories import build_network_from_config

    agg_params = (
        # Krum requires c < (m-2)/2 with m = degree+2 candidates; on the
        # k=4 graph that caps usable c at 1 regardless of N.
        {"num_compromised": 1} if algo == "krum"
        else {"gamma": 2.0}
    )
    model_params = {"variant": variant} if variant else {}
    samples_per_node = 64
    sparse = exchange == "sparse"
    if sparse:
        # exchange == "sparse": the exponential edge-mask engine — the
        # topology selects it; tpu.exchange is moot (factories route every
        # SparseTopology through the sparse circulant dispatch).
        topo_cfg = {"type": "exponential", "num_nodes": nodes}
    else:
        topo_cfg = {"type": "k-regular", "num_nodes": nodes, "k": 4}
    cfg = Config.model_validate(
        {
            "experiment": {"name": f"scale-{algo}-{nodes}", "seed": 7,
                           "rounds": 4},
            "topology": topo_cfg,
            "aggregation": {"algorithm": algo, "params": agg_params},
            "attack": {"enabled": True, "type": "gaussian", "percentage": 0.1,
                        "params": {"noise_std": 10.0}},
            "training": {"local_epochs": 1, "batch_size": 32, "lr": 0.05},
            "data": {
                "adapter": "synthetic",
                "params": {"num_samples": samples_per_node * nodes,
                           "input_shape": [28, 28, 1], "num_classes": 62},
            },
            "model": {
                "factory": "examples.leaf.LEAFFEMNISTModel",
                "params": model_params,
            },
            "backend": "tpu",
            "tpu": {
                "num_devices": 1,
                "compute_dtype": "bfloat16",
                "param_dtype": "bfloat16",
                # exchange == "sparse" is selected by the topology, not
                # this knob (any value validates; the sparse engine wins).
                "exchange": "allgather" if sparse else exchange,
            },
        }
    )
    network = build_network_from_config(cfg)

    timed = 10

    # True XLA compile time, isolated from execution: the round-3 sweep's
    # ``compile_s`` was the whole first train() block, which *includes
    # executing the block's rounds* — at 256 CPU nodes that is ~150 s of
    # execution on top of a ~4 s compile, which the round-3 verdict read
    # as superlinear compile growth.  AOT lower+compile measures the
    # compiler alone, on exactly the program the blocks below execute:
    # the fused multi-round scan when timed > 1, the per-round
    # train_step (+ eval) when timed == 1 (train() only takes the fused
    # path for rounds_per_dispatch > 1).
    if timed > 1:
        targets = [(
            network._fused_step(timed, timed),
            (
                network.params,
                network.agg_state,
                network._rng,
                jnp.asarray(
                    np.stack(
                        [network._adjacency_for_round(i) for i in range(timed)]
                    )
                ),
                jnp.asarray(network.compromised),
                jnp.asarray(0, dtype=jnp.int32),
                network._data,
            ),
        )]
    else:
        import jax.random as jrandom

        targets = [
            (
                network._step,
                (
                    network.params,
                    network.agg_state,
                    jrandom.fold_in(network._rng, 0),
                    jnp.asarray(network._adjacency_for_round(0)),
                    jnp.asarray(network.compromised),
                    jnp.asarray(0.0, dtype=jnp.float32),
                    network._data,
                ),
            ),
            (network._eval, (network.params, network._data)),
        ]
    # The AOT compile below must measure the compiler cold, so the
    # persistent cache (factories.apply_compilation_cache, on since the
    # build above) is switched off around it.
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    lower_s = aot_compile_s = 0.0
    lowereds = []
    for fn, fn_args in targets:
        t0 = time.perf_counter()
        lowereds.append(fn.lower(*fn_args))
        lower_s += time.perf_counter() - t0
    for low in lowereds:
        t0 = time.perf_counter()
        low.compile()
        aot_compile_s += time.perf_counter() - t0
    # AOT compiles do not populate jit's in-memory executable cache, so
    # switch the persistent cache back on and compile the same programs
    # once more through it: block 1 below then pays only the cache
    # write/read, not a third full compile (and repeat sweeps skip this
    # compile too).
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    for fn, fn_args in targets:
        fn.lower(*fn_args).compile()

    # Same convention as bench.py: every block is ONE dispatch of the
    # measured program (the fused lax.scan for timed > 1, a single round
    # for timed == 1; eval on the block's last round).  Block 1 pays
    # persistent-cache deserialization, block 2 absorbs the steady-state
    # input-layout recompile (the program specialized to the layouts of
    # its own outputs), block 3 is the measurement; train() returns only
    # after the block's metrics are fetched, so the wall clock covers
    # every round.
    def block():
        t0 = time.perf_counter()
        network.train(rounds=timed, eval_every=timed,
                      rounds_per_dispatch=timed)
        return time.perf_counter() - t0

    first_block_s = block()
    warmup_s = block()
    rounds_per_sec = timed / block()

    cost = None
    if sparse:
        # The bench.py cost line, per sparse cell: XLA's AOT cost model of
        # the per-round step (flops, bytes; the lower+compile is a cache
        # hit for timed == 1 and a one-off small compile otherwise), MFU
        # against the chip's peak, and the analytic per-round exchange
        # bytes (degree x N x P x itemsize — what actually travels,
        # O(N log N), vs the dense modes' O(N^2) mask alone).
        from bench import _peak_flops

        c = network.step_cost_analysis()
        flops = float(c.get("flops", 0.0)) or None
        peak = _peak_flops(device.device_kind)
        cost = {
            "flops": flops,
            "bytes": float(c.get("bytes accessed", 0.0)) or None,
            "mfu": (
                round(flops * rounds_per_sec / peak, 6) if flops else None
            ),
        }
        itemsize = 2 if cfg.tpu.param_dtype == "bfloat16" else 4
        degree = len(network.topology.offsets)
        exchange_bytes = degree * nodes * int(network.program.model_dim) * itemsize

    # Static XLA residency of the round step (memory_analysis() off the
    # cost line's shared AOT compile — nothing executes): the same fields
    # the MUR1500 budget sweep gates on, recorded next to the *runtime*
    # peaks below so allocator overhead vs compiled footprint is one diff.
    from bench import _memory_block

    memory = _memory_block(network)

    mem = {"peak_device_bytes": int(device.memory_stats()["peak_bytes_in_use"])}
    mem["peak_host_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF
    ).ru_maxrss * 1024

    print(json.dumps({
        "nodes": nodes,
        "algo": algo,
        "exchange": exchange,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "variant": model_params.get("variant", "baseline"),
        "rounds_per_sec": round(rounds_per_sec, 4),
        # compile_s is the compiler alone (AOT lower+compile, nothing
        # executed); first_block_s is what round 3 used to call compile_s
        # (cache-hit compile + executing the block's rounds).
        "compile_s": round(aot_compile_s, 1),
        "lower_s": round(lower_s, 1),
        "first_block_s": round(first_block_s, 1),
        "steady_warmup_s": round(warmup_s, 1),
        "timed_rounds_per_block": timed,
        "samples_per_node": samples_per_node,
        "model_dim": int(network.program.model_dim),
        **({"cost": cost,
            "degree": degree,
            "exchange_bytes_per_round": exchange_bytes} if sparse else {}),
        "memory": memory,
        **mem,
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--point", nargs=3, metavar=("NODES", "ALGO", "EXCHANGE"),
                    default=None, help="internal: run one point in-process")
    ap.add_argument("--variant", default="",
                    help="internal: model variant override for --point")
    ap.add_argument("--sparse", action="store_true",
                    help="run the exponential-graph sparse-exchange cells "
                         "(N in {256, 1024, 4096}) instead of the dense/"
                         "circulant grid; writes bench_scaling_sparse.json")
    ap.add_argument("--sharded", action="store_true",
                    help="run the param-axis sharding cells (ISSUE 15: a "
                         ">= 50M-param-per-node model at N=16 on one "
                         "host's mesh, tpu.param_shards) instead of the "
                         "dense/circulant grid; writes "
                         "bench_scaling_sharded.json")
    ap.add_argument("--sharded-point", nargs=5,
                    metavar=("NODES", "SHARDS", "ALGO", "HIDDEN", "INPUT"),
                    default=None,
                    help="internal: run one sharded point in-process "
                         "(HIDDEN is comma-separated layer widths)")
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = str(Path(__file__).parent / (
            "bench_scaling_sharded.json" if args.sharded else
            "bench_scaling_sparse.json" if args.sparse else
            "bench_scaling.json"
        ))

    if args.sharded_point:
        run_sharded_point(
            int(args.sharded_point[0]), int(args.sharded_point[1]),
            args.sharded_point[2],
            [int(h) for h in args.sharded_point[3].split(",")],
            int(args.sharded_point[4]),
        )
        return
    if args.point:
        run_point(int(args.point[0]), args.point[1], args.point[2],
                  variant=args.variant)
        return

    # The parent stays off JAX: every point is a child that needs the chip
    # for itself, and stamps the platform, device_kind and device count it
    # ran on into its own record.
    results = []

    def flush(done: bool) -> dict:
        # Written after EVERY point: a killed sweep (wall-clock budget)
        # still leaves the completed points on disk.
        blob = {"complete": done, "points": results}
        Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
        return blob

    points = (
        SHARDED_POINTS if args.sharded
        else SPARSE_POINTS if args.sparse else POINTS
    )
    for p in points:
        if args.sharded:
            cmd = [sys.executable, __file__, "--sharded-point",
                   str(p["nodes"]), str(p["shards"]), p["algo"],
                   ",".join(str(h) for h in p["hidden"]),
                   str(p["input_dim"])]
            label = (f"[{p['nodes']:>3} nodes x {p['shards']} shards "
                     f"{p['algo']}/sharded]")
        else:
            cmd = [sys.executable, __file__, "--point", str(p["nodes"]),
                   p["algo"], p["exchange"]]
            if p.get("variant"):
                cmd += ["--variant", p["variant"]]
            label = f"[{p['nodes']:>3} nodes {p['algo']}/{p['exchange']}]"
        print(f"{label} ...", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.timeout)
            if proc.returncode == 0 and proc.stdout.strip():
                results.append(json.loads(proc.stdout.strip().splitlines()[-1]))
            else:
                results.append({**p, "ok": False, "rc": proc.returncode,
                                "err": (proc.stderr or "")[-500:]})
        except subprocess.TimeoutExpired:
            results.append({**p, "ok": False,
                            "err": f"timeout after {args.timeout}s"})
        flush(done=False)

    blob = flush(done=True)
    # Final OpenMetrics snapshot next to the blob (ISSUE 19): the scalar
    # leaves through the same serializer the daemon's metrics op renders,
    # so bench trajectories scrape with stock tooling.
    from murmura_tpu.telemetry.metrics import (
        MetricsRegistry,
        fold_bench_payload,
        render_openmetrics,
    )

    reg = MetricsRegistry()
    fold_bench_payload(reg, "bench_scaling", blob)
    Path(args.out).with_suffix(".prom").write_text(render_openmetrics(reg))
    print(json.dumps(blob))
    if any(p.get("ok") is False for p in results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
