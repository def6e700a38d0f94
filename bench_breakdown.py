"""Per-segment timing breakdown of the headline bench round.

Answers "where do the milliseconds of one FL round go?" by compiling and
timing nested subsets of the round program on the bench configuration
(20-node k-regular(4), FEMNIST baseline CNN, Krum, 20% gaussian):

    overhead   — zero-SGD step with a pass-through aggregator returning
                 ``own``: ravel/unravel + dispatch.  XLA dead-code
                 eliminates the unused attack here — which is the point:
                 it isolates the irreducible plumbing.
    attack     — (zero-SGD pass-through returning ``bcast``) - (overhead):
                 the [C, P] noise draw + one-hot matmul row expansion.
    local_sgd  — (1-epoch pass-through-bcast step) - (attack step): the
                 vmapped epochs x batches SGD scan.
    krum       — (full krum step) - (1-epoch pass-through-bcast step):
                 pairwise distance matmuls + candidate-block selection.
    eval       — the separately compiled eval sweep (paid only on
                 eval_every rounds since round 3's eval split).
    staleness  — bounded-staleness cells (ISSUE 13): the same krum round
                 under a 30% straggler + link-drop FaultSchedule, drop-
                 sync baseline vs max_staleness {1, 4}, with per-round
                 stale-edge counts committed in the manifest.
    pipeline   — pipelined-rounds cells (ISSUE 14): krum serialized vs
                 exchange.pipeline on dense k-regular(4) AND sparse
                 exponential graphs, int8+EF off/on, committing the
                 per-segment hidden fraction ((serialized - pipelined) /
                 (serialized - train)) and the MFU delta per cell, each
                 with its own platform stamp.

Writes bench_breakdown.json (committed) and prints it.  Chip or fail: the
script asks ``jax.devices()`` once and exits 2 unless it is a TPU.
"""

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def _timed_step(step, args, k1=5, k2=45):
    """Marginal per-call device time of a round step, by chain length.

    Dispatch a chain of k steps feeding params/agg_state forward, force
    one sync at the end, and report (t(k2) - t(k1)) / (k2 - k1) — the
    fixed per-fetch host latency cancels.
    """
    params0, agg0, key, adj, comp, ridx, d = args

    def run(k):
        t0 = time.perf_counter()
        p, a = params0, agg0
        for _ in range(k):
            p, a, _m = step(p, a, key, adj, comp, ridx, d)
        jax.device_get(jax.tree_util.tree_leaves(p)[0])
        return time.perf_counter() - t0

    run(2)  # warmup (compile hit + stream spin-up)
    t1 = run(k1)
    t2 = run(k2)
    return (t2 - t1) / (k2 - k1)


def _timed_eval(ev, params, d, k1=5, k2=45):
    """Marginal per-call device time of the eval sweep (same fixed-latency
    cancellation as _timed_step; calls serialize on the device)."""

    def run(k):
        t0 = time.perf_counter()
        m = None
        for _ in range(k):
            m = ev(params, d)
        jax.device_get(jax.tree_util.tree_leaves(m)[0])
        return time.perf_counter() - t0

    run(2)
    t1 = run(k1)
    t2 = run(k2)
    return (t2 - t1) / (k2 - k1)


def flagship_cfg(num_nodes: int = 20) -> dict:
    """The headline scenario at any scale; param_dtype stays on the auto
    default (factories.resolved_param_dtype: bf16 from 64 nodes up), so
    --nodes 256 measures the same configuration the north-star runs."""
    return {
        "experiment": {"name": "breakdown", "seed": 7, "rounds": 10},
        "topology": {"type": "k-regular", "num_nodes": num_nodes, "k": 4},
        "aggregation": {"algorithm": "krum", "params": {"num_compromised": 1}},
        "attack": {"enabled": True, "type": "gaussian", "percentage": 0.2,
                    "params": {"noise_std": 10.0}},
        "training": {"local_epochs": 1, "batch_size": 32, "lr": 0.05},
        "data": {
            "adapter": "synthetic",
            "params": {"num_samples": 160 * num_nodes,
                        "input_shape": [28, 28, 1], "num_classes": 62},
        },
        "model": {"factory": "examples.leaf.LEAFFEMNISTModel", "params": {}},
        "backend": "tpu",
        "tpu": {"num_devices": 1, "compute_dtype": "bfloat16"},
    }


FLAGSHIP_CFG = flagship_cfg()

# The probe-heavy scenario: evidential_trust on a 10-node fully-connected
# UCI-HAR-shaped network — every node cross-evaluates every broadcast state
# on its local probe batch (the reference's worst hot loop: one deepcopy +
# sequential forward sweep per neighbor per round,
# evidential_trust.py:236-260; here one batched [N, N] vmapped forward).
PROBE_CFG = {
    "experiment": {"name": "breakdown-probe", "seed": 7, "rounds": 10},
    "topology": {"type": "fully", "num_nodes": 10},
    "aggregation": {"algorithm": "evidential_trust",
                     "params": {"max_eval_samples": 64}},
    "attack": {"enabled": True, "type": "gaussian", "percentage": 0.2,
                "params": {"noise_std": 10.0}},
    "training": {"local_epochs": 1, "batch_size": 32, "lr": 0.05},
    "data": {
        "adapter": "wearables.uci_har",
        "params": {"num_samples": 160 * 10},
    },
    "model": {"factory": "wearables.uci_har", "params": {}},
    "backend": "tpu",
    "tpu": {"num_devices": 1, "compute_dtype": "bfloat16"},
}


def build(algo: str, local_epochs: int, raw_cfg=None, compression=None,
          pipeline: bool = False, sparse_topology=None):
    from murmura_tpu.aggregation import build_aggregator
    from murmura_tpu.aggregation.base import AggregatorDef
    from murmura_tpu.config import Config
    from murmura_tpu.core.rounds import build_round_program
    from murmura_tpu.data.registry import build_federated_data
    from murmura_tpu.utils.factories import build_attack, resolve_model

    raw = dict(raw_cfg or FLAGSHIP_CFG)
    cfg = Config.model_validate(raw)
    n = cfg.topology.num_nodes
    data = build_federated_data(
        cfg.data.adapter, cfg.data.params, num_nodes=n, seed=7
    )
    model = resolve_model(cfg, data)
    # Sparse exchange mode (the pipeline cells' sparse-exponential
    # column): rules take the [k, N] edge-mask engine, the program's
    # adjacency input is the SparseTopology mask.
    sparse_params = {}
    offsets = None
    if sparse_topology is not None:
        offsets = tuple(sparse_topology.offsets)
        sparse_params = {
            "exchange_offsets": list(offsets), "sparse_exchange": True,
        }
    if algo == "passthrough":
        agg = AggregatorDef(
            name="passthrough",
            aggregate=lambda own, bcast, adj, r, state, ctx: (own, state, {}),
        )
    elif algo == "passthrough_bcast":
        # Returns the post-attack broadcast tensor so the attack transform
        # cannot be dead-code eliminated (unlike ``passthrough``).
        agg = AggregatorDef(
            name="passthrough_bcast",
            aggregate=lambda own, bcast, adj, r, state, ctx: (bcast, state, {}),
        )
    elif algo == "krum":
        agg = build_aggregator(
            algo,
            {"num_compromised": 1, "max_candidates": 5, **sparse_params},
        )
    else:
        agg = build_aggregator(
            algo, {**cfg.aggregation.params, **sparse_params},
            total_rounds=10,
        )
    attack = build_attack(cfg)
    probe_size = cfg.aggregation.params.get("max_eval_samples")
    program = build_round_program(
        model, agg, data,
        local_epochs=local_epochs, batch_size=32, lr=0.05, total_rounds=10,
        attack=attack, seed=7, probe_size=probe_size,
        compression=compression,
        sparse_offsets=offsets,
        pipeline=pipeline,
    )
    return program, attack


def _staleness_cells(nodes: int) -> dict:
    """Bounded-staleness cells (ISSUE 13; docs/ROBUSTNESS.md): the same
    krum scenario under a 30% straggler + 15% link-drop FaultSchedule,
    run drop-sync vs ``max_staleness`` in {1, 4}.  Each cell reports the
    amortized fused-dispatch ms/round (the chain-timing trick applied
    through ``rounds_per_dispatch`` — one dispatch per chunk, fixed
    fetch latency amortized), the final mean accuracy, and the
    PER-ROUND stale-edge counts so the manifest shows how much of the
    exchange actually ran from cache."""
    from murmura_tpu.config import Config
    from murmura_tpu.utils.factories import build_network_from_config

    rounds = 10
    cells = {}
    for name, exchange in (
        ("drop_sync", None),
        ("stale_1", {"max_staleness": 1}),
        ("stale_4", {"max_staleness": 4}),
    ):
        import copy

        raw = copy.deepcopy(flagship_cfg(nodes))
        raw["experiment"]["rounds"] = rounds
        raw["faults"] = {"enabled": True, "straggler_prob": 0.3,
                         "link_drop_prob": 0.15, "seed": 11}
        if exchange is not None:
            raw["exchange"] = exchange
        net = build_network_from_config(Config.model_validate(raw))
        # eval_every=1 keeps every round in history (the per-round
        # stale-edge counts ARE the deliverable); the in-scan eval cost
        # is identical across the three cells, so the ms deltas stay
        # attributable to the stale fold.  Warmup runs the SAME
        # (chunk, eval_every) fused program as the timed pass —
        # Network._fused_step caches compiled programs per chunk size,
        # so a different warmup chunk would leave the timed window
        # paying the full XLA compile.
        net.train(rounds=rounds, eval_every=1, rounds_per_dispatch=rounds)
        t0 = time.perf_counter()
        h = net.train(
            rounds=rounds, eval_every=1, rounds_per_dispatch=rounds
        )
        elapsed = time.perf_counter() - t0
        sched = net.fault_schedule
        # Host-side schedule view next to the in-jit observation: how
        # many senders the schedule itself kept from delivering each
        # timed round (in-jit sentinels can only veto further).
        nondeliv = [
            int((sched.delivering_at(r) < 1).sum())
            for r in range(rounds, 2 * rounds)
        ]
        cells[name] = {
            "ms_per_round": round(1e3 * elapsed / rounds, 3),
            "final_mean_accuracy": round(float(h["mean_accuracy"][-1]), 4),
            "scheduled_nondelivering_per_round": nondeliv,
            "stale_edges_per_round": [
                float(v) for v in h.get("agg_stale_used", [])[-rounds:]
            ],
            "stale_expired_per_round": [
                float(v) for v in h.get("agg_stale_expired", [])[-rounds:]
            ],
        }
    return {
        "config": "krum, 30% straggler + 15% link drop, "
                  f"{nodes}-node k-regular(4), fused dispatch with "
                  "per-round in-scan eval",
        "rounds": rounds,
        "cells": cells,
    }


def _pipeline_cells(nodes: int) -> dict:
    """Pipelined-rounds cells (ISSUE 14; docs/PERFORMANCE.md "Pipelined
    rounds"): the krum scenario serialized vs ``exchange.pipeline``, on
    the dense k-regular(4) graph AND the sparse exponential graph, with
    the int8+EF codec off and on.  Each cell times three per-round
    programs with the marginal chain method (``_timed_step``):

        train     — passthrough-bcast (local SGD + attack + codec, no
                    aggregation): the segment the pipeline hides behind;
        serialized — the full krum round (train THEN exchange+aggregate
                    on the critical path);
        pipelined — the same round with the delayed double-buffered
                    aggregation issued concurrently with training.

    ``hidden_fraction`` = (serialized - pipelined) / (serialized -
    train): 1.0 means the exchange+aggregate segment vanished from the
    critical path entirely, 0.0 means nothing was hidden.  Each cell
    carries its own platform stamp, XLA flop count and the derived MFU
    so the committed artifact records the MFU delta vs the serialized
    baseline per point.
    """
    from murmura_tpu.analysis.budgets import normalize_cost_analysis
    from murmura_tpu.topology.generators import create_topology

    from bench import _peak_flops

    device_kind = jax.devices()[0].device_kind
    peak = _peak_flops(device_kind)

    cells = {}
    for topo_name in ("dense", "sparse_exponential"):
        if topo_name == "dense":
            topo = create_topology(
                "k-regular", num_nodes=nodes, k=4, seed=12345
            )
            sparse_topo = None
            adj = jnp.asarray(topo.mask())
        else:
            sparse_topo = create_topology(
                "exponential", num_nodes=nodes, seed=12345
            )
            adj = jnp.asarray(sparse_topo.edge_mask(0))
        raw = flagship_cfg(nodes)
        if topo_name == "sparse_exponential":
            import copy

            raw = copy.deepcopy(raw)
            raw["topology"] = {"type": "exponential", "num_nodes": nodes}
        for codec_name, spec in (("codec_none", None), ("int8_ef", None)):
            if codec_name == "int8_ef":
                from murmura_tpu.ops.compress import CompressionSpec

                spec = CompressionSpec(
                    "int8", block=256, error_feedback=True
                )
            cell: dict = _platform_stamp()
            ms = {}
            for variant, algo, pipe in (
                ("train", "passthrough_bcast", False),
                ("serialized", "krum", False),
                ("pipelined", "krum", True),
            ):
                program, attack = build(
                    algo, 1, raw_cfg=raw, compression=spec,
                    pipeline=pipe, sparse_topology=sparse_topo,
                )
                step = jax.jit(program.train_step)
                d = {
                    k: jnp.asarray(v)
                    for k, v in program.data_arrays.items()
                }
                comp = jnp.asarray(attack.compromised.astype("float32"))
                args = (
                    program.init_params,
                    {
                        k: jnp.asarray(v)
                        for k, v in program.init_agg_state.items()
                    },
                    jax.random.PRNGKey(0), adj, comp,
                    jnp.asarray(0.0, jnp.float32), d,
                )
                ms[variant] = 1e3 * _timed_step(step, args)
                cell[f"{variant}_ms"] = round(ms[variant], 3)
                if algo == "krum":
                    cost = normalize_cost_analysis(
                        step.lower(*args).compile().cost_analysis()
                    )
                    flops = cost.get("flops")
                    cell[f"{variant}_flops"] = flops
                    if flops and ms[variant] > 0:
                        cell[f"{variant}_mfu"] = round(
                            flops / (ms[variant] / 1e3) / peak, 5
                        )
            seg = ms["serialized"] - ms["train"]
            cell["exchange_aggregate_segment_ms"] = round(seg, 3)
            if seg > 0:
                cell["hidden_fraction"] = round(
                    (ms["serialized"] - ms["pipelined"]) / seg, 4
                )
            if cell.get("serialized_mfu") and cell.get("pipelined_mfu"):
                cell["mfu_delta"] = round(
                    cell["pipelined_mfu"] - cell["serialized_mfu"], 5
                )
            cells[f"{topo_name}/{codec_name}"] = cell
    return {
        "config": f"krum serialized vs exchange.pipeline, {nodes} nodes, "
                  "dense k-regular(4) + sparse exponential, int8+EF "
                  "off/on; hidden_fraction = (serialized - pipelined) / "
                  "(serialized - train)",
        "acceptance": "exchange+aggregate segment >= 80% hidden behind "
                      "local training on TPU",
        "cells": cells,
    }


def main():
    from bench import require_chip
    from murmura_tpu.topology.generators import create_topology

    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20,
                    help="flagship scenario scale (256 = the north-star "
                         "shape; writes bench_breakdown_<N>node.json and "
                         "skips the 10-node probe scenario)")
    nodes = ap.parse_args().nodes
    require_chip("bench_breakdown")

    results = {}
    adj = None
    for name, algo, epochs in (
        ("overhead", "passthrough", 0),
        ("attack_e0", "passthrough_bcast", 0),
        ("passthrough_e1", "passthrough_bcast", 1),
        ("krum_e1", "krum", 1),
    ):
        program, attack = build(algo, epochs, raw_cfg=flagship_cfg(nodes))
        if adj is None:
            topo = create_topology("k-regular", num_nodes=nodes, k=4, seed=12345)
            adj = jnp.asarray(topo.mask())
            comp = jnp.asarray(attack.compromised.astype("float32"))
        step = jax.jit(program.train_step)
        d = {k: jnp.asarray(v) for k, v in program.data_arrays.items()}
        args = (
            program.init_params,
            {k: jnp.asarray(v) for k, v in program.init_agg_state.items()},
            jax.random.PRNGKey(0), adj, comp,
            jnp.asarray(0.0, jnp.float32), d,
        )
        t0 = time.perf_counter()
        results[name] = {"ms": round(1e3 * _timed_step(step, args), 3)}
        results[name]["compile_and_time_s"] = round(time.perf_counter() - t0, 1)
        if name == "krum_e1":
            ev = jax.jit(program.eval_step)
            results["eval"] = {
                "ms": round(1e3 * _timed_eval(ev, program.init_params, d), 3)
            }

    # Compressed-exchange deltas (ops/compress.py; ISSUE 7): the same
    # full krum round with the int8 / topk codec armed — (compressed
    # krum step) - (krum_e1) is the in-round cost (or saving: the codec
    # shrinks the aggregation's HBM reads) of quantize + dequantize +
    # error feedback, next to the analytic exchange-bytes column.
    from murmura_tpu.ops.compress import CompressionSpec

    model_dim = None
    for cname, spec in (
        ("krum_e1_int8", CompressionSpec(
            "int8", block=256, error_feedback=True)),
        ("krum_e1_topk", CompressionSpec(
            "topk", topk_ratio=0.05, error_feedback=True)),
    ):
        program, attack = build(
            "krum", 1, raw_cfg=flagship_cfg(nodes), compression=spec
        )
        model_dim = program.model_dim
        step = jax.jit(program.train_step)
        d = {k: jnp.asarray(v) for k, v in program.data_arrays.items()}
        args = (
            program.init_params,
            {k: jnp.asarray(v) for k, v in program.init_agg_state.items()},
            jax.random.PRNGKey(0), adj, comp,
            jnp.asarray(0.0, jnp.float32), d,
        )
        t0 = time.perf_counter()
        results[cname] = {
            "ms": round(1e3 * _timed_step(step, args), 3),
            "payload_bytes_per_edge": spec.payload_bytes(program.model_dim, 4),
        }
        results[cname]["compile_and_time_s"] = round(
            time.perf_counter() - t0, 1
        )

    seg = {
        "overhead_ms": results["overhead"]["ms"],
        "attack_ms": round(
            results["attack_e0"]["ms"] - results["overhead"]["ms"], 3
        ),
        "local_sgd_ms": round(
            results["passthrough_e1"]["ms"] - results["attack_e0"]["ms"], 3
        ),
        "krum_select_ms": round(
            results["krum_e1"]["ms"] - results["passthrough_e1"]["ms"], 3
        ),
        "eval_ms": results["eval"]["ms"],
        "full_round_ms": results["krum_e1"]["ms"],
        "compress_int8_delta_ms": round(
            results["krum_e1_int8"]["ms"] - results["krum_e1"]["ms"], 3
        ),
        "compress_topk_delta_ms": round(
            results["krum_e1_topk"]["ms"] - results["krum_e1"]["ms"], 3
        ),
        "exchange_payload_bytes": {
            "none": model_dim * 4,
            "int8": results["krum_e1_int8"]["payload_bytes_per_edge"],
            "topk": results["krum_e1_topk"]["payload_bytes_per_edge"],
        },
    }

    # Bounded-staleness cells (ISSUE 13): drop-sync baseline vs
    # max_staleness {1, 4} under a 30% straggler schedule, per-round
    # stale-edge counts committed in the manifest.
    stale_section = _staleness_cells(nodes)

    # Pipelined-rounds cells (ISSUE 14): serialized vs exchange.pipeline
    # with per-segment hidden fraction and the MFU delta.
    pipeline_section = _pipeline_cells(nodes)

    if nodes != 20:
        # Scale runs measure only the flagship segments; the probe
        # scenario is scale-independent (its own 10-node config).
        blob = {
            **_platform_stamp(),
            "num_nodes": nodes,
            "segments": seg,
            "staleness": stale_section,
            "pipeline": pipeline_section,
            "raw": results,
        }
        out = f"bench_breakdown_{nodes}node.json"
        _write_artifact(f"bench_breakdown_{nodes}node", blob, out)
        print(json.dumps(blob))
        return

    # Probe-heavy scenario: the same passthrough-vs-full difference
    # isolates the N x N cross-eval + trust update (the design's biggest
    # win over the reference's per-neighbor deepcopy loop).
    probe_results = {}
    for name, algo, epochs in (
        ("passthrough_e1", "passthrough_bcast", 1),
        ("evidential_e1", "evidential_trust", 1),
    ):
        program, attack = build(algo, epochs, PROBE_CFG)
        topo = create_topology("fully", num_nodes=10, seed=12345)
        p_adj = jnp.asarray(topo.mask())
        p_comp = jnp.asarray(attack.compromised.astype("float32"))
        step = jax.jit(program.train_step)
        d = {k: jnp.asarray(v) for k, v in program.data_arrays.items()}
        args = (
            program.init_params,
            {k: jnp.asarray(v) for k, v in program.init_agg_state.items()},
            jax.random.PRNGKey(0), p_adj, p_comp,
            jnp.asarray(0.0, jnp.float32), d,
        )
        t0 = time.perf_counter()
        probe_results[name] = {"ms": round(1e3 * _timed_step(step, args), 3)}
        probe_results[name]["compile_and_time_s"] = round(
            time.perf_counter() - t0, 1
        )
        if name == "evidential_e1":
            ev = jax.jit(program.eval_step)
            probe_results["eval"] = {
                "ms": round(1e3 * _timed_eval(ev, program.init_params, d), 3)
            }
    probe_seg = {
        "cross_eval_trust_ms": round(
            probe_results["evidential_e1"]["ms"]
            - probe_results["passthrough_e1"]["ms"], 3
        ),
        "eval_ms": probe_results["eval"]["ms"],
        "full_round_ms": probe_results["evidential_e1"]["ms"],
    }

    blob = {
        **_platform_stamp(),
        "segments": seg,
        "staleness": stale_section,
        "pipeline": pipeline_section,
        "probe_scenario": {
            "config": "evidential_trust, 10-node fully, UCI-HAR-shaped, "
                       "max_eval_samples=64",
            "segments": probe_seg,
        },
        "raw": results,
        "raw_probe": probe_results,
    }
    _write_artifact("bench_breakdown", blob, "bench_breakdown.json")
    print(json.dumps(blob))


def _platform_stamp() -> dict:
    """The device every bench JSON ran on, as JAX reports it in the
    measuring process."""
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def _write_artifact(name: str, blob: dict, legacy_name: str) -> None:
    """Bench output through the one telemetry schema (docs/OBSERVABILITY.md):
    the canonical artifact is a ``kind: bench`` manifest under
    telemetry_runs/<name>/; the historical filename at the repo root stays
    as a duplicated view of the same payload for one release."""
    from murmura_tpu.telemetry.writer import write_bench_manifest

    here = Path(__file__).parent
    write_bench_manifest(
        here / "telemetry_runs" / name, name, blob,
        legacy_path=here / legacy_name,
    )


if __name__ == "__main__":
    main()
