"""256-node per-rule chip validation: does each aggregation rule's
north-star-scale program compile and run on ONE v5e chip, and at what
rate?

The krum number is bench.py's `north_star_256node`; this harness covers
the rest of the rule space at the same scale (the round-5 memory work:
P-chunked circulant kernels, the Gram-path geometric median, the
backend-aware probe shifts).  Known-infeasible combinations are listed
as such rather than skipped silently.

Writes bench_rules_256.json (appends nothing; full rewrite per run).
Chip or fail: exits 2 unless ``jax.devices()`` is a TPU.  A rule that does
not fit or run is recorded as such and the script then exits non-zero.
"""

import json
import time
from pathlib import Path

CASES = [
    # (rule, params, exchange) — exchange chosen per the round-5
    # measurements: dense allgather wins on a single chip for the
    # matmul-friendly rules; ppermute validates the chunked roll paths.
    ("geometric_median", {}, "allgather"),
    ("ubar", {"rho": 0.6}, "ppermute"),
    ("median", {}, "ppermute"),
    ("trimmed_mean", {"trim_ratio": 0.2}, "ppermute"),
    ("median", {}, "allgather"),
    ("trimmed_mean", {"trim_ratio": 0.2}, "allgather"),
    ("balance", {"gamma": 1.5}, "ppermute"),
    ("sketchguard", {"sketch_size": 1024}, "ppermute"),
    ("evidential_trust", {}, "ppermute"),
]


def cfg(algo, params, exchange):
    from murmura_tpu.config import Config

    raw = {
        "experiment": {"name": f"ns-{algo}", "seed": 7, "rounds": 4},
        "topology": {"type": "k-regular", "num_nodes": 256, "k": 4},
        "aggregation": {"algorithm": algo, "params": dict(params)},
        "attack": {"enabled": True, "type": "gaussian", "percentage": 0.2,
                    "params": {"noise_std": 10.0}},
        "training": {"local_epochs": 1, "batch_size": 32, "lr": 0.05},
        "data": {"adapter": "synthetic", "params": {
            "num_samples": 160 * 256, "input_shape": [28, 28, 1],
            "num_classes": 62}},
        "model": {"factory": "examples.leaf.LEAFFEMNISTModel", "params": {}},
        "backend": "tpu",
        "tpu": {"num_devices": 1, "compute_dtype": "bfloat16",
                 "param_dtype": "bfloat16", "exchange": exchange},
    }
    if algo == "evidential_trust":
        raw["model"]["params"] = {"evidential": True}
    return Config.model_validate(raw)


def main():
    import jax

    from bench import require_chip
    from murmura_tpu.utils.factories import build_network_from_config

    device = require_chip("bench_rules_256")
    results = {}
    for algo, params, exch in CASES:
        tag = f"{algo}/{exch}"
        net = None
        try:
            t0 = time.time()
            net = build_network_from_config(cfg(algo, params, exch))
            net.train(rounds=2, eval_every=2, rounds_per_dispatch=2)
            compile_s = round(time.time() - t0, 1)
            t0 = time.time()
            net.train(rounds=4, eval_every=4, rounds_per_dispatch=4)
            e = time.time() - t0
            results[tag] = {
                "ok": True,
                "compile_plus_2rounds_s": compile_s,
                "rounds_per_sec": round(4 / e, 3),
                "round_ms": round(e / 4 * 1e3, 1),
            }
        except Exception as ex:  # noqa: BLE001
            results[tag] = {
                "ok": False,
                "error": f"{type(ex).__name__}: {str(ex)[:300]}",
            }
        finally:
            # Drop the network's resident [256, 6.6M] state before the
            # next case builds; two cases' buffers would not fit together.
            net = None
        print(tag, results[tag], flush=True)

    blob = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "nodes": 256,
        "results": results,
    }
    Path(__file__).with_name("bench_rules_256.json").write_text(
        json.dumps(blob, indent=2) + "\n"
    )
    print(json.dumps({k: v.get("rounds_per_sec", "FAIL")
                      for k, v in results.items()}))
    if not all(v["ok"] for v in results.values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
