"""What the next ``model_config`` PR does, as a test lays it out: a
temporary copy of the tree's ``benchmark/`` and, laid beside its files with
none of them edited, a token model's configuration (the program's
char-LSTM, ``leaf.shakespeare``: the one model the program has that takes
ids), its operation counts, its plain reference, a traffic file, a
per-layer metric on the innermost-table reader, and the extended
``BENCHMARK.json``."""

import json
import shutil
from pathlib import Path

from bench_tiny import BENCH, REPO

SIZES = {"vocab_size": 24, "embed_dim": 8, "hidden": 16, "num_layers": 2,
         "seq_len": 12}

CONFIG = {
    "name": "tiny_char_lstm",
    "source": "https://arxiv.org/abs/1812.01097",
    "source_file": "LEAF models/shakespeare/stacked_lstm.py",
    "model": {"factory": "leaf.shakespeare", "params": SIZES},
    "reference": "char_lstm",
    "flops": "char_lstm",
    "loss": "label",
    **SIZES,
    "compute_dtype": "float32",
    "param_dtype": None,
    "data": {
        "adapter": "synthetic_sequences",
        "generator": "tokens",
        "samples_per_node": 40,
        "held_out_per_node": 8,
        "seq_len": SIZES["seq_len"],
        "vocab_size": SIZES["vocab_size"],
        "targets": "last",
        "zipf_exponent": 1.0,
        "dependence": 0.6,
        "params": {"seq_len": SIZES["seq_len"], "vocab_size": SIZES["vocab_size"]},
    },
    "reduced": [],
    "assumed": ["a test's sizes"],
}

REFERENCE = '''"""Plain reference of LEAF's stacked char-LSTM: embedding, LSTM layers with
the gates packed [i, f, g, o] and a forget bias of 1, a dense layer on the
last position's state."""

import jax
import jax.numpy as jnp

from benchmark.reference.precision import matmul


def init(key, doc):
    layers, hidden = doc["num_layers"], doc["hidden"]
    keys = jax.random.split(key, layers + 2)
    uniform = lambda k, shape, fan: jax.random.uniform(
        k, shape, jnp.float32, -fan ** -0.5, fan ** -0.5)
    cells, width = [], doc["embed_dim"]
    for l in range(layers):
        ki, kh = jax.random.split(keys[1 + l])
        cells.append({"wi": uniform(ki, (width, 4 * hidden), hidden),
                      "wh": uniform(kh, (hidden, 4 * hidden), hidden),
                      "b": jnp.zeros((4 * hidden,), jnp.float32)})
        width = hidden
    kw, kb = jax.random.split(keys[-1])
    return {
        "embed": 0.1 * jax.random.normal(
            keys[0], (doc["vocab_size"], doc["embed_dim"]), jnp.float32),
        "cells": cells,
        "out": {"w": uniform(kw, (hidden, doc["vocab_size"]), hidden),
                "b": uniform(kb, (doc["vocab_size"],), hidden)},
    }


def apply(params, x, dtype):
    f32 = lambda t: t.astype(jnp.float32)
    steps = jnp.swapaxes(f32(params["embed"])[x], 0, 1)  # [T, B, E]
    hidden = params["cells"][0]["wh"].shape[0]
    zeros = jnp.zeros((x.shape[0], hidden), jnp.float32)

    def step(carry, inp):
        out = []
        for cell, (h, c) in zip(params["cells"], carry):
            z = (matmul(inp, cell["wi"], dtype) + matmul(h, cell["wh"], dtype)
                 + f32(cell["b"]))
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            inp = jax.nn.sigmoid(o) * jnp.tanh(c)
            out.append((inp, c))
        return tuple(out), None

    carry, _ = jax.lax.scan(step, tuple((zeros, zeros) for _ in params["cells"]), steps)
    return matmul(carry[-1][0], params["out"]["w"], dtype) + f32(params["out"]["b"])
'''

FLOPS = '''"""Operations of the stacked char-LSTM from its sizes: two matmuls a layer
and position, and the last dense layer."""


def forward_flops_per_sample(doc):
    total, width = 0.0, doc["embed_dim"]
    for _ in range(doc["num_layers"]):
        total += 2.0 * doc["seq_len"] * (width + doc["hidden"]) * 4 * doc["hidden"]
        width = doc["hidden"]
    return total + 2.0 * doc["hidden"] * doc["vocab_size"]


def train_flops_per_sample(doc):
    return 3.0 * forward_flops_per_sample(doc)


def parameter_count(doc):
    total, width = doc["vocab_size"] * doc["embed_dim"], doc["embed_dim"]
    for _ in range(doc["num_layers"]):
        total += (width + doc["hidden"] + 1) * 4 * doc["hidden"]
        width = doc["hidden"]
    return total + (doc["hidden"] + 1) * doc["vocab_size"]
'''

LAYER_METRIC = {
    "layer": "round program", "unit": "ms", "moves": "round_ms",
    "reader": "leaf_scope_ms",
    "args": {"chain": "murmura.train/murmura.recurrence"},
}


def lay_out(tmp: Path) -> Path:
    """The copy with the added files; returns its root."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    job = json.loads((BENCH / "workloads" / "sketchguard_er_n64.json").read_text())
    job["topology"].update(num_nodes=8, p=0.6)
    job["training"]["batch_size"] = 8
    job["correct"].update(node_block=4, limits={
        "loss": 1e-3, "eval_loss": 1e-5, "first_update": 0.1, "change": 0.1})
    job["trace_rounds"] = 2
    added = {
        "configs/tiny_char_lstm.json": json.dumps(CONFIG, indent=1),
        "configs/char_lstm.py": FLOPS,
        "reference/char_lstm.py": REFERENCE,
        "workloads/tiny_tokens_er.json": json.dumps(job, indent=1),
        "layer_metrics/recurrence_ms.json": json.dumps(LAYER_METRIC, indent=1),
    }
    for name, text in added.items():
        path = root / "benchmark" / name
        assert not path.exists(), name
        path.write_text(text)
    bench["configs"].append({
        "name": "tiny_char_lstm", "source": CONFIG["source"],
        "file": "benchmark/configs/tiny_char_lstm.json", "reduced": [],
        "why": "the program's char-LSTM at a test's sizes"})
    bench["workloads"].append({
        "name": "lstm_tokens_er_n8", "config": "tiny_char_lstm",
        "traffic": "tiny_tokens_er", "chips": 1, "why": "a test's cell"})
    bench["per_layer"].append({
        "name": "recurrence_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "round program", "moves": "round_ms",
        "workloads": ["lstm_tokens_er_n8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
