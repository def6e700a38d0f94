"""A cell's inputs, made by the benchmark from ``--seed``.

The initial weights and the data are drawn here, on the device, each in one
jitted call, and placed into the built network in the program's own
layout; after the window the reference draws them again from the same seed
(the same compiled program on the same device gives the same bits), so it
takes no value that the program has made.  The graph and the compromised
set are the program's to make (the round step takes the first from its
topology every round and compiles the second in); they are read off the
built network and held to what the workload file states (``problems``).

Weights: the configuration's plain reference draws one node's
(``reference/<model>.py init``); every node gets its own key.  Data: the
generator the configuration's ``data.generator`` names
(``data/<generator>.py make``: ``clusters``, labelled float clusters, or
``tokens``, integer id sequences), the first ``train`` samples of a node
trained on and the rest held out.
"""

import importlib
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.data import stream_key

DATA_KEYS = ("x", "y", "mask", "num_samples", "eff_batch", "steps", "eval_x",
             "eval_y", "eval_mask", "probe_x", "probe_y", "probe_mask")


def make_params(model: str, doc: dict, n: int, seed: int, dtype) -> Any:
    """Stacked initial parameters [n, ...] in the resident ``dtype``."""
    init = importlib.import_module(f"benchmark.reference.{model}").init

    @jax.jit
    def draw(keys):
        tree = jax.vmap(lambda k: init(k, doc))(keys)
        return jax.tree_util.tree_map(lambda l: l.astype(dtype), tree)

    return draw(jax.random.split(stream_key(seed, 1), n))


def make_data(doc: dict, n: int, seed: int) -> Dict[str, Any]:
    """``x, y`` [n, train, ...] and ``eval_x, eval_y`` [n, held, ...] by the
    generator the configuration names."""
    generator = importlib.import_module(f"benchmark.data.{doc['data']['generator']}")
    return generator.make(doc, n, seed)


def _like(new, old):
    """``new`` where ``old`` was: its shape, its dtype, its sharding."""
    if new.shape != old.shape:
        raise ValueError(
            f"the benchmark's input has shape {new.shape}, the program's {old.shape}"
        )
    integers = [jnp.issubdtype(a.dtype, jnp.integer) for a in (new, old)]
    if integers[0] != integers[1]:
        raise ValueError(
            f"the benchmark's input is {new.dtype}, the program's {old.dtype}: "
            "ids are not cast to floats, nor floats to ids"
        )
    return jax.device_put(new.astype(old.dtype), old.sharding)


def place(network, cell, seed: int) -> None:
    """Put the benchmark's own weights and data where the build put the
    program's.  The program's are dropped first, so the draw adds nothing
    to the memory peak."""
    old = network.params
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(old)[0]]
    leaves, treedef = jax.tree_util.tree_flatten(old)
    like = [jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=l.sharding) for l in leaves]
    n, dtype = leaves[0].shape[0], leaves[0].dtype
    network.params = None
    del old, leaves
    new = make_params(cell.config["reference"], cell.config, n, seed, dtype)
    new_paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(new)[0]]
    if new_paths != paths:
        raise ValueError(
            f"the reference's parameter tree {new_paths} is not the program's {paths}"
        )
    network.params = jax.tree_util.tree_unflatten(
        treedef, [_like(a, b) for a, b in zip(jax.tree_util.tree_leaves(new), like)]
    )
    made = make_data(cell.config, n, seed)
    data = dict(network._data)
    probe = data["probe_x"].shape[1]
    made["probe_x"], made["probe_y"] = made["x"][:, :probe], made["y"][:, :probe]
    for k, v in made.items():
        data[k] = _like(v, data[k])
    network._data = data


def read(network, cell, seed: int) -> Dict[str, Any]:
    """The inputs as the reference takes them, on the host: the weights and
    the data drawn again from the seed, the graph, the compromised set and
    the batch layout read off the built network."""
    small = {k: np.asarray(network._data[k]) for k in DATA_KEYS
             if k not in ("x", "y", "eval_x", "eval_y", "probe_x", "probe_y")}
    x = network._data["x"]
    return {
        "seed": int(seed),
        # Positions of a sample: the ids of a token sequence [n, samples, T].
        "positions": int(x.shape[2])
        if x.ndim == 3 and jnp.issubdtype(x.dtype, jnp.integer) else 0,
        "adjacency": np.asarray(network.topology.mask(), np.float32),
        "compromised": np.asarray(network.compromised, np.float32),
        "data": small,
        "param_dtype": str(jax.tree_util.tree_leaves(network.params)[0].dtype),
    }


def draw_again(inputs: Dict[str, Any], cell) -> None:
    """Fill in the weights and the data, drawn again from the seed (after
    the window: they take device memory while they are drawn)."""
    n = inputs["adjacency"].shape[0]
    inputs["params"] = jax.device_get(make_params(
        cell.config["reference"], cell.config, n, inputs["seed"],
        jnp.dtype(inputs["param_dtype"]),
    ))
    made = jax.device_get(make_data(cell.config, n, inputs["seed"]))
    probe = inputs["data"]["probe_mask"].shape[1]
    made["probe_x"], made["probe_y"] = made["x"][:, :probe], made["y"][:, :probe]
    inputs["data"] = {**inputs["data"], **made}


def problems(inputs: Dict[str, Any], cell) -> List[str]:
    """What the built network's inputs have that the cell's files do not
    state: each a line; none where the build is as the files say."""
    out = []
    job, doc = cell.job, cell.config
    topo = job["topology"]
    n = int(topo["num_nodes"])
    adj = np.asarray(inputs["adjacency"])
    if adj.shape != (n, n):
        return [f"adjacency {adj.shape}, not {(n, n)}"]
    if not np.isin(adj, (0.0, 1.0)).all() or np.diag(adj).any():
        out.append("adjacency is not 0/1 with an empty diagonal")
    if not np.array_equal(adj, adj.T):
        out.append("adjacency is not symmetric")
    degree = adj.sum(axis=1)
    if (degree == 0).any():
        out.append(f"{int((degree == 0).sum())} nodes have no neighbour")
    if topo["type"] == "k-regular" and not (degree == int(topo["k"])).all():
        out.append(f"degrees {sorted(set(degree.tolist()))}, not all {topo['k']}")
    if topo["type"] == "erdos":
        pairs, p = n * (n - 1) / 2, float(topo["p"])
        edges, spread = adj.sum() / 2, (pairs * p * (1 - p)) ** 0.5
        if abs(edges - pairs * p) > 4 * spread:
            out.append(f"{edges:.0f} edges, not {pairs * p:.0f} +- {4 * spread:.0f}")
    attack = job.get("attack") or {}
    share = float(attack.get("percentage", 0.0)) if attack.get("enabled") else 0.0
    comp = np.asarray(inputs["compromised"])
    if comp.shape != (n,) or not np.isin(comp, (0.0, 1.0)).all():
        out.append("the compromised set is not a 0/1 vector over the nodes")
    elif abs(comp.sum() - share * n) >= 1:
        out.append(f"{int(comp.sum())} compromised nodes, not {share * n:.1f}")
    data = inputs["data"]
    held = int(doc["data"]["held_out_per_node"])
    train = int(doc["data"]["samples_per_node"]) - held
    batch = int(job["training"]["batch_size"])
    want = {
        "mask": np.ones((n, train)), "eval_mask": np.ones((n, held)),
        "num_samples": np.full(n, train), "eff_batch": np.full(n, min(batch, train)),
        "steps": np.full(n, max(train // batch, 1)),
    }
    for k, v in want.items():
        if not np.array_equal(np.asarray(data[k]), v):
            out.append(f"data[{k!r}] is not {train} trained and {held} held-out "
                       f"samples a node in batches of {batch}")
    return out
