"""The cell's inputs: drawn by the benchmark from the seed, placed into the
built network, drawn again for the reference; the graph and the compromised
set held to what the workload file states."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, inputs
from benchmark.cells import Cell

from bench_tiny import BENCH, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def placed(root):
    cell = Cell("tiny_sketchguard", root=root)
    network = harness.build(cell, 2**31 - 5)
    before = jax.device_get(network.params)
    inputs.place(network, cell, 2**31 - 5)
    read = inputs.read(network, cell, 2**31 - 5)
    state = jax.device_get((network.params, dict(network._data)))
    inputs.draw_again(read, cell)
    return cell, before, state, read


def test_the_reference_draws_what_the_network_was_given(placed):
    cell, before, (params, data), read = placed
    for a, b, c in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (params, read["params"], before))):
        assert a.dtype == b.dtype and np.array_equal(a, b)
        assert a.shape == c.shape and not np.array_equal(a, c)
    for k in inputs.DATA_KEYS:
        assert np.array_equal(np.asarray(data[k]), read["data"][k]), k
    assert inputs.problems(read, cell) == []


def test_every_node_and_every_sample_differs(placed):
    _, _, (params, data), _ = placed
    w = np.asarray(params["fcs"][0]["w"])
    assert len({row.tobytes() for row in w.reshape(w.shape[0], -1)}) == w.shape[0]
    x = np.asarray(data["x"]).reshape(-1, 28 * 28)
    assert len({row.tobytes() for row in x}) == x.shape[0]
    assert set(np.unique(data["y"])) <= set(range(62))
    bound = (28 * 28 * 16 // 16) ** -0.5  # fan_in of the first dense layer: 7*7*16
    assert np.abs(w).max() <= bound and np.abs(w).max() > 0.99 * bound


def test_another_seed_draws_other_inputs(root):
    doc = Cell("tiny_sketchguard", root=root).config
    a, b = (inputs.make_data(doc, 4, seed) for seed in (1, 2))
    assert not np.array_equal(a["x"], b["x"])
    again = inputs.make_data(doc, 4, 1)
    assert np.array_equal(a["x"], again["x"]) and np.array_equal(a["y"], again["y"])


def test_the_references_tree_is_the_programs():
    from murmura_tpu.models.registry import build_model

    doc = json.loads((BENCH / "configs" / "femnist_cnn.json").read_text())
    module = __import__("benchmark.reference.femnist_cnn", fromlist=["init"])
    mine = jax.eval_shape(lambda k: module.init(k, doc), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        build_model(doc["model"]["factory"], {}).init, jax.random.PRNGKey(0)
    )
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    assert [l.shape for l in jax.tree_util.tree_leaves(mine)] == [
        l.shape for l in jax.tree_util.tree_leaves(theirs)]
    assert sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(mine)) == \
        doc["num_parameters"]


def _cut_an_edge(read):
    read["adjacency"][0, np.flatnonzero(read["adjacency"][0])[0]] = 0.0


def _empty_graph(read):
    read["adjacency"][:] = 0.0


def _dense_graph(read):
    n = read["adjacency"].shape[0]
    read["adjacency"] = np.ones((n, n), np.float32) - np.eye(n, dtype=np.float32)


def _no_attackers(read):
    read["compromised"][:] = 0.0


def _too_many_attackers(read):
    read["compromised"][:8] = 1.0


def _short_batches(read):
    read["data"]["eff_batch"] = read["data"]["eff_batch"] // 2


def _masked_samples(read):
    read["data"]["mask"] = read["data"]["mask"].copy()
    read["data"]["mask"][0, -1] = 0.0


@pytest.mark.parametrize("spoil,said", [
    (_cut_an_edge, "not symmetric"), (_empty_graph, "no neighbour"),
    (_dense_graph, "edges, not"), (_no_attackers, "compromised nodes, not"),
    (_too_many_attackers, "compromised nodes, not"),
    (_short_batches, "in batches of"), (_masked_samples, "trained and"),
])
def test_inputs_that_are_not_as_the_files_state(placed, spoil, said):
    cell, _, _, read = placed
    spoiled = {**read, "adjacency": read["adjacency"].copy(),
               "compromised": read["compromised"].copy(), "data": dict(read["data"])}
    spoil(spoiled)
    lines = inputs.problems(spoiled, cell)
    assert any(said in line for line in lines), lines


def test_a_regular_graph_is_held_to_its_degree(root):
    cell = Cell("tiny_sketchguard_kreg", root=root)
    network = harness.build(cell, 3)
    inputs.place(network, cell, 3)
    read = inputs.read(network, cell, 3)
    assert inputs.problems(read, cell) == []
    i, j = np.argwhere(read["adjacency"] == 0)[1]
    read["adjacency"][i, j] = read["adjacency"][j, i] = 1.0
    assert any("not all 4" in line for line in inputs.problems(read, cell))
