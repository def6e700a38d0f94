"""The harness end to end on the CPU at a tiny size.  The look for a chip
is the command's (``benchmark/run.py``); these tests call what follows it.
"""

import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.cells import Cell

from bench_tiny import REPO, make_root

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def timed_run(root):
    return harness.run_cell(Cell("tiny_sketchguard", root=root), seed=2**31 + 5,
                            seconds=1.0, trace=False)


def test_result_object_keeps_the_contract(timed_run, root):
    r = timed_run
    assert list(r)[-1] == "checks" and r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"round_ms", "round_ms_p95", "setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"] in ("ms", "s")
    window = r["window"]
    assert r["metrics"]["round_ms"]["value"] == window["round_ms"]
    assert window["calls"] * 2 == r["attempted"] and window["median_round_ms"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    limits = Cell("tiny_sketchguard", root=root).job["correct"]["limits"]
    assert set(r["checks"]) == set(limits) | {"window_compiles", "inputs_off"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    json.loads(json.dumps(r))


@pytest.mark.parametrize("name,seed", [("tiny_sketchguard_kreg", 7),
                                       ("tiny_sketchguard_kreg", 2**31 + 99)])
def test_another_graph_follows_its_reference(root, name, seed):
    r = harness.run_cell(Cell(name, root=root), seed=seed, seconds=0.5, trace=False)
    assert r["correct"] is True, r["checks"]
    # On the CPU in float32 the reference follows the program closely.
    assert r["checks"]["loss"]["value"] < 1e-3
    assert r["checks"]["change"]["value"] < 1e-2


def test_traced_run_reports_what_its_readers_find(root, monkeypatch):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: PEAKS)
    r = harness.run_cell(Cell("tiny_sketchguard", root=root), seed=9, seconds=0.5,
                         trace=True)
    # No device plane in a CPU trace: the trace readers find nothing and
    # their metrics are left out, never reported as 0.
    assert {"setup_build_s", "setup_compile_s", "train_mfu", "peak_hbm_gib"} <= set(
        r["metrics"]
    )
    for absent in ("train_scope_ms", "aggregate_roofline", "device_idle_pct",
                   "host_gap_ms", "train_leaf_ops_ms"):
        assert absent not in r["metrics"]
    assert "busy_s" in r["device"] and "breakdown" in r
    assert r["correct"] is True


def _broken(monkeypatch, breakage):
    """Build the cell's network as always, then break the timed path
    underneath the harness."""
    build = harness.build

    def broken_build(cell, seed):
        network = build(cell, seed)
        breakage(network)
        return network

    monkeypatch.setattr(harness, "build", broken_build)


def _state_unchanged(network):
    step = network._step

    def frozen(params, agg_state, *rest):
        import jax
        import jax.numpy as jnp

        # The step donates what it is given: keep copies to hand back.
        kept = jax.tree_util.tree_map(jnp.copy, (params, agg_state))
        _, _, metrics = step(params, agg_state, *rest)
        return (*kept, metrics)

    network._step = frozen


def _half_batch(network):
    step = network._step

    def halved(*args):
        data = dict(args[-1])
        data["eff_batch"] = data["eff_batch"] // 2
        return step(*args[:-1], data)

    network._step = halved


def _eval_altered(network):
    evaluate = network._eval

    def altered(*args):
        return {k: v * 1.001 for k, v in evaluate(*args).items()}

    network._eval = altered


def _no_exchange(network):
    """Every node keeps what it trained: no node has a neighbour to take
    anything from."""
    import jax.numpy as jnp

    step = network._step

    def alone(params, agg_state, key, adj, *rest):
        return step(params, agg_state, key, jnp.zeros_like(adj), *rest)

    network._step = alone


@pytest.mark.parametrize("breakage,caught_by", [
    (_state_unchanged, "first_update"), (_half_batch, "first_update"),
    (_eval_altered, "eval_loss"), (_no_exchange, "change"),
])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, breakage, caught_by):
    _broken(monkeypatch, breakage)
    r = harness.run_cell(Cell("tiny_sketchguard", root=root), seed=11, seconds=0.3,
                         trace=False)
    assert r["correct"] is False
    assert r["checks"][caught_by]["value"] > r["checks"][caught_by]["limit"]


def test_weights_that_are_not_the_benchmarks_are_not_correct(root, monkeypatch):
    from benchmark import inputs

    place = inputs.place

    def keep_the_programs(network, cell, seed):
        kept = network.params
        place(network, cell, seed)
        network.params = kept

    monkeypatch.setattr(inputs, "place", keep_the_programs)
    r = harness.run_cell(Cell("tiny_sketchguard", root=root), seed=11, seconds=0.3,
                         trace=False)
    assert r["correct"] is False


@pytest.mark.parametrize("name,seed", [("tiny_sketchguard", 7),
                                       ("tiny_sketchguard_kreg", 3)])
def test_the_lower_precision_control_fails(root, name, seed):
    """The reference put in the program's place, computed in the nearest
    precision below the stated one, is not correct by the cell's limits;
    put there as it is, it is.  (A seed on which this size separates the
    two by three times or more: a few thousand parameters are noisier than
    the cells' 6.6 million.)"""
    from benchmark.reference import round as ref_round
    from benchmark.study import LOWER_PRECISION

    cell = Cell(name, root=root)
    from benchmark import inputs as cell_inputs

    network = harness.build(cell, seed)
    cell_inputs.place(network, cell, seed)
    inputs = cell_inputs.read(network, cell, seed)
    job = harness.reference_job(cell, inputs)
    harness.free(network)
    cell_inputs.draw_again(inputs, cell)
    rounds = int(cell.job["correct"]["rounds"])
    limits = cell.job["correct"]["limits"]
    stated = ref_round.run(inputs, job, rounds=rounds, keep_first=True)
    same = harness.compare(stated, stated, inputs, job)
    assert all(same[k] <= 1e-4 for k in limits), same
    lower = dataclasses.replace(job, compute_dtype=LOWER_PRECISION[job.compute_dtype])
    control = ref_round.run(inputs, lower, rounds=rounds, keep_first=True)
    numbers = harness.compare(control, stated, inputs, job)
    assert any(numbers[k] > limits[k] for k in limits), (numbers, limits)


def test_no_chip_no_result():
    """The command itself: without a TPU it exits 2 and prints no result."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "cnn_sketchguard_er_n64",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(REPO)},
    )
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr


def test_a_stall_moves_round_ms_and_not_the_median_call():
    """One planted call of ten times the length among 300 alike: the
    end-to-end ``round_ms`` is taken over all the time of the window and
    moves; the median call, printed beside it, says it was a stall."""
    rng = np.random.default_rng(3)
    calls = list(0.129 + 0.0002 * rng.standard_normal(300))
    quiet = harness.window_numbers(calls, 1, sum(calls))
    stalled = calls[:140] + [1.29] + calls[141:]
    hit = harness.window_numbers(stalled, 1, sum(stalled))
    assert hit["round_ms"] == pytest.approx(sum(stalled) / 300 * 1e3, rel=1e-12)
    assert hit["round_ms"] > 1.025 * quiet["round_ms"]
    assert hit["median_round_ms"] == pytest.approx(quiet["median_round_ms"], rel=1e-4)
    assert hit["round_ms_p95"] == pytest.approx(quiet["round_ms_p95"], rel=2e-3)
    assert quiet["median_round_ms"] == pytest.approx(quiet["round_ms"], rel=1e-3)
    assert (quiet["stall_calls"], quiet["stall_s"]) == (0, 0.0)
    assert hit["stall_calls"] == 1 and hit["longest"][0] == [140, 1.29]
    assert hit["stall_s"] == pytest.approx(1.29 - 0.129, rel=1e-2)
    # The time between the calls is the window's too.
    gaps = harness.window_numbers(calls, 1, sum(calls) + 0.3)
    assert gaps["round_ms"] == pytest.approx(quiet["round_ms"] + 1.0, rel=1e-9)
    assert gaps["median_round_ms"] == quiet["median_round_ms"]
    # A chunk of several rounds a call: every number is per round.
    pairs = harness.window_numbers([2 * c for c in calls], 2, 2 * sum(calls))
    for k in ("round_ms", "round_ms_p95", "median_round_ms"):
        assert pairs[k] == pytest.approx(quiet[k], rel=1e-12)


def test_samples_per_round_counts_the_honest_nodes_batches():
    inputs = {"data": {"steps": np.array([4, 4, 3]), "eff_batch": np.array([8, 8, 8])},
              "compromised": np.array([0.0, 1.0, 0.0])}
    assert harness.samples_per_round(inputs, local_epochs=2) == (32 + 24) * 2


def test_worst_leaf_gap_measures_against_the_larger_norm():
    want = {"a": 1.0, "b": 10.0, "c": 1e-6}
    got = {"a": 1.1, "b": 10.0, "c": 2e-6}
    # a: 0.1 / max(1, median 1); c: 1e-6 / max(1e-6, 1) is nothing.
    assert harness.worst_leaf_gap(got, want) == pytest.approx(0.1)
    # One leaf against its own norm, however small.
    assert harness.leaf_gap(got, want, "c") == pytest.approx(1.0)
    assert harness.p95(list(range(101))) == pytest.approx(95.0)
