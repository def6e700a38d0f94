#!/usr/bin/env python3
"""Chip smoke: the flagship round on the TPU, through ``murmura run``.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --multichip  # four chips: the sharded paths only

One process, chip or fail.  With no arguments it runs
``examples/configs/femnist_krum_tpu.yaml`` (16-node k-regular Krum under a
Gaussian attack, the 6.6M-parameter FEMNIST CNN, bf16 compute) through the
CLI's ``run`` command in both dispatch modes, checks the histories it wrote,
then runs the Pallas kernels the chip's compiler must accept — inside a
round and directly — against their lax paths on the same chip.
``--multichip`` runs only the mesh paths (node axis and param axis sharded
over four chips) and the one-device runs they are compared with; every run
ends in a verdict (see the note above ``multichip_phase``).

The flagship yaml is run with ``aggregation.params.num_compromised: 1`` in the
smoke's copy: as committed (3) it fails Krum's own constraint
``c < (m - 2) / 2`` at m = 5 candidates, every node keeps its own model, and
no comparison could see the exchange or the selection.

Earlier lines say what is worth knowing (versions, cache directory and its
entry count, per-phase wall/compile seconds and cache hits, peak device
memory); the last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failed check raises, so the exit code is non-zero and that line is never
printed.  ``run_smoke(size)`` takes the sizes as an argument so the tests
can rehearse every phase on the CPU at a tiny size
(tests/test_chip_smoke.py); run as a script the size is always :data:`FULL`.
"""

import argparse
import contextlib
import copy
import dataclasses
import functools
import gc
import json
import math
import sys
import time
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
FLAGSHIP = ROOT / "examples" / "configs" / "femnist_krum_tpu.yaml"
SHARDED = ROOT / "examples" / "configs" / "sharded_model.yaml"
# Configs, histories and telemetry of the smoke itself (git-ignored).
WORK_DIR = ROOT / "chiprun_out" / "smoke"

FEMNIST_CLASSES = 62
# Run-vs-run tolerance, each key scaled by its reference magnitude (the
# tolerance __graft_entry__._dryrun_body has always used; also the rule-output
# tolerance of tests/test_pallas_agg.py).
HISTORY_TOL = 1e-4
# With 3 the committed flagship never aggregates (see the module docstring).
LIVE_KRUM = {"algorithm": "krum", "params": {"num_compromised": 1}}
# Per-node metrics that are the argmin's pick or computed from the model it
# picked; everything else in a round event is fixed before the selection.
POST_SELECTION = ("accuracy", "loss", "agg_selected_index", "agg_selected_own")
COLLECTIVES = (
    "all-gather", "all-reduce", "collective-permute", "all-to-all",
    "reduce-scatter",
)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


@dataclasses.dataclass(frozen=True)
class Size:
    """What the smoke runs at.  :data:`FULL` is the only size the script
    itself ever uses; tests pass a tiny CPU one."""

    platform: str = "tpu"
    model: str = "leaf.femnist.baseline"  # the flagship's own factory
    rounds: int = 4
    fused_chunk: int = 2  # tpu.rounds_per_dispatch of the fused run
    # Kernel phase: compiled-mode envelope is N % 128 == 0.  The flagship's
    # own CNN is the largest registered model whose N=128 Krum round fits
    # one v5e chip (bf16 resident params — the auto default from 64 nodes
    # up; compiled for the described chip: 12.0 GB temp + 1.8 GB arguments
    # of 15.75 GB; in f32 it is refused by 48 MB, "large" by far).
    kernel_nodes: int = 128
    kernel_model: str = "leaf.femnist.baseline"
    # Direct kernel calls: a quarter of the flagship's 6,603,710 columns,
    # deliberately not a multiple of 128 so the masked tail block runs.
    kernel_width: int = 1_650_927
    sketch_width: int = 6_603_710
    multichip_devices: int = 4
    multichip_rounds: int = 3


FULL = Size()


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def scaled_history_delta(history: Dict[str, list], ref: Dict[str, list]):
    """(max scaled deviation, its key) between two run histories.  Keys
    span scales (accuracies ~1, losses ~4, krum scores ~10+), so each
    key's deviation is normalized by its reference magnitude; the populated
    key sets must agree first — a schema divergence must not silently
    shrink the comparison."""
    import numpy as np

    keys = {k for k, v in history.items() if v}
    ref_keys = {k for k, v in ref.items() if v}
    require(
        keys == ref_keys,
        f"history key sets differ: only-run {sorted(keys - ref_keys)}, "
        f"only-reference {sorted(ref_keys - keys)}",
    )
    require(history["round"] == ref["round"], "round lists differ")
    delta, delta_key = float("nan"), None
    for k in sorted(keys - {"round"}):
        a = np.asarray(history[k], dtype=np.float64)
        b = np.asarray(ref[k], dtype=np.float64)
        scaled = float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))
        if not (scaled <= delta):  # also replaces the initial NaN
            delta, delta_key = scaled, k
    return delta, delta_key


def last_line(devices_used: int) -> str:
    """The contract's final stdout line, from what JAX reports."""
    import jax

    dev = jax.devices()[0]
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": dev.platform,
                "kind": dev.device_kind,
                "count": devices_used,
            },
        }
    )


# ---------------------------------------------------------------------------
# Compile accounting (jax.monitoring) and phase bracketing
# ---------------------------------------------------------------------------


class CompileMeter:
    """Sums backend-compile seconds and counts persistent-cache hits and
    misses, from the events JAX itself records."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE:
            self.compile_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


@functools.cache
def _meter() -> CompileMeter:
    """The process's one meter: jax.monitoring listeners cannot be removed,
    so a second registration would count every event twice."""
    return CompileMeter()


def say(message: str) -> None:
    print(f"[smoke] {message}", flush=True)


def run_phase(name: str, body: Callable[[], Any]) -> Any:
    """Run one phase; its wall seconds, compile seconds and cache traffic
    go to an earlier line.  An exception propagates — a failed phase fails
    the smoke."""
    meter = _meter()
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    out = body()
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    say(
        f"phase {name}: wall {wall:.2f}s compile {c1 - c0:.2f}s "
        f"cache_hits {h1 - h0} cache_misses {m1 - m0}"
    )
    return out


def cache_entries(directory: Optional[str]) -> int:
    if directory is None:  # no persistent cache (factories.apply_compilation_cache)
        return 0
    d = Path(directory)
    return sum(1 for f in d.iterdir() if f.is_file()) if d.is_dir() else 0


def report_memory() -> None:
    import jax

    for dev in jax.local_devices():
        stats = dev.memory_stats()  # raises on an accelerator that cannot say
        if stats is None:
            say(f"memory {dev}: backend reports none")
            continue
        say(
            f"memory {dev}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
            f"bytes_limit {stats.get('bytes_limit')}"
        )


# ---------------------------------------------------------------------------
# Running a config through the CLI's `run` command, in this process
# ---------------------------------------------------------------------------


def load_config(path: Path) -> Dict[str, Any]:
    import yaml

    return yaml.safe_load(path.read_text())


def merged(raw: Dict[str, Any], **sections) -> Dict[str, Any]:
    """A deep copy of ``raw`` with each named section's keys updated."""
    out = copy.deepcopy(raw)
    for section, values in sections.items():
        if isinstance(values, dict):
            out[section] = {**(out.get(section) or {}), **values}
        else:
            out[section] = values
    return out


@dataclasses.dataclass
class Run:
    """What one ``murmura run`` left behind."""

    name: str
    network: Any  # the Network the command built
    history: Dict[str, list]  # the history JSON as written
    # round -> metric -> per-node array, from the run's telemetry stream
    nodes: Dict[int, Dict[str, Any]]


def murmura_run(name: str, raw: Dict[str, Any], size: Size) -> Run:
    """``murmura run <yaml> [--require-tpu] -o <history>`` in-process, with
    the telemetry stream on (it carries every round's per-node metrics and
    leaves the compiled program as it is).

    The network is the one the command built: ``build_network_from_config``
    is wrapped for the call so the checks can look at where its arrays live.
    """
    import numpy as np
    import yaml

    from murmura_tpu import cli
    from murmura_tpu.utils import factories

    WORK_DIR.mkdir(parents=True, exist_ok=True)
    cfg_path = WORK_DIR / f"{name}.yaml"
    hist_path = WORK_DIR / f"{name}.history.json"
    run_dir = WORK_DIR / "telemetry" / name
    raw = merged(raw, telemetry={"enabled": True, "dir": str(run_dir)})
    cfg_path.write_text(yaml.safe_dump(raw, sort_keys=False))
    hist_path.unlink(missing_ok=True)

    built: List[Any] = []
    original = factories.build_network_from_config

    def capture(*args, **kwargs):
        network = original(*args, **kwargs)
        built.append(network)
        return network

    argv = ["run", str(cfg_path), "--quiet", "-o", str(hist_path)]
    if size.platform == "tpu":
        argv.append("--require-tpu")
    factories.build_network_from_config = capture
    try:
        cli.app.main(args=argv, standalone_mode=False)
    finally:
        factories.build_network_from_config = original
    require(len(built) == 1, f"{name}: the run command built {len(built)} networks")
    require(hist_path.is_file(), f"{name}: no history JSON at {hist_path}")
    nodes = {}
    for line in (run_dir / "events.jsonl").read_text().splitlines():
        event = json.loads(line)
        if event.get("type") == "round":
            nodes[event["round"]] = {
                k: np.asarray(v, dtype=np.float64)
                for k, v in event["metrics"].items()
            }
    history = json.loads(hist_path.read_text())
    # Two products of one command: the history file is the stream's means.
    for i, r in enumerate(history["round"]):
        require(
            r in nodes
            and abs(history["mean_loss"][i] - float(nodes[r]["loss"].mean())) <= 1e-6,
            f"{name}: history round {r} disagrees with the telemetry stream",
        )
    return Run(name, built[0], history, nodes)


def compare_runs(
    label: str, run: Run, ref: Run, tol: float = HISTORY_TOL,
    later_tol: Optional[float] = None,
):
    """Hold ``run``'s history to ``ref``'s, to ``tol`` scaled by each key's
    reference magnitude, up to argmin ties.  Returns a failure message or
    None.  ``later_tol`` replaces ``tol`` from the second round on.

    The history is what the command writes — each metric's mean over the
    nodes — recomputed here from the per-node stream, because Krum's argmin
    is discontinuous: two candidates whose scores agree to rounding may be
    picked differently by two programs that compute the same function.  At
    a node whose pick differs, the metrics downstream of the pick
    (:data:`POST_SELECTION`) are left out of that round's means; the mean
    winning score, over every node, is still held to the tolerance — a pick
    that differed for another reason than a tie would move it (on four v5e
    chips the scores at such nodes agreed to 2e-5).  Rounds after such a
    round start from different states at those nodes and are not compared.
    """
    import numpy as np

    rounds = sorted(ref.nodes)
    if sorted(run.nodes) != rounds:
        return f"{label}: recorded rounds {sorted(run.nodes)} != {rounds}"
    failures = []
    worst, where, compared = 0.0, None, 0
    for r in rounds:
        got, want = run.nodes[r], ref.nodes[r]
        if set(got) != set(want):
            return f"{label}: round {r} metric keys differ: {sorted(set(got) ^ set(want))}"
        picks = "agg_selected_index"
        tied = (
            got[picks] != want[picks] if picks in want
            else np.zeros(np.shape(want["accuracy"]), bool)
        )
        if tied.all():
            return f"{label}: round {r}: every node picked differently"
        limit = tol if r == rounds[0] or later_tol is None else later_tol
        for key in sorted(want):
            x, y = got[key], want[key]
            if key in POST_SELECTION and x.ndim:
                x, y = x[~tied], y[~tied]
            scaled = float(abs(x.mean() - y.mean()) / max(1.0, abs(float(y.mean()))))
            if where is None or not scaled <= worst:  # also catches NaN
                worst, where = scaled, f"{key}, round {r}"
            if not scaled <= limit:
                failures.append(f"{key} round {r}: {scaled:.3g} > {limit:g}")
        compared += 1
        if tied.any():
            say(
                f"{label}: round {r}: nodes {np.flatnonzero(tied).tolist()} broke "
                "an argmin tie differently and are left out of its "
                f"post-selection means; rounds after {r} are not compared"
            )
            break
    say(
        f"{label}: max scaled delta {worst:.3g} ({where}) over {compared} of "
        f"{len(rounds)} rounds; tol {tol:g}"
        + (f", {later_tol:g} after round {rounds[0]}" if later_tol else "")
    )
    if failures:
        return f"{label}: history deviates: " + "; ".join(failures)
    return None


def check_history(
    name: str, history: Dict[str, list], rounds: int,
    classes: int = FEMNIST_CLASSES, learns: bool = True,
) -> None:
    require(
        history["round"] == list(range(1, rounds + 1)),
        f"{name}: history rounds {history['round']} != 1..{rounds}",
    )
    for key, values in history.items():
        for v in values:
            # Scalars only; per-node lists (if any) are checked element-wise.
            flat = v if isinstance(v, list) else [v]
            require(
                all(isinstance(x, (int, float)) and math.isfinite(x) for x in flat),
                f"{name}: non-finite {key} in {values}",
            )
    require(
        len(history["mean_loss"]) == rounds and len(history["honest_accuracy"]) == rounds,
        f"{name}: loss/accuracy columns do not cover {rounds} rounds",
    )
    acc = history["honest_accuracy"][-1]
    require(
        acc > 1.0 / classes or not learns,
        f"{name}: honest accuracy {acc:.4f} after round {rounds} is not "
        f"above chance for {classes} classes",
    )
    say(
        f"{name}: rounds {rounds} mean_loss {history['mean_loss'][-1]:.4f} "
        f"honest_accuracy {acc:.4f}"
    )


def check_placement(name: str, network, size: Size, devices: int) -> None:
    """Every stacked parameter array lives on ``devices`` devices of the
    required platform."""
    import jax

    leaves = jax.tree_util.tree_leaves(network.params)
    require(bool(leaves), f"{name}: the network holds no parameter arrays")
    for leaf in leaves:
        device_set = leaf.sharding.device_set
        platforms = {d.platform for d in device_set}
        require(
            platforms == {size.platform},
            f"{name}: a parameter array lives on {sorted(platforms)}, "
            f"not on {size.platform}",
        )
        require(
            len(device_set) == devices,
            f"{name}: a parameter array spans {len(device_set)} devices, "
            f"expected {devices}",
        )


def compiled_round_text(network) -> str:
    return network._step_compiled().as_text()


# ---------------------------------------------------------------------------
# One chip: the main path, then the kernels
# ---------------------------------------------------------------------------


def flagship_config(size: Size, rounds: int, **tpu) -> Dict[str, Any]:
    """The smoke's copy of the flagship yaml: rounds cut, Krum live."""
    return merged(
        load_config(FLAGSHIP),
        experiment={"rounds": rounds},
        aggregation=LIVE_KRUM,
        model={"factory": size.model},
        tpu=tpu,
    )


def check_selection_is_live(name: str, history: Dict[str, list]) -> None:
    """Some node adopted a neighbour's model in some round — otherwise the
    run's output cannot tell an exchange that works from one that does not."""
    own = history.get("agg_selected_own")
    require(bool(own), f"{name}: the history has no agg_selected_own")
    require(
        min(own) < 1.0,
        f"{name}: every node kept its own model in every round "
        f"(agg_selected_own {own}) — the aggregation never acted",
    )
    say(f"{name}: share of nodes keeping their own model, by round: {own}")


def main_path_phase(size: Size) -> None:
    """The flagship through `murmura run`, per-round then fused dispatch."""
    raw = flagship_config(size, size.rounds, num_devices=1)
    runs = {}
    for mode, chunk in (("per_round", 1), ("fused", size.fused_chunk)):
        name = f"flagship_{mode}"
        run_raw = merged(raw, tpu={"rounds_per_dispatch": chunk})
        if mode == "fused":
            # memory_stats() sampling runs on the chip once.
            run_raw["telemetry"] = {"memory_stats": True}

        def body(name=name, run_raw=run_raw):
            run = murmura_run(name, run_raw, size)
            check_history(name, run.history, size.rounds)
            check_selection_is_live(name, run.history)
            check_placement(name, run.network, size, devices=1)
            return run

        runs[mode] = run_phase(name, body)
        runs[mode].network = None  # free the device arrays
        gc.collect()
    # Same fold_in(base, round) key stream: the dispatch mode must not
    # change what is learned.
    failure = compare_runs("fused vs per-round", runs["fused"], runs["per_round"])
    require(failure is None, str(failure))


@contextlib.contextmanager
def envelope_misses():
    """Collects the message of every ``PallasEnvelopeWarning`` raised inside
    the block: each is a requested kernel that gave way to the lax path."""
    from murmura_tpu.ops.pallas_agg import PallasEnvelopeWarning

    misses: List[str] = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", PallasEnvelopeWarning)
        yield misses
    misses.extend(
        str(w.message) for w in caught
        if issubclass(w.category, PallasEnvelopeWarning)
    )


def _expect_kernel(name: str, text: str, size: Size) -> None:
    """On the TPU the compiled program must hold the Mosaic kernel.  The
    CPU rehearsal runs kernels interpreted, which leaves no such call."""
    if size.platform != "tpu":
        say(f"{name}: interpret mode (no chip), no tpu_custom_call to look for")
        return
    require(
        "tpu_custom_call" in text,
        f"{name}: no tpu_custom_call in the compiled program — the kernel "
        "gave way to the lax path",
    )
    say(f"{name}: tpu_custom_call present ({text.count('tpu_custom_call')}x)")


def _max_rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.max(np.abs(got - ref)) / max(1e-30, float(np.max(np.abs(ref)))))


def _flat_params(network):
    """[N, P] float32 on the host."""
    import jax
    import numpy as np

    return np.concatenate(
        [np.asarray(x, dtype=np.float32).reshape(x.shape[0], -1)
         for x in jax.tree_util.tree_leaves(network.params)],
        axis=1,
    )


@contextlib.contextmanager
def sketch_kernel_off():
    """Sketchguard's Count-Sketch on the segment_sum path.  The kernel is on
    by default on a TPU and the program has no switch for it (nor should a
    smoke add one), so the rule's reference to the op is swapped."""
    from murmura_tpu.aggregation import sketchguard

    original = sketchguard.count_sketch
    sketchguard.count_sketch = functools.partial(original, use_pallas=False)
    try:
        yield
    finally:
        sketchguard.count_sketch = original


def _kernel_vs_lax(label: str, runs: Dict[str, Any]) -> None:
    """A kernel round against its lax round: metrics up to argmin ties, and
    the parameters of every node that made the same pick."""
    import numpy as np

    (k_run, k_flat), (l_run, l_flat) = runs["kernel"], runs["lax"]
    failure = compare_runs(f"{label}: kernel vs lax", k_run, l_run)
    require(failure is None, str(failure))
    last = max(l_run.nodes)
    picks = "agg_selected_index"
    same = (
        k_run.nodes[last][picks] == l_run.nodes[last][picks]
        if picks in l_run.nodes[last] else np.ones(len(l_flat), bool)
    )
    rows = np.flatnonzero(same)
    require(rows.size > 0, f"{label}: no node made the same pick on both paths")
    err = max(float(np.max(np.abs(k_flat[i] - l_flat[i]))) for i in rows) / max(
        1e-30, max(float(np.max(np.abs(l_flat[i]))) for i in rows)
    )
    say(
        f"{label}: params of the {int(same.sum())}/{len(same)} nodes with the "
        f"same pick: max rel err {err:.3g} (tol {HISTORY_TOL:g})"
    )
    require(
        err <= HISTORY_TOL, f"{label}: kernel params deviate from lax by {err}"
    )


def kernel_round_phase(size: Size) -> None:
    """One sketchguard round (Count-Sketch kernel, on by default on TPU)
    and one `tpu.pallas_agg: true` Krum round at an in-envelope node count,
    each checked for the kernel in its compiled text and compared with the
    same round on the lax path."""
    flagship = flagship_config(size, 1, num_devices=1)

    def one(name, raw, expect_kernel):
        with envelope_misses() as fell_out:
            run = murmura_run(name, raw, size)
            text = compiled_round_text(run.network)
        require(
            not fell_out,
            f"{name}: a requested kernel gave way to lax: {fell_out}",
        )
        check_history(name, run.history, 1)
        if expect_kernel:
            _expect_kernel(name, text, size)
        elif size.platform == "tpu":
            require(
                "tpu_custom_call" not in text,
                f"{name}: the lax round holds a tpu_custom_call",
            )
        flat = _flat_params(run.network)
        run.network = None
        gc.collect()
        return run, flat

    sketch_raw = merged(
        flagship, aggregation={"algorithm": "sketchguard", "params": {}}
    )
    runs = {"kernel": one("kernel_sketchguard", sketch_raw, True)}
    with sketch_kernel_off():
        runs["lax"] = one("kernel_sketchguard_lax", sketch_raw, False)
    _kernel_vs_lax("sketchguard", runs)
    del runs
    gc.collect()

    krum_raw = merged(
        flagship,
        topology={"num_nodes": size.kernel_nodes},
        model={"factory": size.kernel_model},
    )
    runs = {
        label: one(
            f"kernel_krum_{label}", merged(krum_raw, tpu={"pallas_agg": armed}),
            armed,
        )
        for label, armed in (("kernel", True), ("lax", False))
    }
    _kernel_vs_lax(f"krum N={size.kernel_nodes} {size.kernel_model}", runs)


def kernel_direct_phase(size: Size) -> None:
    """Each Pallas kernel called directly at an in-envelope shape and
    compared with its lax path on the same device, to the tolerances of
    tests/test_pallas_agg.py and tests/test_pallas_sketch.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from murmura_tpu.aggregation.base import (
        circulant_candidate_map,
        circulant_neighbor_distances,
        pairwise_l2_distances,
    )
    from murmura_tpu.ops import pallas_agg
    from murmura_tpu.ops.sketch import count_sketch, make_sketch_tables

    n, p = size.kernel_nodes, size.kernel_width
    offsets = (1, 2, n - 2, n - 1)
    m = len(offsets) + 1
    ka, kb, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    own = 0.5 * jax.random.normal(ka, (n, p), jnp.float32)
    bcast = 0.5 * jax.random.normal(kb, (n, p), jnp.float32)

    def coord_median(cand):
        ranked = jnp.sort(cand, axis=0)
        return 0.5 * (ranked[(m - 1) // 2] + ranked[m // 2])

    def coord_trimmed(cand):
        return jnp.sort(cand, axis=0)[1 : m - 1].mean(axis=0)

    # name -> (kernel fn, lax fn, rtol, atol); every fn takes (own, bcast).
    cases = {
        "circulant_sq_distances": (
            lambda o, b: pallas_agg.circulant_sq_distances(o, b, offsets),
            lambda o, b: circulant_neighbor_distances(o, b, offsets) ** 2,
            1e-5, 1e-4,
        ),
        "pairwise_l2_distances": (
            lambda o, b: pairwise_l2_distances(o, b, pallas=True),
            lambda o, b: pairwise_l2_distances(o, b),
            1e-4, 1e-2,
        ),
        "candidate_select[median]": (
            lambda o, b: pallas_agg.fused_candidate_select(
                o, b, offsets, median=True
            ),
            lambda o, b: circulant_candidate_map(o, b, offsets, coord_median),
            1e-6, 1e-6,
        ),
        "candidate_select[trimmed_mean]": (
            lambda o, b: pallas_agg.fused_candidate_select(
                o, b, offsets, trim=1, median=False
            ),
            lambda o, b: circulant_candidate_map(o, b, offsets, coord_trimmed),
            1e-6, 1e-6,
        ),
    }
    failures: List[str] = []
    for name, (kernel, lax_path, rtol, atol) in cases.items():
        with envelope_misses() as fell_out:
            jitted = jax.jit(kernel)
            text = jitted.lower(own, bcast).compile().as_text()
            got = jitted(own, bcast)
        require(
            not fell_out and got is not None,
            f"{name}: the kernel gave way to lax at shape {(n, p)}: {fell_out}",
        )
        _expect_kernel(name, text, size)
        ref = jax.jit(lax_path)(own, bcast)
        got, ref = np.asarray(got), np.asarray(ref)
        ok = np.allclose(got, ref, rtol=rtol, atol=atol)
        say(
            f"{name} [{n}, {p}]: max rel err {_max_rel_err(got, ref):.3g} "
            f"(rtol {rtol:g} atol {atol:g}) {'ok' if ok else 'MISMATCH'}"
        )
        if not ok:
            failures.append(name)
        del got, ref

    # Count-Sketch at the flagship width and sketchguard's default size: a
    # [P] vector (the N = 1 case), then all rows of [kernel_nodes, P] in
    # both resident dtypes, each against segment_sum of the float32-lifted
    # values.  The first rows are also held against float64 bincount on the
    # host: the two device paths differ in accumulation order only, and this
    # says which of them carries the error.
    del own, bcast
    sp, sketch_size = size.sketch_width, 1000
    hash_np, sign_np = make_sketch_tables(sp, sketch_size, 42)
    hash_table, sign_table = jnp.asarray(hash_np), jnp.asarray(sign_np)

    def sketch_with(use_pallas):
        return jax.jit(
            lambda x: count_sketch(
                x, hash_table, sign_table, sketch_size, use_pallas=use_pallas
            )
        )

    kernel, lax_path = sketch_with(True), sketch_with(False)
    rows = jax.random.normal(kv, (n, sp), jnp.float32)
    for label, x in (
        ("", rows[0]),
        (" rows float32", rows),
        (" rows bfloat16", rows.astype(jnp.bfloat16)),
    ):
        name = f"count_sketch{label}"
        _expect_kernel(name, kernel.lower(x).compile().as_text(), size)
        got = np.asarray(kernel(x))
        lifted = x.astype(jnp.float32)
        ref = np.asarray(lax_path(lifted))
        first = np.asarray(lifted.reshape(-1, sp)[:2], dtype=np.float64)
        exact = np.stack([
            np.bincount(hash_np, weights=sign_np * row, minlength=sketch_size)
            for row in first
        ])
        # Float accumulation order differs over ~P/S terms per bucket; the
        # interpret-mode tests use 1e-5 at P=5000, scaled here by sqrt(P)
        # growth.
        ok = np.allclose(got, ref, rtol=1e-4, atol=1e-2)
        say(
            f"{name} {list(x.shape)} -> {sketch_size}: max rel err "
            f"{_max_rel_err(got, ref):.3g} (against float64 bincount: kernel "
            f"{_max_rel_err(got.reshape(-1, sketch_size)[:2], exact):.3g}, "
            f"segment_sum {_max_rel_err(ref.reshape(-1, sketch_size)[:2], exact):.3g}) "
            f"{'ok' if ok else 'MISMATCH'}"
        )
        if not ok:
            failures.append(name)
        del got, ref, lifted
    require(not failures, f"kernels disagree with their lax paths: {failures}")


# ---------------------------------------------------------------------------
# Four chips: node-axis and param-axis sharding against one device
# ---------------------------------------------------------------------------


def _check_spread(name: str, network, size: Size) -> None:
    check_placement(name, network, size, devices=size.multichip_devices)
    text = compiled_round_text(network)
    found = [c for c in COLLECTIVES if c in text]
    require(
        bool(found),
        f"{name}: no cross-device collective in the compiled round — the "
        "work never left device 0",
    )
    say(f"{name}: params on {size.multichip_devices} devices, collectives {found}")


# Every run of this phase ends in a verdict.  Two kinds of run:
#
# As committed (bf16 compute, the yaml's own lr), on the mesh: the history
# has its rounds, is finite and learns, Krum's selection is live, the params
# span the four chips and the compiled round holds a collective.  It is not
# held to one device: bf16 compute touches local training only (params and
# the exchange are float32 in both yamls), a different node count per device
# changes the batched conv's tiling, and every re-rounding of activations to
# bf16 amplifies the last bits — four v5e chips measured 1.2e-2 scaled, one
# eval sample of 84 (PR 22, PERF.md section 6).
#
# The equality instrument, mesh against one device of the same process, to
# HISTORY_TOL up to argmin ties (compare_runs): float32 compute and params,
# traced under jax.default_matmul_precision("highest") so that float32 means
# float32 on the MXU too, and for the flagship the learning rate cut tenfold.
# At the yaml's 0.05 local training is chaotic at this width: on ONE chip a
# 1-ulp perturbation of the round-1 parameters moves mean_loss by 1.4e-5
# scaled in round 2 and 1.4e-3 in round 3 (PR 22, PERF.md section 6), so two
# programs that differ in the last bit — as the allgather mesh round does
# from the one-device round — cannot agree to 1e-4 over three rounds, and a
# comparison at that rate measures the training's sensitivity, not the mesh.
F32 = {"compute_dtype": "float32", "param_dtype": "float32"}
EXACT_LR_CUT = 0.1
# sharded_model.yaml exchanges int8 payloads: once a last-bit difference has
# crossed a quantization boundary the state differs by a quantizer step, so
# from the second round on its runs are held to one int8 step (1/127 of a
# block's range; 4 virtual CPU devices measure 7e-4) and only the first
# round to HISTORY_TOL.
INT8_STEP = 1.0 / 127


def _highest_precision():
    import jax

    return jax.default_matmul_precision("highest")


def multichip_phase(size: Size) -> None:
    """The mesh paths, and the one-device runs they are held to.  Every
    comparison is made before any verdict on them, so one run shows all."""
    nd, rounds = size.multichip_devices, size.multichip_rounds
    failures: List[Optional[str]] = []

    def mesh_run(name, raw, classes=FEMNIST_CLASSES, learns=True, param=None):
        """One run on the mesh with its own verdicts."""
        run = murmura_run(name, raw, size)
        check_history(name, run.history, rounds, classes=classes, learns=learns)
        check_selection_is_live(name, run.history)
        if param is not None:
            mesh_shape = dict(run.network.mesh.shape)
            require(
                mesh_shape.get("param") == param,
                f"{name}: mesh {mesh_shape} has no param axis of {param}",
            )
            say(f"{name}: mesh {mesh_shape}")
        _check_spread(name, run.network, size)
        run.network = None
        gc.collect()
        return run

    def one_device_run(name, raw):
        run = murmura_run(name, raw, size)
        run.network = None
        gc.collect()
        return run

    for exchange in ("allgather", "ppermute"):
        def body(exchange=exchange):
            committed = flagship_config(
                size, rounds, exchange=exchange, num_devices=nd
            )
            mesh_run(f"multichip_{exchange}_committed", committed)
            exact = merged(
                committed,
                training={"lr": committed["training"]["lr"] * EXACT_LR_CUT},
                tpu=F32,
            )
            with _highest_precision():
                # Three rounds at a tenth of the lr need not beat chance.
                mesh = mesh_run(f"multichip_{exchange}_f32_mesh", exact, learns=False)
                one = one_device_run(
                    f"multichip_{exchange}_f32_one",
                    merged(exact, tpu={"num_devices": 1}),
                )
            failures.append(
                compare_runs(f"{exchange} f32: {nd} devices vs 1", mesh, one)
            )

        run_phase(f"multichip_{exchange}", body)

    sharded = merged(load_config(SHARDED), experiment={"rounds": rounds})
    classes = sharded["data"]["params"]["num_classes"]

    def sharded_body():
        # As committed: 1 x 1 x 4, the param axis only.
        mesh_run(
            f"sharded_param{nd}_committed",
            merged(sharded, tpu={"param_shards": nd, "num_devices": nd}),
            classes=classes, param=nd,
        )
        # 4 shards, and 2 (1 x 2 x 2: node and param axis both live),
        # against param_shards: 1 on one device.
        with _highest_precision():
            ref = one_device_run(
                "sharded_f32_reference",
                merged(sharded, tpu={"param_shards": 1, "num_devices": 1, **F32}),
            )
            for shards in (nd, nd // 2):
                run = mesh_run(
                    f"sharded_param{shards}_f32",
                    merged(
                        sharded,
                        tpu={"param_shards": shards, "num_devices": nd, **F32},
                    ),
                    classes=classes, param=shards,
                )
                failures.append(compare_runs(
                    f"sharded param_shards={shards} f32 vs 1", run, ref,
                    later_tol=INT8_STEP,
                ))

    run_phase("multichip_param_shards", sharded_body)
    failed = [f for f in failures if f]
    require(not failed, "; ".join(failed))


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def run_smoke(size: Size = FULL, multichip: bool = False) -> str:
    """Run the smoke at ``size``; returns the contract's last line.  Raises
    (``SmokeFailure`` or whatever the failing phase raised) otherwise."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != size.platform:
        raise SmokeFailure(
            f"chip_smoke needs a {size.platform} device; JAX reports "
            f"platform {platform!r} ({devices[0].device_kind}, {len(devices)} devices)"
        )
    needed = size.multichip_devices if multichip else 1
    require(
        len(devices) >= needed,
        f"chip_smoke needs {needed} {size.platform} devices, JAX reports {len(devices)}",
    )

    import jaxlib

    from murmura_tpu.utils.factories import apply_compilation_cache

    say(
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {_libtpu_version()} python {sys.version.split()[0]}"
    )
    say(f"device {platform} {devices[0].device_kind} x{len(devices)} (using {needed})")
    cache_dir = apply_compilation_cache()
    say(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries before")
    _meter()

    if multichip:
        multichip_phase(size)
    else:
        main_path_phase(size)
        run_phase("kernel_rounds", lambda: kernel_round_phase(size))
        run_phase("kernel_direct", lambda: kernel_direct_phase(size))

    meter = _meter()
    say(
        f"compile total {meter.compile_s:.2f}s cache_hits {meter.hits} "
        f"cache_misses {meter.misses}"
    )
    say(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries after")
    report_memory()
    return last_line(needed)


def _libtpu_version() -> str:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "not installed"


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--multichip", action="store_true",
        help="run only the four-chip mesh paths and what they are compared with",
    )
    args = parser.parse_args(argv)
    try:
        line = run_smoke(FULL, multichip=args.multichip)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
