"""One run of one cell: set-up, the measured window, the comparison.

The window drives ``build_network_from_config`` -> ``Network.train`` — what
``murmura run <yaml> --require-tpu`` calls — with the cell's YAML as a user
would write it.  From the program the harness takes the system under test,
its ``murmura.*`` scope names and its round counter; the clocks, the trace
reduction, the peaks, the operation counts and the reference are the
benchmark's own.

Order of a run: build the network; put the benchmark's own weights and data
(``inputs.py``, from the seed) where the build put the program's; drive it
through its first rounds (the same object, the same call the window makes)
and keep its state after the first and the last of them; warm until a call
compiles nothing; measure; read the memory peak; free the program's state;
then draw the inputs again, follow the same first rounds with the plain
reference and compare.
"""

import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import inputs as cell_inputs
from benchmark.cells import Cell, load_peaks
from benchmark.compile_meter import meter


def say(message: str) -> None:
    print(f"[bench] {message}", flush=True)


def p95(values: List[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


STALL = 1.5  # a call over this many medians is counted as a stall


def window_numbers(calls: List[float], chunk: int, window_s: float) -> Dict[str, Any]:
    """The window's statistics, from its calls' wall times (seconds).

    ``round_ms``: the window's wall time over all its rounds, the time
    between the calls included; ``round_ms_p95``: the 95th percentile of
    the calls, over ``chunk``.  A stall of the host (a call of twice the
    length, once or twice in a window) moves the first and not the second.
    So that a reader can tell a stall from a change of level, the median
    call is printed beside them (``median_round_ms``, judged by nothing)
    with the stalls counted: ``stall_calls`` over ``STALL`` medians,
    ``stall_s`` the seconds they took beyond a median each, ``longest`` the
    three longest calls as [index, seconds].
    """
    times = np.asarray(calls, np.float64)
    median = float(np.median(times))
    over = times[times > STALL * median]
    longest = np.argsort(-times)[:3]
    return {
        "round_ms": window_s / (len(calls) * chunk) * 1e3,
        "round_ms_p95": p95(calls) / chunk * 1e3,
        "median_round_ms": median / chunk * 1e3,
        "calls": len(calls),
        "stall_calls": int(over.size),
        "stall_s": float((over - median).sum()),
        "longest": [[int(i), float(times[i])] for i in longest],
    }


class Spans:
    """Host-clock spans of the harness's own calls into the program."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    def timed(self, name: str, body: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        out = body()
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0
        return out


def device_stamp(chips: int) -> Dict[str, Any]:
    import jax

    devices = jax.devices()[:chips]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    cannot say, as the CPU)."""
    import jax

    peak = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def build(cell: Cell, seed: int):
    from murmura_tpu.config import Config
    from murmura_tpu.utils.factories import build_network_from_config

    return build_network_from_config(
        Config.model_validate(cell.program_config(seed))
    )


def to_host(tree):
    import jax

    return jax.device_get(tree)


def samples_per_round(inputs: Dict[str, Any], local_epochs: int) -> int:
    """Training samples a round consumes: every honest node's steps times
    its batch, as the built data has them."""
    data = inputs["data"]
    per_node = np.asarray(data["steps"]) * np.asarray(data["eff_batch"])
    honest = np.asarray(inputs["compromised"]) == 0
    return int(per_node[honest].sum()) * int(local_epochs)


def reference_job(cell: Cell, inputs: Dict[str, Any]):
    """The cell's job in the reference's terms, at the precision the
    configuration states (the resident dtype was read off the built state,
    since the program chooses it by its own rule where the file leaves it
    open)."""
    from benchmark.reference.round import Job

    job, attack = cell.job, cell.job.get("attack") or {}
    return Job(
        model=cell.config["reference"],
        rule=job["aggregation"]["algorithm"],
        rule_params=dict(job["aggregation"].get("params") or {}),
        attack=attack.get("type") if attack.get("enabled") else None,
        attack_params=dict(attack.get("params") or {}),
        lr=float(job["training"]["lr"]),
        batch_size=int(job["training"]["batch_size"]),
        local_epochs=int(job["training"]["local_epochs"]),
        total_rounds=int(job["experiment"]["rounds"]),
        compute_dtype=cell.config["compute_dtype"],
        param_dtype=inputs["param_dtype"],
        node_block=int(job["correct"].get("node_block", 32)),
        loss=cell.config.get("loss", "label"),
        loss_params=dict(cell.config.get("loss_params") or {}),
    )


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """The widest gap between a run's and the reference's norm of a leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves all but stand still)."""
    median = float(np.median(list(want.values())))
    return max(
        abs(got[path] - ref) / max(ref, median, 1e-30) for path, ref in want.items()
    )


def leaf_gap(got: Dict[str, float], want: Dict[str, float], path: str) -> float:
    """The gap between a run's and the reference's norm of one leaf."""
    return abs(got[path] - want[path]) / max(want[path], 1e-30)


def compare(run: Dict[str, Any], reference: Dict[str, Any],
            inputs: Dict[str, Any], job) -> Dict[str, float]:
    """The numbers ``correct`` is decided by, of a run (the program, or a
    control put in its place) against the reference; each has a limit in
    the cell's workload file.

    ``loss``: the widest relative gap of a compared round's mean loss.
    ``eval_loss``: the run's first mean loss against the reference's
    evaluation of the run's own state after that round: the eval program
    alone, whatever the rule chose before it.
    ``first_update``: the first round's training taken out of the mixed
    state (``reference.round.first_update``), by the worst leaf;
    ``first_update_largest``: the same of the one leaf that holds most of
    a node's parameters: millions of entries average the rounding of a
    stored mean away, which swings the small leaves' gaps from seed to seed.
    ``change``: the norm of the whole change over the compared rounds, by
    the worst leaf; the rule's mixing of independent starts dominates it,
    so it says whether the run aggregated as the rule does.
    """
    from benchmark.reference.round import eval_loss, first_update

    evaluated = eval_loss(run["state_first"], inputs, job)
    loss = max(
        abs(a - b) / max(abs(b), 1e-30)
        for a, b in zip(run["loss"], reference["loss"])
    )
    update = first_update(
        run["state_first"], inputs, job, reference["trained_first"]
    )
    return {
        "loss": float(loss),
        "eval_loss": abs(run["loss"][0] - evaluated) / max(abs(evaluated), 1e-30),
        "first_update": worst_leaf_gap(update["got"], update["want"]),
        "first_update_largest": leaf_gap(
            update["got"], update["want"], update["largest"]
        ),
        "change": worst_leaf_gap(run["change"], reference["change"]),
    }


def first_calls(cell: Cell, seed: int, spans: Spans):
    """Build the cell's network, give it the benchmark's weights and data
    from the seed, and drive it through the compared rounds: the window's
    own call on the window's own object, one round to a call (under
    per-round dispatch the same compiled programs as the window's
    ``rounds=chunk``), so that the state after exactly one round can be
    kept.

    Returns the network, the cell's inputs as the reference takes them
    (the weights and the data still to be drawn again: ``draw_again``),
    what the program produced (its state after the first and the last
    compared round, on the host; its loss of every compared round; the
    rule's statistics) and the window's call.
    """
    network = spans.timed("build", lambda: build(cell, seed))
    spans.timed("inputs", lambda: cell_inputs.place(network, cell, seed))
    inputs = cell_inputs.read(network, cell, seed)
    call = cell.train_kwargs()

    def one_call(**overrides) -> float:
        t0 = time.perf_counter()
        network.train(**{**call, **overrides})
        return time.perf_counter() - t0

    rounds = int(cell.job["correct"]["rounds"])
    spans.timed("first_calls", lambda: one_call(rounds=1))
    captured = {"state_first": spans.timed("snapshot", lambda: to_host(network.params))}
    for _ in range(rounds - 1):
        spans.timed("first_calls", lambda: one_call(rounds=1))
    captured["state_last"] = spans.timed("snapshot", lambda: to_host(network.params))
    captured["loss"] = list(network.history["mean_loss"][:rounds])
    captured["stats"] = {
        k: [float(v) for v in vals[:rounds]]
        for k, vals in network.history.items() if k.startswith("agg_")
    }
    return network, inputs, captured, one_call


def program_numbers(captured: Dict[str, Any], inputs: Dict[str, Any]) -> Dict[str, Any]:
    """What the program produced, in the reference's terms."""
    from benchmark.reference.round import leaf_norms

    return {
        "loss": captured["loss"],
        "state_first": captured["state_first"],
        "change": leaf_norms(captured["state_last"], inputs["params"]),
    }


def program_texts(network) -> List[str]:
    """The compiled text of the programs a round runs (the step and the
    eval), for joining trace events to ``murmura.*`` scopes by operation
    name where the events carry no metadata.  Read after the window; a
    program that does not give its text leaves the join to the events."""
    texts = []
    for get in (
        lambda: network._step_compiled().as_text(),
        lambda: network._eval.lower(network.params, network._data).compile().as_text(),
    ):
        try:
            texts.append(get())
        except Exception as e:  # noqa: BLE001 - the join is best effort
            say(f"no compiled text for the scope join ({type(e).__name__}: {e})")
    return texts


def free(network) -> None:
    """Drop the program's device state (the data stays: it is an input)."""
    network.params = network.agg_state = None
    gc.collect()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None,
             keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """Everything of a run after the look for a chip; returns the result
    object of the contract (the caller prints it)."""
    import jax

    from benchmark.reference import round as ref_round

    t_start = time.perf_counter() if t_start is None else t_start
    spans, counter = Spans(), meter()
    # Small programs are cached too, so a second run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    program_seed = int(seed) % (2**31 - 1)

    network, inputs, captured, one_call = first_calls(cell, program_seed, spans)
    chunk, call_kwargs = cell.chunk, cell.train_kwargs()
    # Warm until a call compiles nothing (the layout of a step's own
    # outputs can cost one more compile), at least two calls.  A warm call
    # is the window's call cut to two rounds (or one period of the eval):
    # per-round dispatch runs the same programs whatever ``rounds`` is, and
    # ``window_compiles`` holds the window to that.
    warm_rounds = min(chunk, max(2, int(call_kwargs["eval_every"])))
    warm_calls = []
    for i in range(8):
        before = counter.compiles
        warm_calls.append(
            spans.timed("warm", lambda: one_call(rounds=warm_rounds)) / warm_rounds
        )
        if i >= 1 and counter.compiles == before:
            break
    setup_s = time.perf_counter() - t_start
    setup_counters = {
        "compile_s": counter.compile_s, "compiles": counter.compiles,
        "cache_hits": counter.hits, "cache_misses": counter.misses,
    }
    say(
        f"set-up {setup_s:.2f}s: build {spans.seconds['build']:.2f}s inputs "
        f"{spans.seconds['inputs']:.2f}s snapshots {spans.seconds['snapshot']:.2f}s first calls "
        f"{spans.seconds['first_calls']:.2f}s warm {spans.seconds['warm']:.2f}s "
        f"(calls of {warm_rounds} rounds, a round {[round(c * 1e3, 2) for c in warm_calls]} ms); "
        f"compile {counter.compile_s:.2f}s in {counter.compiles} programs, "
        f"cache hits {counter.hits} misses {counter.misses}"
    )

    # ---- the measured window -------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="murmura_bench_trace_") if trace else None
    trace_calls = max(1, int(cell.job.get("trace_rounds", 8)) // chunk) if trace else 0
    compiles_before = counter.compiles
    calls: List[float] = []
    traced = {"window_s": 0.0, "rounds": 0}
    t0 = time.perf_counter()
    if trace:
        jax.profiler.start_trace(trace_dir)
        tt0 = time.perf_counter()
    while True:
        calls.append(one_call())
        if trace and len(calls) == trace_calls:
            traced = {"window_s": time.perf_counter() - tt0,
                      "rounds": trace_calls * chunk}
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds and len(calls) >= trace_calls:
            break
    window_s = time.perf_counter() - t0
    window_compiles = counter.compiles - compiles_before
    rounds = len(calls) * chunk
    window_loss = network.history["mean_loss"][-rounds:]
    failed = int(sum(1 for v in window_loss if not np.isfinite(v)))
    peak = memory_peak_bytes()
    samples = samples_per_round(inputs, cell.job["training"]["local_epochs"])
    tokens = samples * inputs["positions"]
    window = window_numbers(calls, chunk, window_s)
    say(
        f"window {window_s:.3f}s: {rounds} rounds in {len(calls)} calls, "
        f"{rounds / window_s:.4f} rounds/s, {samples * rounds / window_s:.1f} "
        f"samples/s ({samples} samples"
        + (f", {tokens} tokens" if tokens else "")
        + f" a round), compiles in window {window_compiles}, peak {peak} bytes"
    )
    say(
        f"window round_ms: {window['round_ms']:.4f} p95 "
        f"{window['round_ms_p95']:.4f} median call {window['median_round_ms']:.4f}; "
        f"{window['stall_calls']} calls over {STALL} medians took "
        f"{window['stall_s']:.3f}s beyond them; longest [call, s] {window['longest']}"
    )

    hlo = program_texts(network) if trace else []

    # ---- free the program's state, then the reference --------------------
    job = reference_job(cell, inputs)
    free(network)
    del network
    t_ref = time.perf_counter()
    cell_inputs.draw_again(inputs, cell)
    program = program_numbers(captured, inputs)
    reference = ref_round.run(inputs, job, rounds=len(program["loss"]))
    numbers = compare(program, reference, inputs, job)
    numbers["window_compiles"] = float(window_compiles)
    off = cell_inputs.problems(inputs, cell)
    for line in off:
        say(f"inputs: {line}")
    numbers["inputs_off"] = float(len(off))
    limits = {**cell.job["correct"]["limits"], "window_compiles": 0.0,
              "inputs_off": 0.0}
    checks = {
        k: {"value": numbers[k], "limit": float(limit)} for k, limit in limits.items()
    }
    say(f"numbers {numbers}")
    correct = failed == 0 and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()
    )
    say(
        f"reference {time.perf_counter() - t_ref:.2f}s: program loss "
        f"{program['loss']} reference loss {reference['loss']} reference stats "
        f"{reference['stats']} program stats {captured['stats']}"
    )

    # ---- metrics ----------------------------------------------------------
    device = device_stamp(cell.chips)
    device["memory_peak_bytes"] = peak
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": rounds, "failed": failed,
        "window": window,
    }
    if not trace:
        values = {**window, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }
    else:
        from benchmark import trace_reduce

        scope_map = trace_reduce.scope_map_from_hlo(hlo)
        reduction = trace_reduce.reduce_dir(trace_dir, scope_map)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            with open(f"{keep_trace}/scope_map.json", "w") as f:
                json.dump(scope_map, f)
            with open(f"{keep_trace}/programs.hlo.txt", "w") as f:
                f.write("\n".join(hlo))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(
            f"trace {traced['rounds']} rounds in {traced['window_s']:.3f}s: device "
            f"busy {reduction.busy_s:.4f}s of {reduction.window_s:.4f}s; scopes "
            f"{ {k: round(v, 5) for k, v in sorted(reduction.scope_s.items())} } "
            f"no scope {reduction.unscoped_s:.5f}s; innermost "
            f"{ {k: round(v, 5) for k, v in sorted(reduction.leaf_s.items())} } "
            f"no scope {reduction.leaf_unscoped_s:.5f}s; programs "
            f"{ {k: round(v, 5) for k, v in sorted(reduction.program_s.items())} }"
        )
        context = {
            "cell": cell, "spans": spans.seconds,
            "counters": {**setup_counters, "memory_peak_bytes": peak},
            "trace": reduction, "traced_rounds": traced["rounds"],
            "traced_window_s": traced["window_s"],
            "peaks": load_peaks(device["kind"]),
            "samples_per_round": samples,
            "param_dtype": inputs["param_dtype"],
        }
        result["metrics"] = read_layer_metrics(cell, context)
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def read_layer_metrics(cell: Cell, context: Dict[str, Any]) -> Dict[str, Any]:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = cell.layer_metric(m["name"])
        reader = cell.module("readers", spec["reader"])
        value = reader.read(context, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
