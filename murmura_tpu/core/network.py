"""Network orchestrator (reference: murmura/core/network.py:16-312).

Drives the jitted round step across rounds, maintains the reference's
history schema (network.py:47-58), and exposes per-node aggregator
statistics (network.py:201-210).  The same orchestrator serves both the
``simulation`` backend (single device) and the ``tpu`` backend (node axis
sharded over a mesh) — only the compilation of the step differs.
"""

import contextlib
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from murmura_tpu.attacks.base import Attack
from murmura_tpu.core.rounds import RoundProgram
from murmura_tpu.telemetry.host_spans import add_counters, span
from murmura_tpu.topology.base import Topology
from murmura_tpu.topology.dynamic import MobilityModel


def effective_adjacency(
    topology, mobility, fault_schedule, round_idx: int
) -> np.ndarray:
    """One round's effective [N, N] adjacency: mobility G^t (or the static
    mask) with the fault-schedule masks folded in host-side.  Shared by
    the single-run orchestrator and the gang dispatch path (core/gang.py)
    so the fold-in semantics cannot drift between them."""
    if mobility is not None:
        adj = mobility.adjacency_at(round_idx).astype(np.float32)
    else:
        adj = topology.mask()
    if fault_schedule is not None:
        # adj * alive_i * alive_j * link_mask * straggler columns —
        # folded host-side so the compiled program only ever sees a
        # differently-valued adjacency input.
        adj = fault_schedule.masked_adjacency(adj, round_idx)
    return adj


def effective_edge_mask(topology, fault_schedule, round_idx: int) -> np.ndarray:
    """One round's effective [k, N] sparse edge mask (topology/sparse.py):
    the SparseTopology schedule (static all-ones / one_peer single-offset)
    with the fault-schedule masks folded in host-side — the sparse twin of
    :func:`effective_adjacency`, consumed by round programs built with
    ``sparse_offsets``.  O(k·N) host work per round, never O(N^2)."""
    mask = topology.edge_mask(round_idx)
    if fault_schedule is not None:
        mask = fault_schedule.masked_edge_mask(
            mask, topology.offsets, round_idx
        )
    return mask


def effective_alive(fault_schedule, num_nodes: int, round_idx: int) -> np.ndarray:
    """[N] float32 alive mask for a faulted program's extra input (shared
    single-run/gang helper, see :func:`effective_adjacency`)."""
    if fault_schedule is not None:
        return fault_schedule.alive_at(round_idx)
    return np.ones(num_nodes, dtype=np.float32)


@contextlib.contextmanager
def sanitizer_scope(owner):
    """Arm the opt-in runtime sanitizers around one train() call.

    ``owner`` (Network or GangNetwork — one shared contract) provides
    ``transfer_guard``/``recompile_guard`` flags and receives ``_tracker``
    during the scope plus ``last_compile_report`` on exit.

    ``tpu.transfer_guard``: jax.transfer_guard("disallow") over the round
    loop — the loop's deliberate transfers are explicit (jnp.asarray /
    device_put / device_get) and pass; implicit traffic raises.
    ``tpu.recompile_guard``: a CompileTracker the round loops bracket each
    round with; post-warmup compiles raise RecompileError.
    """
    with contextlib.ExitStack() as stack:
        if owner.transfer_guard:
            from murmura_tpu.analysis.sanitizers import transfer_sanitizer

            stack.enter_context(transfer_sanitizer())
        if owner.recompile_guard:
            from murmura_tpu.analysis.sanitizers import track_compiles

            owner._tracker = stack.enter_context(track_compiles())
        try:
            yield
        finally:
            if owner._tracker is not None:
                owner.last_compile_report = list(owner._tracker.per_round)
            owner._tracker = None


def empty_history() -> Dict[str, List[Any]]:
    """The reference's history schema (network.py:47-58) — shared by the
    single-run orchestrator and the gang dispatch path (core/gang.py) so
    the two cannot drift."""
    return {
        "round": [],
        "mean_accuracy": [],
        "std_accuracy": [],
        "mean_loss": [],
        "honest_accuracy": [],
        "compromised_accuracy": [],
        "mean_vacuity": [],
        "mean_entropy": [],
        "mean_strength": [],
    }


def record_round_metrics(
    history: Dict[str, List[Any]],
    round_num: int,
    metrics: Dict[str, np.ndarray],
    compromised: np.ndarray,
    evidential: bool,
    has_attack: bool,
) -> Dict[str, np.ndarray]:
    """Append one evaluated round to ``history``; returns the round's raw
    per-node ``agg_*`` stats (the ``get_node_statistics`` source).

    This is the single source of truth for how device metrics become
    history floats — the gang-parity contract (a gang member's history is
    byte-identical to its single run, tests/test_gang.py) rides on both
    paths sharing it.
    """
    acc = np.asarray(metrics["accuracy"])
    loss = np.asarray(metrics["loss"])
    comp = np.asarray(compromised) > 0

    history["round"].append(round_num)
    history["mean_accuracy"].append(float(acc.mean()))
    history["std_accuracy"].append(float(acc.std()))
    history["mean_loss"].append(float(loss.mean()))
    if has_attack and comp.any():
        history["honest_accuracy"].append(float(acc[~comp].mean()))
        history["compromised_accuracy"].append(float(acc[comp].mean()))
    if evidential:
        history["mean_vacuity"].append(float(np.asarray(metrics["vacuity"]).mean()))
        history["mean_entropy"].append(float(np.asarray(metrics["entropy"]).mean()))
        history["mean_strength"].append(
            float(np.asarray(metrics["strength"]).mean())
        )

    last_stats = {
        k[len("agg_"):]: np.asarray(v)
        for k, v in metrics.items()
        if k.startswith("agg_")
    }
    # Per-round rule statistics (acceptance rates, thresholds, trust...)
    # accumulate in the history under their agg_ keys — the reference
    # buries these in aggregator-internal lists surfaced only via
    # get_statistics() (e.g. balance.py:46-53).
    for k, v in last_stats.items():
        arr = np.asarray(v, dtype=np.float64)
        history.setdefault(f"agg_{k}", []).append(
            float(arr.mean()) if arr.ndim else float(arr)
        )
    return last_stats


class Network:
    """Orchestrates decentralized FL over a compiled round program."""

    def __init__(
        self,
        program: RoundProgram,
        topology: Topology,
        attack: Optional[Attack] = None,
        mobility: Optional[MobilityModel] = None,
        backend: str = "simulation",
        mesh=None,
        seed: int = 42,
        donate: bool = True,
        profile_dir: Optional[str] = None,
        recompile_guard: bool = False,
        transfer_guard: bool = False,
        fault_schedule=None,
        telemetry=None,
    ):
        self.program = program
        self.topology = topology
        self.attack = attack
        self.mobility = mobility
        self.backend = backend
        self.seed = seed
        self.profile_dir = profile_dir
        # Operational fault model (faults/schedule.py): per-round alive and
        # link masks fold into the adjacency input and the faulted
        # program's alive argument — values only, no recompiles (the same
        # trick the compromised mask and mobility G^t already use).
        self.fault_schedule = fault_schedule
        # Telemetry (telemetry/writer.py, docs/OBSERVABILITY.md): when a
        # writer is attached, the round loops emit phase_times / round /
        # memory / checkpoint events and each train() call re-finalizes
        # the run manifest.  None (default) leaves every loop byte-for-byte
        # on its pre-telemetry path — histories and compiled programs are
        # identical (tested, tests/test_telemetry.py).
        self.telemetry = telemetry
        self._profile_window_active = False
        # round_idx -> host in-degree of the round's effective adjacency,
        # captured as a byproduct of the dispatch loop's own adjacency
        # computation so _record's round events never re-run the mobility
        # G^t / fault masking (O(N^2) host work) inside the timed window.
        self._in_degree_cache: Dict[int, np.ndarray] = {}
        if fault_schedule is not None and not program.faulted:
            raise ValueError(
                "A fault schedule was supplied but the round program was "
                "built without faults (build_round_program(faults=...)); "
                "the alive mask would silently never reach the round step"
            )
        # Opt-in runtime sanitizers (tpu.recompile_guard / tpu.transfer_guard;
        # analysis/sanitizers.py).  Backend-independent: the simulation
        # backend exercises them in CI where no chip is at stake.
        self.recompile_guard = recompile_guard
        self.transfer_guard = transfer_guard
        self._tracker = None
        # (label, compiles) per round bracket from the last guarded train()
        # — diagnostics for tests and post-mortems.
        self.last_compile_report: Optional[List] = None
        # Programs that have already executed once (and thus compiled):
        # "step", "eval", ("fused", chunk, eval_every).  A compile in any
        # later round is a post-warmup recompile and fails the guard.
        self._warmed: set = set()

        n = program.num_nodes
        if topology.num_nodes != n:
            raise ValueError(
                f"Topology has {topology.num_nodes} nodes, data/model stack has {n}"
            )
        if program.sparse:
            from murmura_tpu.topology.sparse import SparseTopology

            if not isinstance(topology, SparseTopology):
                raise ValueError(
                    "the round program was built with sparse_offsets but "
                    "the topology is not a SparseTopology — the program's "
                    "adjacency input is a [k, N] edge mask only a sparse "
                    "topology can produce"
                )
            if tuple(topology.offsets) != tuple(program.sparse_offsets):
                raise ValueError(
                    f"sparse topology offsets {tuple(topology.offsets)} != "
                    f"round program offsets {tuple(program.sparse_offsets)}"
                )
            if mobility is not None:
                raise ValueError(
                    "sparse exchange mode does not compose with mobility "
                    "(G^t is a dense per-round graph)"
                )

        self.compromised = (
            attack.compromised.astype(np.float32)
            if attack is not None
            else np.zeros(n, dtype=np.float32)
        )

        if backend == "tpu":
            from murmura_tpu.parallel.mesh import (
                adj_stack_sharding,
                make_shardings,
                shard_eval_step,
                shard_step,
            )

            if mesh is None:
                from murmura_tpu.parallel.mesh import make_mesh

                mesh = make_mesh()
            self.mesh = mesh
            self._step = shard_step(program.train_step, program, mesh, donate=donate)
            self._eval = shard_eval_step(program.eval_step, program, mesh)
            self._node_s, self._repl = make_shardings(mesh)
            if program.sparse:
                # Sparse adjacency inputs carry the node axis SECOND
                # ([k, N] per-round mask, [chunk, k, N] fused stack).
                from murmura_tpu.parallel.mesh import (
                    edge_mask_sharding,
                    sparse_adj_stack_sharding,
                )

                self._adj_s = edge_mask_sharding(mesh)
                self._adj_stack_s = sparse_adj_stack_sharding(mesh)
            else:
                self._adj_s = self._node_s
                self._adj_stack_s = adj_stack_sharding(mesh)
        else:
            self.mesh = None
            donate_argnums = (0, 1) if donate else ()
            self._step = jax.jit(program.train_step, donate_argnums=donate_argnums)
            self._eval = jax.jit(program.eval_step)
            self._node_s = self._repl = None
            self._adj_s = self._adj_stack_s = None
        if transfer_guard and jax.process_count() > 1:
            raise ValueError(
                "tpu.transfer_guard is single-host only: multi-host "
                "resident state cannot be explicitly pre-placed with "
                "jax.device_put, so the guard would flag the legitimate "
                "cross-process staging"
            )

        # Mutable run state
        # A large initial state is the program's on the host
        # (rounds.LARGE_STATE_BYTES); a device array passes through as it is.
        self.params = jax.tree_util.tree_map(jnp.asarray, program.init_params)
        self.agg_state = {k: jnp.asarray(v) for k, v in program.init_agg_state.items()}
        self._data = {k: jnp.asarray(v) for k, v in program.data_arrays.items()}
        self._place_resident_state()
        # Base key; round r always runs with fold_in(base, r), so the stream
        # is a pure function of (seed, round) — identical across per-round
        # and fused dispatch, any rounds_per_dispatch chunking, and
        # checkpoint resume points.
        self._rng = jax.random.PRNGKey(seed)
        # Jitted so its internal constants compile into the program instead
        # of landing as per-round implicit host->device transfers (eager
        # fold_in stages them eagerly and trips tpu.transfer_guard).
        self._fold_in = jax.jit(jax.random.fold_in)
        # Deferred-quiesce scalar fetch (see _train_rounds): built once here
        # so repeated defer_metrics train() calls reuse one compile cache
        # instead of paying a fresh XLA compile per call.
        self._first_scalar = jax.jit(
            lambda tree: jax.tree_util.tree_leaves(tree)[0].ravel()[0]
        )

        # History schema parity (reference: network.py:47-58)
        self.history: Dict[str, List[Any]] = empty_history()
        self._last_stats: Dict[str, np.ndarray] = {}
        self._donate = donate
        self._fused_cache: Dict[Any, Any] = {}
        self.round_times: List[float] = []
        # Persistent round counter: schedules (BALANCE/trust tightening,
        # evidential-loss annealing) and the mobility G^t keep advancing
        # across successive train() calls and checkpoint resumes.
        self.current_round = 0

    @property
    def params(self):
        """The stacked [N, ...] state of the run."""
        return self._params

    @params.setter
    def params(self, value) -> None:
        # ``params = None`` is how the benchmark's harness gives the device
        # back before its reference runs (benchmark/harness.py ``free``),
        # and a PR that adds a cell may not edit the harness: until a
        # ``benchmark`` PR calls ``unload()`` there, that assignment is the
        # call (PERF.md section 7, item 6 h).  Then this property goes.
        if value is None:
            self.unload()
        else:
            self._params = value

    def unload(self) -> None:
        """Drop the state and this network's compiled programs, to have the
        device back.  On a TPU a loaded program keeps its scratch memory
        reserved (5.46 GiB for the round step of three 568M-parameter
        nodes), and ``jax.clear_caches`` is the one handle that frees the
        reservation (a jitted function's own ``clear_cache`` leaves the
        loaded program where it is: read on the chip, PERF.md section 6,
        PR 34), so **every compiled program of the process goes**: the next
        call of any of them compiles, or loads from the persistent cache,
        again.  A network that has compiled nothing clears nothing."""
        self._params = None
        programs = (
            getattr(self, "_step", None), getattr(self, "_eval", None),
            *getattr(self, "_fused_cache", {}).values(),
        )
        compiled = self.__dict__.get("_aot_compiled") is not None or any(
            getattr(fn, "_cache_size", lambda: 0)() for fn in programs
        )
        self._aot_compiled = None
        if compiled:
            jax.clear_caches()

    def _place_resident_state(self) -> None:
        """Explicitly place params/agg_state/data on the mesh (tpu backend,
        single host).

        Without this the first sharded jit call reshards every single-device
        input implicitly — a device-to-device transfer per buffer that (a)
        trips tpu.transfer_guard and (b) repeats after every checkpoint
        restore.  Multi-host placement stays with the jit staging path
        (device_put cannot target non-addressable devices).
        """
        if self._node_s is None or jax.process_count() > 1:
            return
        from murmura_tpu.parallel.mesh import (
            _shard_leading_axis,
            mesh_param_shards,
            state_sharding_specs,
        )

        if self.mesh is not None and mesh_param_shards(self.mesh) > 1:
            # Param-sharded placement: [N, flat_dim] leaves (the stale
            # cache, pipeline buffers, EF residual) land column-split
            # over the "param" axis — the layout the jit expects, so the
            # first call (and every restore) stays reshard-free.
            flat_dim = self.program.flat_dim or self.program.model_dim
            place = lambda tree: jax.device_put(  # noqa: E731
                tree, state_sharding_specs(tree, self.mesh, flat_dim)
            )
            self.params = place(self.params)
            self.agg_state = place(self.agg_state)
            self._data = jax.device_put(
                self._data,
                _shard_leading_axis(self._data, self._node_s, self._repl),
            )
            return
        place = lambda tree: jax.device_put(  # noqa: E731
            tree, _shard_leading_axis(tree, self._node_s, self._repl)
        )
        self.params = place(self.params)
        self.agg_state = place(self.agg_state)
        self._data = place(self._data)

    def _stage(self, value, sharding):
        """Stage one loop input explicitly: plain device transfer off-mesh,
        ``jax.device_put`` to the target sharding on the tpu backend (jit
        would otherwise reshard implicitly — see _place_resident_state).

        Multi-host keeps the jit ``in_shardings`` staging path: device_put
        to a non-addressable sharding is a blocking cross-process broadcast
        collective per call (and unsupported on some backends), which would
        cost more per round than the implicit reshard it avoids.
        """
        if sharding is None or jax.process_count() > 1:
            return jnp.asarray(value)
        return jax.device_put(value, sharding)

    def _adjacency_for_round(self, round_idx: int) -> np.ndarray:
        if self.program.sparse:
            mask = effective_edge_mask(
                self.topology, self.fault_schedule, round_idx
            )
            if self.telemetry is not None:
                self._in_degree_cache[round_idx] = (
                    self.topology.in_degree_from_edge_mask(mask)
                )
            return mask
        adj = effective_adjacency(
            self.topology, self.mobility, self.fault_schedule, round_idx
        )
        if self.telemetry is not None:
            self._in_degree_cache[round_idx] = np.asarray(adj).sum(axis=0)
        return adj

    def _alive_for_round(self, round_idx: int) -> np.ndarray:
        """[N] float32 alive mask for a faulted program's extra input."""
        return effective_alive(
            self.fault_schedule, self.program.num_nodes, round_idx
        )

    def exchange_cost_analysis(self) -> Dict[str, float]:
        """Analytic per-round exchange accounting (docs/PERFORMANCE.md).

        ``exchange_bytes_per_round`` is edges x the bytes of the
        representation that actually crosses an edge — the full [P] row in
        the resident dtype, or the compressed payload (int8 blocks+scales /
        top-k values+indices) when the program was built with a
        ``compression`` spec.  Everything here is counted from shapes
        (edges x payload bytes), nothing is timed: the bytes reduction of
        a codec is attributable to it, not a claim (what it is worth in
        milliseconds is a chip measurement, PERF.md).
        """
        import jax.numpy as _jnp

        p = self.program.model_dim
        leaf = jax.tree_util.tree_leaves(self.program.init_params)[0]
        itemsize = _jnp.dtype(leaf.dtype).itemsize
        if self.program.sparse:
            edges = float(
                np.asarray(
                    effective_edge_mask(
                        self.topology, self.fault_schedule, self.current_round
                    )
                ).sum()
            )
        else:
            edges = float(
                np.asarray(
                    effective_adjacency(
                        self.topology, self.mobility, self.fault_schedule,
                        self.current_round,
                    )
                ).sum()
            )
        comp = self.program.compression
        uncompressed = float(p * itemsize)
        payload = (
            float(comp.payload_bytes(p, itemsize))
            if comp is not None
            else uncompressed
        )
        return {
            "edges": edges,
            "payload_bytes_per_edge": payload,
            "uncompressed_bytes_per_edge": uncompressed,
            "exchange_bytes_per_round": edges * payload,
            "uncompressed_exchange_bytes_per_round": edges * uncompressed,
            "exchange_bytes_reduction": (
                uncompressed / payload if payload else None
            ),
        }

    def _step_compiled(self):
        """AOT-compile the train step on the shapes ``train`` runs.

        Memoized so :meth:`step_cost_analysis` and
        :meth:`step_memory_analysis` (and any future AOT introspection)
        share one compile — the jit cache is keyed on the same shapes, so
        ``train`` afterwards still hits it and nothing executes here.
        """
        compiled = getattr(self, "_aot_compiled", None)
        if compiled is not None:
            return compiled
        args = [
            self.params,
            self.agg_state,
            jax.random.PRNGKey(0),
            jnp.asarray(self._adjacency_for_round(self.current_round)),
            jnp.asarray(self.compromised),
            jnp.asarray(0.0, dtype=jnp.float32),
            self._data,
        ]
        if self.program.faulted:
            args.insert(5, jnp.asarray(self._alive_for_round(self.current_round)))
        compiled = self._step.lower(*args).compile()
        self._aot_compiled = compiled
        return compiled

    def step_cost_analysis(self) -> Dict[str, float]:
        """XLA cost analysis of the compiled train step (flops, bytes).

        Uses the AOT path on the same shapes ``train`` runs, so the compile
        cache is hit and nothing executes.  These are the compiler's
        counts for one built run, not a measurement: the runtime twin of
        the per-aggregator budget sweep (``murmura check --ir``,
        analysis/budgets.py — which also owns the cross-version result
        normalization used here).  Covers the
        per-round program only — eval is compiled separately and runs on the
        ``eval_every`` cadence, so its flops are not part of a round.
        """
        from murmura_tpu.analysis.budgets import normalize_cost_analysis

        return normalize_cost_analysis(self._step_compiled().cost_analysis())

    def step_memory_analysis(self) -> Dict[str, float]:
        """XLA memory analysis of the compiled train step (bytes).

        Runtime twin of the MUR1500 memory-budget sweep (``murmura check
        --memory``, analysis/memory.py — which owns the cross-version
        normalization used here).  Shares the AOT compile with
        :meth:`step_cost_analysis`, so asking for both costs one compile.
        ``peak_bytes`` is the static accounting identity
        arguments + outputs - aliased + temporaries + generated code; on
        backends whose ``memory_analysis()`` lacks a field it contributes
        zero rather than failing.
        """
        from murmura_tpu.analysis.memory import normalize_memory_analysis

        return normalize_memory_analysis(
            self._step_compiled().memory_analysis()
        )

    def train(
        self,
        rounds: int,
        verbose: bool = False,
        eval_every: int = 1,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        defer_metrics: bool = False,
        rounds_per_dispatch: int = 1,
    ) -> Dict[str, List[Any]]:
        """Run the FL rounds (reference: network.py:60-94).

        Evaluation is a separately compiled program run only on rounds that
        are recorded (``eval_every``) — unlike the reference, whose loop
        evaluates every round (network.py:141-199), skipped-eval rounds pay
        zero eval FLOPs here.

        Args:
            checkpoint_dir: if set, write a checkpoint after every
                ``checkpoint_every`` rounds (and at the end). No reference
                counterpart — the reference keeps all state in memory.
            defer_metrics: keep per-round metrics on device and record them
                only after the last round.  Removes the host sync from the
                round loop so XLA queues rounds back-to-back (throughput
                mode — history is identical, per-round ``round_times``
                become dispatch times rather than wall round times).
                Only meaningful for per-round dispatch: with
                ``rounds_per_dispatch > 1`` the fused scan already fetches
                metrics once per chunk, so ``defer_metrics`` is ignored
                (a warning is emitted).
            rounds_per_dispatch: fuse this many rounds into one
                ``lax.scan`` program (core.rounds.build_multi_round) — the
                round loop lives on the device and history comes back as
                stacked arrays per chunk.  Eval still runs only on the
                ``eval_every`` cadence (``lax.cond`` inside the scan).
                Checkpoints land on chunk boundaries.  1 = per-round
                dispatch (default).
        """
        from murmura_tpu.analysis.sanitizers import CompileTracker

        profile = self.profile_dir is not None
        if profile:
            jax.profiler.start_trace(self.profile_dir)
        # Passive compile accounting independent of the recompile guard:
        # the manifest's `compiles` counter feeds the offline metrics fold
        # (telemetry/metrics.py), so a scrape can surface recompile churn
        # without arming the raising sanitizer.
        compile_probe = CompileTracker()
        try:
            with self._sanitizer_scope():
                if rounds_per_dispatch > 1:
                    if defer_metrics:
                        import warnings

                        warnings.warn(
                            "defer_metrics is ignored when rounds_per_dispatch > 1: "
                            "the fused scan already syncs metrics once per chunk",
                            stacklevel=2,
                        )
                    self._train_fused(
                        rounds, verbose, eval_every, checkpoint_dir,
                        checkpoint_every, rounds_per_dispatch,
                    )
                else:
                    self._train_rounds(
                        rounds, verbose, eval_every, checkpoint_dir,
                        checkpoint_every, defer_metrics,
                    )
        finally:
            if profile:
                jax.profiler.stop_trace()
            # Close a still-open telemetry profile window (the run may end
            # mid-window) and commit the manifest: each train() call
            # re-finalizes, so the manifest is always the latest complete
            # view even across checkpoint/resume segments.
            self._profile_window_stop(self.current_round, force=True)
            if self.telemetry is not None:
                compiled = compile_probe.total
                if compiled:
                    self.telemetry.add_counters({"compiles": compiled})
                self.telemetry.finalize(history=self.history)
        return self.history

    # ------------------------------------------------------------------
    # telemetry hooks (telemetry/writer.py; docs/OBSERVABILITY.md)

    def _profile_window_start(self, round_idx: int, span: int = 1) -> None:
        """Open the telemetry profiler window at its scheduled round.

        Skipped while the legacy whole-train trace (``tpu.profile_dir``)
        is active — jax.profiler traces do not nest.  On the fused path
        this is called at chunk boundaries with ``span`` = chunk size, so
        the window opens at the first chunk OVERLAPPING it — a start round
        strictly inside a chunk must not be skipped (the rounds
        [round_idx, round_idx + span) dispatch as one program; containment
        of round_idx alone would miss it).
        """
        t = self.telemetry
        if (
            t is None
            or not t.profile_rounds
            or self._profile_window_active
            or self.profile_dir is not None
        ):
            return
        end = t.profile_start_round + t.profile_rounds
        if round_idx < end and round_idx + span > t.profile_start_round:
            trace_dir = t.profile_dir or str(t.run_dir / "trace")
            jax.profiler.start_trace(trace_dir)
            self._profile_window_active = True
            t.emit(
                "profile", status="started", round=round_idx,
                trace_dir=trace_dir,
            )

    def _profile_window_stop(self, next_round: int, force: bool = False) -> None:
        t = self.telemetry
        if t is None or not self._profile_window_active:
            return
        if force or next_round >= t.profile_start_round + t.profile_rounds:
            jax.profiler.stop_trace()
            self._profile_window_active = False
            t.emit(
                "profile", status="stopped", round=next_round - 1,
                trace_dir=t.profile_dir or str(t.run_dir / "trace"),
            )

    def _phase_overlap(self) -> Dict[str, str]:
        """Extra phase_times fields describing in-dispatch concurrency.

        A pipelined program (exchange.pipeline) runs train and the
        delayed exchange+aggregate concurrently inside every dispatch:
        the recorded wall time is the round's CRITICAL PATH, and the
        per-phase named_scope brackets overlap in profiler-trace time —
        summing them would double-count the hidden exchange.  The
        ``overlap`` marker lets ``murmura report`` render a
        critical-path decomposition instead (telemetry/report.py);
        serialized programs emit no marker, keeping their phase_times
        records byte-identical to previous releases (pinned by
        tests/test_pipeline.py).
        """
        if self.program.pipelined:
            return {"overlap": "pipelined"}
        return {}

    def _sanitizer_scope(self):
        """The shared :func:`sanitizer_scope` over this orchestrator."""
        return sanitizer_scope(self)

    def _fused_step(self, chunk: int, eval_every: int):
        """Compiled fused multi-round program, cached per (chunk, cadence)."""
        key = (chunk, eval_every)
        if key not in self._fused_cache:
            from murmura_tpu.core.rounds import build_multi_round

            fn = build_multi_round(self.program, chunk, eval_every)
            if self.backend == "tpu":
                from murmura_tpu.parallel.mesh import shard_multi_round

                self._fused_cache[key] = shard_multi_round(
                    fn, self.program, self.mesh, donate=self._donate
                )
            else:
                donate_argnums = (0, 1) if self._donate else ()
                self._fused_cache[key] = jax.jit(
                    fn, donate_argnums=donate_argnums
                )
        return self._fused_cache[key]

    def _fused_inputs(self, round0: int, k: int, comp) -> List[Any]:
        """Stage one fused chunk's inputs (the murmura.host.stage span)."""
        adj_stack = self._stage(
            np.stack(
                [self._adjacency_for_round(round0 + i) for i in range(k)]
            ),
            self._adj_stack_s,
        )
        step_args = [
            self.params,
            self.agg_state,
            self._stage(self._rng, self._repl),
            adj_stack,
            comp,
            self._stage(np.asarray(round0, np.int32), self._repl),
            self._data,
        ]
        if self.program.faulted:
            # Per-round alive masks ride the scan like the adj stack.
            step_args.insert(
                5,
                self._stage(
                    np.stack(
                        [self._alive_for_round(round0 + i) for i in range(k)]
                    ),
                    self._adj_stack_s,
                ),
            )
        return step_args

    def _train_fused(
        self, rounds, verbose, eval_every, checkpoint_dir, checkpoint_every,
        chunk,
    ) -> None:
        from murmura_tpu.analysis.sanitizers import compile_count

        comp = self._stage(self.compromised, self._node_s)
        overlap = self._phase_overlap()
        done = 0
        while done < rounds:
            k = min(chunk, rounds - done)
            step = self._fused_step(k, eval_every)
            round0 = self.current_round
            self._profile_window_start(round0, span=k)
            program_key = ("fused", k, eval_every)
            # One chunk is one murmura.round span (rounds=k): the chunk
            # runs as a single device program, so the host sees no round
            # boundary inside it.
            with span(
                "murmura.round", round=round0, step=True, rounds=k
            ) as chunk_span:
                if self._tracker is not None:
                    self._tracker.begin(f"rounds {round0}..{round0 + k - 1}")
                with span("murmura.host.stage", round=round0):
                    step_args = self._fused_inputs(round0, k, comp)
                with span(
                    "murmura.host.dispatch", round=round0,
                    compiles=compile_count, program="fused",
                ):
                    self.params, self.agg_state, rows = step(*step_args)
                with span("murmura.host.fetch", round=round0):
                    rows = jax.device_get(rows)
                chunk_warmup = program_key not in self._warmed
                self._warmed.add(program_key)
                self.current_round = round0 + k
            # Keep round_times in per-round units across dispatch modes:
            # one amortized entry per round, not one per chunk (per-round
            # wall times inside one device program are not observable).
            # The chunk's time is the span's: one clock, read once.
            elapsed = chunk_span.seconds
            self.round_times.extend([elapsed / k] * k)
            done += k
            if self.telemetry is not None:
                # One amortized phase_times record per round, in the same
                # unit as round_times (mode records the split).
                with span("murmura.host.record", round=round0):
                    for i in range(k):
                        self.telemetry.phase_times(
                            round0 + i, "fused", elapsed / k, chunk=k, **overlap
                        )
                    self.telemetry.memory_event(self.current_round - 1)
                self._profile_window_stop(self.current_round)
            # The chunk's bookkeeping follows its span: round_times and
            # phase_times exclude it, and are recorded before anything
            # here can raise beside the already-advanced params.
            with span("murmura.host.record", round=round0):
                for i in range(k):
                    if rows["evaluated"][i]:
                        self._record(
                            round0 + i + 1,
                            {
                                m: v[i]
                                for m, v in rows.items()
                                if m != "evaluated"
                            },
                            verbose,
                        )
            # After the bookkeeping: a guard raise must leave
            # current_round/history aligned with the already-advanced
            # (donated) params, or a catch-and-checkpoint caller would
            # record k-rounds-stale metadata beside the new state.
            if self._tracker is not None:
                self._tracker.end(allow=chunk_warmup)
            crossed_cadence = checkpoint_every and (
                self.current_round // checkpoint_every > round0 // checkpoint_every
            )
            if checkpoint_dir and (crossed_cadence or done >= rounds):
                self.save_checkpoint(checkpoint_dir)

    def _round_inputs(self, round_idx: int, comp) -> List[Any]:
        """Stage one round's step inputs (the murmura.host.stage span)."""
        adj = self._stage(self._adjacency_for_round(round_idx), self._adj_s)
        # 0-d numpy staging: scalar conversions from numpy ARRAYS are
        # explicit transfers (transfer_guard-clean); Python/numpy
        # scalars would be implicit and trip the sanitizer.
        step_key = self._stage(
            self._fold_in(
                self._rng, jnp.asarray(np.asarray(round_idx, np.uint32))
            ),
            self._repl,
        )
        step_args = [
            self.params,
            self.agg_state,
            step_key,
            adj,
            comp,
            self._stage(np.asarray(round_idx, np.float32), self._repl),
            self._data,
        ]
        if self.program.faulted:
            step_args.insert(
                5, self._stage(self._alive_for_round(round_idx), self._node_s)
            )
        return step_args

    def _train_rounds(
        self, rounds, verbose, eval_every, checkpoint_dir, checkpoint_every,
        defer_metrics=False,
    ) -> None:
        from murmura_tpu.analysis.sanitizers import compile_count

        comp = self._stage(self.compromised, self._node_s)
        overlap = self._phase_overlap()
        last_saved = -1
        pending: List[Any] = []
        for _ in range(rounds):
            round_idx = self.current_round
            evaluated = (round_idx + 1) % eval_every == 0
            self._profile_window_start(round_idx)
            with span("murmura.round", round=round_idx, step=True) as round_span:
                warmup = "step" not in self._warmed
                if self._tracker is not None:
                    self._tracker.begin(f"round {round_idx}")
                with span("murmura.host.stage", round=round_idx):
                    step_args = self._round_inputs(round_idx, comp)
                with span(
                    "murmura.host.dispatch", round=round_idx,
                    compiles=compile_count, program="step",
                ):
                    self.params, self.agg_state, agg_metrics = self._step(
                        *step_args
                    )
                self._warmed.add("step")
                self.current_round = round_idx + 1
                if evaluated:
                    # Close the step phase before eval runs: eval's own
                    # warmup must not whitelist a post-warmup step
                    # recompile landing in the same round (and vice versa).
                    if self._tracker is not None:
                        self._tracker.mark(allow=warmup)
                    warmup = "eval" not in self._warmed
                    with span(
                        "murmura.host.dispatch", round=round_idx,
                        compiles=compile_count, program="eval",
                    ):
                        metrics = {
                            **self._eval(self.params, self._data), **agg_metrics
                        }
                    self._warmed.add("eval")
                    if defer_metrics:
                        pending.append((self.current_round, metrics))
                    else:
                        # Where the host waits for the device: the span an
                        # operator sees the round's device time in.
                        with span("murmura.host.fetch", round=round_idx):
                            metrics = jax.device_get(metrics)
                        with span("murmura.host.record", round=round_idx):
                            self._record(self.current_round, metrics, verbose)
                if self._tracker is not None:
                    self._tracker.end(allow=warmup)
            # The round's wall time is the span's: one clock, read once.
            wall = round_span.seconds
            self.round_times.append(wall)
            if self.telemetry is not None:
                with span("murmura.host.record", round=round_idx):
                    self.telemetry.phase_times(
                        round_idx, "per_round", wall,
                        evaluated=evaluated,
                        deferred=bool(defer_metrics),
                        **overlap,
                    )
                    self.telemetry.memory_event(round_idx)
                self._profile_window_stop(self.current_round)
            if (
                checkpoint_dir
                and checkpoint_every
                and self.current_round % checkpoint_every == 0
            ):
                self._drain_pending(pending, verbose)  # checkpointed history
                self.save_checkpoint(checkpoint_dir)   # must be complete
                last_saved = self.current_round
        self._drain_pending(pending, verbose)
        if defer_metrics and rounds > 0:
            # Quiesce: in deferred mode the only host syncs are the drained
            # metrics, which cover rounds only up to the last eval — any
            # later rounds are still in flight when the loop exits (and this
            # environment's block_until_ready does not block).  Fetching one
            # scalar that depends on the final params makes train() return
            # only after every dispatched round has executed, so wall-clock
            # timing around a deferred train() call is honest.
            if jax.process_count() == 1:
                # Jitted: eager [0]-indexing stages its slice start as an
                # implicit scalar transfer and trips tpu.transfer_guard.
                with span("murmura.host.fetch", round=self.current_round - 1):
                    jax.device_get(self._first_scalar(self.params))
            else:
                # Multi-host: params are sharded across non-addressable
                # devices, so a scalar fetch would raise; block on the
                # sharded tree instead (real TPU runtimes do block here).
                jax.block_until_ready(self.params)
        if checkpoint_dir and rounds > 0 and self.current_round != last_saved:
            self.save_checkpoint(checkpoint_dir)

    def _drain_pending(self, pending: List[Any], verbose: bool) -> None:
        for round_num, metrics in pending:
            with span("murmura.host.fetch", round=round_num - 1):
                metrics = jax.device_get(metrics)
            with span("murmura.host.record", round=round_num - 1):
                self._record(round_num, metrics, verbose)
        pending.clear()

    def save_checkpoint(self, directory: str) -> None:
        """Snapshot the complete run state to ``directory``
        (durability/snapshot.py over the fsync'd utils/checkpoint.py
        path)."""
        from murmura_tpu.durability.snapshot import save_run_snapshot

        with span(
            "murmura.host.checkpoint", round=self.current_round, action="save"
        ) as saved:
            save_run_snapshot(directory, self)
        if self.telemetry is not None:
            self.telemetry.checkpoint_event(
                self.current_round, saved.seconds,
                action="save", path=str(directory),
            )

    def restore_checkpoint(self, directory: str) -> int:
        """Restore run state; returns the round to continue from.

        Value-only into the (possibly warm) compiled program — zero extra
        compiles, donation-safe (restored buffers are fresh).  Emits a
        ``run_resumed`` telemetry event so a resumed run is visible in
        the event stream it APPENDS to (the writer must have been opened
        with ``resume=True`` — factories.build_network_from_config does
        this automatically when a checkpoint exists).
        """
        from murmura_tpu.durability.snapshot import restore_run_snapshot

        with span("murmura.host.checkpoint", action="restore") as restored:
            round_num = restore_run_snapshot(directory, self)
        if self.telemetry is not None:
            self.telemetry.checkpoint_event(
                round_num, restored.seconds,
                action="restore", path=str(directory),
            )
            self.telemetry.emit(
                "run_resumed", round=round_num, path=str(directory),
                run_id=self.telemetry.run_id,
            )
        return round_num

    # ------------------------------------------------------------------
    # durability hooks (durability/snapshot.py): what a complete snapshot
    # of THIS orchestrator carries beyond the base sections.  Subclasses
    # (PopulationNetwork, and the gang twin in core/gang.py) override.

    def _durability_history(self):
        """The json-able history section of a snapshot."""
        return self.history

    def _durability_set_history(self, history) -> None:
        self.history = history

    def _durability_extra_state(self):
        """(arrays, meta) extra sections; the base orchestrator carries
        the telemetry run id (stable across resumes — writer.py) and, for
        param-sharded programs, the shard count (gather-on-save makes the
        *values* layout-free, but the flat PAD is a function of the shard
        count, so a different-shard restore must refuse loudly instead of
        loading a wrong-width cache row)."""
        meta = {}
        if self.telemetry is not None:
            meta["telemetry_run_id"] = self.telemetry.run_id
        if self.program.param_shards > 1:
            meta["param_shards"] = int(self.program.param_shards)
        return {}, meta

    def _durability_validate_extra(self, arrays, meta) -> None:
        """Pure pre-restore validation, called BEFORE any live state is
        mutated — raise to refuse the snapshot.  A gang snapshot carries
        its member data in extra_meta with NO extra arrays, and flax's
        from_bytes would happily load its [S, ...]-stacked leaves into a
        single run — so refuse on meta keys too, symmetric with the
        gang/population guards."""
        foreign = sorted(set(arrays) | ({"gang", "population"} & set(meta)))
        if foreign:
            raise ValueError(
                f"snapshot carries extra sections {foreign} this "
                "orchestrator does not understand — it was written by a "
                "population/gang run; rebuild with the matching config"
            )
        snap_shards = int(meta.get("param_shards", 1))
        ours = int(self.program.param_shards)
        if snap_shards != ours:
            # The flat pad is shards-dependent (ops/flatten.padded_dim),
            # so even when two shard counts happen to produce the same
            # padded width, a cross-shard restore is a different program
            # family — refuse loudly, symmetric with the gang/population
            # identity guards (satellite: restoring a 4-shard snapshot
            # into a 2-shard mesh must refuse, not silently reshard).
            raise ValueError(
                f"snapshot was written by a param-sharded run with "
                f"tpu.param_shards={snap_shards} but this run has "
                f"param_shards={ours} — the flat pad (and the mesh "
                "layout the cache rows restore into) is a function of "
                "the shard count; rebuild with the matching "
                "tpu.param_shards"
            )

    def _durability_restore_extra(self, arrays, meta) -> None:
        """Apply orchestrator-specific sections after the base restore;
        validation already happened in ``_durability_validate_extra``."""

    def _record(self, round_num: int, metrics: Dict[str, np.ndarray], verbose: bool):
        acc = np.asarray(metrics["accuracy"])
        last_stats = record_round_metrics(
            self.history, round_num, metrics, self.compromised,
            self.program.evidential, self.attack is not None,
        )
        add_counters({f"agg_{k}": self.history[f"agg_{k}"][-1] for k in last_stats})

        if self.telemetry is not None:
            # Per-node arrays of the recorded round (accuracy, agg_* rule
            # stats, agg_tap_* audit taps) plus the host-side in-degree of
            # the round's effective adjacency — the sender-side context
            # `murmura report` turns tap counts into rejection counts
            # with.  The in-degree was cached when the dispatch loop built
            # the round's adjacency; the fallback recompute only fires for
            # out-of-band _record calls (none today).
            in_deg = self._in_degree_cache.pop(round_num - 1, None)
            # Unrecorded rounds (eval_every > 1) never pop their entries;
            # prune everything at or below the recorded round so the cache
            # stays O(eval_every), not O(total rounds).
            self._in_degree_cache = {
                r: v for r, v in self._in_degree_cache.items()
                if r >= round_num
            }
            if in_deg is None:
                # Re-running the round's adjacency build repopulates the
                # cache with the mode-correct in-degree (dense column sums
                # or the sparse edge-mask roll sums).
                self._adjacency_for_round(round_num - 1)
                in_deg = self._in_degree_cache.pop(round_num - 1)
            self.telemetry.round_event(
                round_num,
                {k: np.asarray(v) for k, v in metrics.items()},
                in_degree=in_deg,
            )
        self._last_stats = last_stats

        if verbose:
            comp = self.compromised > 0
            line = f"Round {round_num}: Mean Accuracy = {acc.mean():.4f} ± {acc.std():.4f}"
            print(line, flush=True)
            if self.attack is not None and comp.any():
                print(
                    f"  Honest: {acc[~comp].mean():.4f}, "
                    f"Compromised: {acc[comp].mean():.4f}",
                    flush=True,
                )
            if self.program.evidential:
                print(
                    f"  Uncertainty: Vacuity={np.asarray(metrics['vacuity']).mean():.4f}, "
                    f"Entropy={np.asarray(metrics['entropy']).mean():.4f}, "
                    f"Strength={np.asarray(metrics['strength']).mean():.2f}",
                    flush=True,
                )

    def get_node_statistics(self) -> Dict[int, Dict[str, Any]]:
        """Per-node aggregator statistics (reference: network.py:201-210)."""
        n = self.program.num_nodes
        return {
            i: {k: float(v[i]) for k, v in self._last_stats.items()}
            for i in range(n)
        }
