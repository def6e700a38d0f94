"""Gang-batched multi-seed execution — vmap the round program over an
experiment axis (ISSUE 5; docs/PERFORMANCE.md).

The paper's evaluation is a grid: every (rule x attack x topology) cell is
re-run across seeds, yet one network per process pays the full trace/compile
for seconds of rounds, and a small-N round
leaves the device mostly idle.  A *gang* stacks S independent experiments —
differing in seed, and optionally in traced scalar hyperparameters (lr,
attack intensity) — into leading-axis-``[S, ...]`` inputs and ``jax.vmap``s
the existing round program (:func:`core.rounds.build_round_program` /
:func:`core.rounds.build_multi_round`) over that axis: ONE compile and one
saturated device program cover the whole sweep.

Design invariants (each machine-checked):

- **Parity** — a gang member's history is byte-identical on CPU to the
  single run with that member's seed (tests/test_gang.py), because every
  member's inputs are built by the very same per-member
  ``build_round_program`` call a single run would make, and the batched
  program applies identical math per member.  The attack's compromised
  *placement* is pinned across members (attacks close over a static
  compromised set — the gaussian scatter matrix); a single run reproduces a
  member exactly by pinning ``attack.params.seed`` to the gang's base seed.
- **No new collectives** — vmapping the round program must not introduce
  communication the single-run program lacks (``murmura check --ir``
  MUR500).
- **Bucketed compiles** — the gang pads S to the next power of two and
  masks padding members out of recording, so growing S within a bucket
  reuses the compiled executable: zero recompiles (MUR501), the same trick
  the alive/adjacency value-inputs use for churn (MUR302).

When gang loses: resident memory is S x a single run's (params, optimizer
state, data all gain the seed axis) — at large models or large N, prefer
fewer members per gang over spilling HBM.  Shape-affecting knobs
(num_nodes, batch_size, model size, krum's selection count) cannot vary
inside a gang; they change the traced program and belong in separate
sweeps.
"""

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from murmura_tpu.core.network import (
    effective_adjacency,
    effective_alive,
    effective_edge_mask,
    empty_history,
    record_round_metrics,
    sanitizer_scope,
)
from murmura_tpu.core.rounds import RoundProgram


def next_bucket(size: int) -> int:
    """Smallest power of two >= size — the gang's compile-shape bucket."""
    if size < 1:
        raise ValueError(f"gang size must be >= 1, got {size}")
    b = 1
    while b < size:
        b *= 2
    return b


@dataclass(frozen=True)
class GangMember:
    """One experiment of the gang: a seed plus optional traced-scalar
    hyperparameter overrides (values the compiled program takes as inputs,
    so every member rides one jit)."""

    seed: int
    lr: Optional[float] = None
    attack_scale: Optional[float] = None

    @property
    def label(self) -> str:
        parts = [f"seed_{self.seed}"]
        if self.lr is not None:
            parts.append(f"lr_{self.lr:g}")
        if self.attack_scale is not None:
            parts.append(f"atk_{self.attack_scale:g}")
        return "-".join(parts)


def resolve_members(config, seeds: Optional[Sequence[int]] = None) -> List[GangMember]:
    """The gang's member list from ``config.sweep`` (or an explicit seed
    list — the CLI ``--seeds`` override / ``murmura run --seeds N`` sugar).

    ``noise_std`` member overrides are resolved here into the program-level
    ``attack_scale`` multiplier (scale = noise_std / the configured gaussian
    noise_std), so the round program needs only one knob.
    """
    def _distinct(members: List[GangMember]) -> List[GangMember]:
        # Member labels key the sweep output JSON and the per-member
        # telemetry run dirs — a duplicate would silently collapse one
        # member's results onto another's, so every member source (the
        # --seeds CLI list included) fails loud instead.
        labels = [m.label for m in members]
        if len(labels) != len(set(labels)):
            raise ValueError(
                f"sweep members are not distinct (labels: {labels}) — two "
                "identical members would just duplicate work"
            )
        return members

    if seeds is not None:
        return _distinct([GangMember(seed=int(s)) for s in seeds])
    sweep = config.sweep
    if sweep is None:
        raise ValueError("config has no sweep block and no explicit seeds")
    if sweep.seeds is not None:
        return _distinct([GangMember(seed=int(s)) for s in sweep.seeds])
    if sweep.num_seeds is not None:
        base = config.experiment.seed
        return [GangMember(seed=base + i) for i in range(sweep.num_seeds)]
    p = config.attack.params
    base_noise = float(p.get("noise_std", p.get("std", 10.0)))
    members = []
    for m in sweep.members:
        scale = m.attack_scale
        if m.noise_std is not None:
            if base_noise <= 0:
                raise ValueError(
                    "sweep member noise_std override needs a positive "
                    "attack.params.noise_std to scale against"
                )
            scale = m.noise_std / base_noise
        members.append(GangMember(
            seed=int(m.seed if m.seed is not None else config.experiment.seed),
            lr=m.lr,
            attack_scale=scale,
        ))
    return _distinct(members)


def gang_hp_inputs(members: Sequence[GangMember]) -> Tuple[str, ...]:
    """Which scalar hyperparameters the gang's program must take as inputs
    (``build_round_program(hp_inputs=...)``).  Seed-only gangs lift none —
    the traced program stays byte-identical to a single run's."""
    hp = []
    if any(m.lr is not None for m in members):
        hp.append("lr")
    if any(m.attack_scale is not None for m in members):
        hp.append("attack_scale")
    return tuple(hp)


def _stack_trees(trees: Sequence[Any], indices: Sequence[int]) -> Any:
    """Stack member pytrees along a new leading axis in ``indices`` order
    (the bucket-padding order: real members then replicas of member 0)."""
    picked = [trees[i] for i in indices]
    return jax.tree_util.tree_map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves]), *picked
    )


def _check_member_compatible(progs: Sequence[RoundProgram], members) -> None:
    """Fail loud when member programs are not gang-batchable.

    The gang runs member 0's traced function over everyone's inputs, so
    every static property the trace bakes in — shapes, dtypes, the batch
    schedule's max step count — must agree, or a member would silently
    train differently than its single run (a parity violation worse than
    an error)."""
    base = progs[0]
    base_shapes = {
        k: (v.shape, str(np.asarray(v).dtype))
        for k, v in base.data_arrays.items()
    }
    for i, prog in enumerate(progs[1:], start=1):
        label = members[i].label
        if prog.num_nodes != base.num_nodes or prog.model_dim != base.model_dim:
            raise ValueError(
                f"gang member {label}: num_nodes/model_dim mismatch with "
                "member 0 — members must share the network and model shape"
            )
        shapes = {
            k: (v.shape, str(np.asarray(v).dtype))
            for k, v in prog.data_arrays.items()
        }
        if shapes != base_shapes:
            diff = sorted(
                k for k in set(shapes) | set(base_shapes)
                if shapes.get(k) != base_shapes.get(k)
            )
            raise ValueError(
                f"gang member {label}: data arrays differ from member 0's "
                f"in {diff} — per-seed partitions must produce identical "
                "shapes to share one compiled program (pin "
                "training.max_samples or use an equal-shard partitioner)"
            )
        for k in ("steps", "eff_batch"):
            if int(prog.data_arrays[k].max()) != int(base.data_arrays[k].max()):
                raise ValueError(
                    f"gang member {label}: static batch schedule "
                    f"(max {k}) differs from member 0's — the traced scan "
                    "length would silently truncate this member's training; "
                    "equalize per-node sample counts across seeds"
                )


class GangNetwork:
    """Orchestrates S stacked experiments over one vmapped round program.

    The gang twin of :class:`core.network.Network`: same history schema,
    same RNG discipline (round r runs with ``fold_in(PRNGKey(member_seed),
    r)`` per member), same fused-dispatch semantics — but every device
    program carries a leading ``[B]`` experiment axis (B = the padded
    bucket) and history/telemetry fan out per member.

    Args:
        program: member 0's RoundProgram (the gang's traced function).
        member_programs: every member's RoundProgram — their init state and
            data arrays are the gang's stacked inputs.
        members: the resolved member list (seeds + hp overrides).
        topology / mobility / fault_schedule: shared across members — their
            seeds are independent of the experiment seed by construction
            (topology.seed / mobility.seed / faults.seed).
        backend: ``simulation`` (one device) or ``tpu`` (gang laid onto a
            2-D ("seed", "nodes") mesh — parallel/mesh.py).
        telemetry_writers: optional per-member TelemetryWriter list (one
            manifest per member, ``<run_dir>/<member label>``).
    """

    def __init__(
        self,
        program: RoundProgram,
        member_programs: Sequence[RoundProgram],
        members: Sequence[GangMember],
        topology,
        attack=None,
        mobility=None,
        fault_schedule=None,
        backend: str = "simulation",
        mesh=None,
        num_devices: Optional[int] = None,
        donate: bool = True,
        bucket: bool = True,
        base_lr: float = 0.01,
        recompile_guard: bool = False,
        transfer_guard: bool = False,
        telemetry_writers: Optional[Sequence] = None,
        retain_init: bool = False,
        min_batch: int = 1,
    ):
        if len(member_programs) != len(members):
            raise ValueError("one RoundProgram per member required")
        _check_member_compatible(member_programs, members)
        if program.sparse:
            from murmura_tpu.topology.sparse import SparseTopology

            # Sparse exchange mode (topology/sparse.py): the gang's adj
            # input is the member-shared [k, N] edge mask, exactly like a
            # single run's — it rides in_axes=None so nothing here is
            # mode-specific beyond the per-round mask source below.  A
            # node-SHARDED gang mesh is still rejected at the factory
            # (the [k, N] layout needs edge_mask_sharding plumbing).
            if not isinstance(topology, SparseTopology):
                raise ValueError(
                    "the gang's round program was built with "
                    "sparse_offsets but the topology is not a "
                    "SparseTopology"
                )
            if mobility is not None:
                raise ValueError(
                    "sparse exchange mode does not compose with mobility"
                )
        self.program = program
        self.members = list(members)
        self.gang_size = len(members)
        # min_batch pre-grows the compile shape (serve/daemon.py: a bucket
        # built at full capacity admits tenants value-only — the shape
        # never changes, so admission never recompiles).
        self.batch = (
            next_bucket(max(self.gang_size, min_batch))
            if bucket else self.gang_size
        )
        self.topology = topology
        self.attack = attack
        self.mobility = mobility
        self.fault_schedule = fault_schedule
        self.backend = backend
        self.recompile_guard = recompile_guard
        self.transfer_guard = transfer_guard
        self._tracker = None
        self.last_compile_report: Optional[List] = None
        self._warmed: set = set()
        self.telemetry = list(telemetry_writers or [])
        if self.telemetry and len(self.telemetry) != self.gang_size:
            raise ValueError("one telemetry writer per member required")

        n = program.num_nodes
        if topology.num_nodes != n:
            raise ValueError(
                f"Topology has {topology.num_nodes} nodes, gang stack has {n}"
            )

        # Bucket padding: replicate member 0 into the tail slots.  Padding
        # members execute (their cost is the price of the stable compile
        # shape) but are never recorded and never see a telemetry writer.
        self._indices = list(range(self.gang_size)) + [0] * (
            self.batch - self.gang_size
        )

        # Per-member compromised masks are identical by construction (the
        # attack placement is pinned across the gang — module docstring),
        # but stack them anyway: the program takes the mask as an input,
        # and a future per-member threat model only needs this array.
        if attack is not None:
            comp = attack.compromised.astype(np.float32)
        else:
            comp = np.zeros(n, dtype=np.float32)
        self.compromised = comp
        self._comp_stack = np.stack([comp for _ in self._indices])

        stack = lambda get: _stack_trees(  # noqa: E731
            [get(p) for p in member_programs], self._indices
        )
        init_params_host = stack(lambda p: p.init_params)
        init_agg_host = stack(lambda p: p.init_agg_state)
        # retain_init keeps the stacked host-side init arrays alive so
        # reset_run() can rebuild fresh device state without the member
        # programs (the frontier's stage loop — value-only resets over one
        # warm compiled program).  Off by default: normal sweeps should
        # not hold a second host copy of [B, N, P] params.
        self._init_params_host = init_params_host if retain_init else None
        self._init_agg_host = init_agg_host if retain_init else None
        self._base_lr = base_lr
        self.params = jax.tree_util.tree_map(jnp.asarray, init_params_host)
        self.agg_state = {
            k: jnp.asarray(v) for k, v in init_agg_host.items()
        }
        data = stack(lambda p: p.data_arrays)
        # Per-member hyperparameter inputs overwrite the stacked defaults.
        if "lr" in program.hp_inputs:
            data["hp_lr"] = np.asarray(
                [
                    members[i].lr if members[i].lr is not None else base_lr
                    for i in self._indices
                ],
                np.float32,
            )
        if "attack_scale" in program.hp_inputs:
            data["hp_attack_scale"] = np.asarray(
                [
                    members[i].attack_scale
                    if members[i].attack_scale is not None
                    else 1.0
                    for i in self._indices
                ],
                np.float32,
            )
        self._data = {k: jnp.asarray(v) for k, v in data.items()}
        # Per-member base keys: round r always runs with fold_in(base_s, r),
        # exactly the single-run stream for that member's seed.
        self._rng = jnp.stack(
            [jax.random.PRNGKey(members[i].seed) for i in self._indices]
        )
        self._fold_in = jax.jit(
            jax.vmap(jax.random.fold_in, in_axes=(0, None))
        )

        # --- the vmapped programs ------------------------------------------
        # The experiment axis is data-parallel by construction: members
        # share the shape family and the adjacency/alive inputs (seed-
        # independent), so adj/alive/round ride unbatched (in_axes=None) —
        # less resident memory and no per-member copies of [N, N] masks.
        if program.faulted:
            step_axes = (0, 0, 0, None, 0, None, None, 0)
        else:
            step_axes = (0, 0, 0, None, 0, None, 0)
        vstep = jax.vmap(program.train_step, in_axes=step_axes)
        veval = jax.vmap(program.eval_step, in_axes=(0, 0))

        if backend == "tpu":
            from jax.sharding import NamedSharding, PartitionSpec as P

            from murmura_tpu.parallel.mesh import (
                gang_adj_stack_sharding,
                gang_node_sharding,
                make_gang_mesh,
                make_gang_param_mesh,
                shard_gang_eval_step,
                shard_gang_step,
            )

            if mesh is None:
                if getattr(program, "param_shards", 1) > 1:
                    # The sharding x sweep lift (ISSUE 16): the gang
                    # mesh grows a "param" role so the [S, N, P] stacked
                    # state shards its trailing flat axis too.
                    mesh = make_gang_param_mesh(
                        self.batch, n, program.param_shards, num_devices
                    )
                else:
                    mesh = make_gang_mesh(self.batch, n, num_devices)
            self.mesh = mesh
            self._step = shard_gang_step(
                vstep, program, self.batch, mesh, donate=donate
            )
            self._eval = shard_gang_eval_step(veval, program, self.batch, mesh)
            self._adj_stack_s = gang_adj_stack_sharding(mesh)
            self._node_rows_s = gang_node_sharding(mesh)
            self._gang2d_s = NamedSharding(mesh, P("seed", "nodes"))
            self._member_s = NamedSharding(mesh, P("seed"))
            self._repl_s = NamedSharding(mesh, P())
        else:
            self.mesh = None
            donate_argnums = (0, 1) if donate else ()
            self._step = jax.jit(vstep, donate_argnums=donate_argnums)
            self._eval = jax.jit(veval)
            self._adj_stack_s = None
            self._node_rows_s = self._gang2d_s = None
            self._member_s = self._repl_s = None
        self._donate = donate
        self._fused_cache: Dict[Any, Any] = {}
        self._place_resident_state()
        # The compromised stack never changes across rounds: staged onto
        # its device layout once, not per dispatch.
        self._comp_dev = self._stage(self._comp_stack, self._gang2d_s)

        self.histories: List[Dict[str, List[Any]]] = [
            empty_history() for _ in range(self.gang_size)
        ]
        self._last_stats: List[Dict[str, np.ndarray]] = [
            {} for _ in range(self.gang_size)
        ]
        self.round_times: List[float] = []
        self.current_round = 0
        # Graceful degradation (durability/dispatch.py; docs/ROBUSTNESS.md):
        # a member marked dead keeps computing (its vmap lane cannot be
        # carved out of the compiled program — the same reason padding
        # members execute) but its history FREEZES at the failure round
        # and its telemetry surfaces the degradation, while survivors
        # continue unperturbed.  The alive-mask trick, one level up.
        self.member_active: List[bool] = [True] * self.gang_size

    # ------------------------------------------------------------------

    def _place_resident_state(self) -> None:
        """Pre-place the stacked state on the gang mesh (tpu backend,
        single host) — the gang twin of Network._place_resident_state."""
        if self.mesh is None or jax.process_count() > 1:
            return
        from murmura_tpu.parallel.mesh import (
            _shard_gang_leading,
            mesh_param_shards,
        )

        flat_dim = None
        if mesh_param_shards(self.mesh) > 1:
            flat_dim = getattr(
                self.program, "flat_dim", self.program.model_dim
            )
        place = lambda tree: jax.device_put(  # noqa: E731
            tree, _shard_gang_leading(tree, self.mesh, flat_dim)
        )
        self.params = place(self.params)
        self.agg_state = place(self.agg_state)
        self._data = place(self._data)

    def _stage(self, value, sharding=None):
        if sharding is None or self.mesh is None or jax.process_count() > 1:
            return jnp.asarray(value)
        return jax.device_put(value, sharding)

    def _adjacency_for_round(self, round_idx: int) -> np.ndarray:
        """Member-shared per-round adjacency (the Network helper — the
        topology/mobility/fault seeds are member-independent).  Sparse
        programs take the [k, N] edge mask where dense ones take the
        [N, N] matrix, exactly like a single run's dispatch loop."""
        if self.program.sparse:
            return effective_edge_mask(
                self.topology, self.fault_schedule, round_idx
            )
        return effective_adjacency(
            self.topology, self.mobility, self.fault_schedule, round_idx
        )

    def _alive_for_round(self, round_idx: int) -> np.ndarray:
        return effective_alive(
            self.fault_schedule, self.program.num_nodes, round_idx
        )

    def _sanitizer_scope(self):
        """The shared :func:`core.network.sanitizer_scope` (recompile /
        transfer guards) over this orchestrator."""
        return sanitizer_scope(self)

    # ------------------------------------------------------------------

    def train(
        self,
        rounds: int,
        verbose: bool = False,
        eval_every: int = 1,
        rounds_per_dispatch: int = 1,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
    ) -> List[Dict[str, List[Any]]]:
        """Run the gang for ``rounds`` FL rounds; returns per-member
        histories (``self.histories``).

        ``checkpoint_dir``/``checkpoint_every`` snapshot the FULL stacked
        gang state — every member's params/agg_state/rng lane plus every
        per-member history — through the same durable path single runs
        use (durability/snapshot.py), so an interrupted sweep resumes all
        S members byte-identically (`murmura sweep --resume`).
        """
        from murmura_tpu.analysis.sanitizers import CompileTracker

        # Independent of the recompile guard: a passive process-wide
        # baseline so every member's manifest carries the compiles this
        # train() call paid (the metrics fold's `counter="compiles"`).
        compile_probe = CompileTracker()
        try:
            with self._sanitizer_scope():
                if rounds_per_dispatch > 1:
                    self._train_fused(
                        rounds, verbose, eval_every, rounds_per_dispatch,
                        checkpoint_dir, checkpoint_every,
                    )
                else:
                    self._train_rounds(
                        rounds, verbose, eval_every, checkpoint_dir,
                        checkpoint_every,
                    )
        finally:
            compiled = compile_probe.total
            for s, t in enumerate(self.telemetry):
                if t is not None:
                    if compiled:
                        t.add_counters({"compiles": compiled})
                    t.finalize(history=self.histories[s])
        return self.histories

    def _step_args(self, keys, adj, round_value, alive=None):
        args = [
            self.params,
            self.agg_state,
            keys,
            self._stage(adj, self._node_rows_s),
            self._comp_dev,
            self._stage(np.asarray(round_value, np.float32), self._repl_s),
            self._data,
        ]
        if self.program.faulted:
            args.insert(5, self._stage(alive, self._node_rows_s))
        return args

    def _train_rounds(
        self, rounds, verbose, eval_every, checkpoint_dir=None,
        checkpoint_every=0,
    ) -> None:
        last_saved = -1
        for _ in range(rounds):
            round_idx = self.current_round
            t0 = time.perf_counter()
            warmup = "step" not in self._warmed
            if self._tracker is not None:
                self._tracker.begin(f"gang round {round_idx}")
            adj = self._adjacency_for_round(round_idx)
            keys = self._stage(
                self._fold_in(
                    self._rng, jnp.asarray(np.asarray(round_idx, np.uint32))
                ),
                self._member_s,
            )
            args = self._step_args(
                keys, adj, round_idx,
                alive=self._alive_for_round(round_idx)
                if self.program.faulted else None,
            )
            self.params, self.agg_state, agg_metrics = self._step(*args)
            self._warmed.add("step")
            self.current_round = round_idx + 1
            if self.current_round % eval_every == 0:
                if self._tracker is not None:
                    self._tracker.mark(allow=warmup)
                warmup = "eval" not in self._warmed
                metrics = {**self._eval(self.params, self._data), **agg_metrics}
                self._warmed.add("eval")
                self._record_all(self.current_round, jax.device_get(metrics), verbose)
            if self._tracker is not None:
                self._tracker.end(allow=warmup)
            wall = time.perf_counter() - t0
            self.round_times.append(wall)
            self._emit_phase_times(round_idx, "gang_per_round", wall)
            if (
                checkpoint_dir
                and checkpoint_every
                and self.current_round % checkpoint_every == 0
            ):
                self.save_checkpoint(checkpoint_dir)
                last_saved = self.current_round
        if checkpoint_dir and rounds > 0 and self.current_round != last_saved:
            self.save_checkpoint(checkpoint_dir)

    def _fused_step(self, chunk: int, eval_every: int):
        key = (chunk, eval_every)
        if key not in self._fused_cache:
            from murmura_tpu.core.rounds import build_multi_round

            fn = build_multi_round(self.program, chunk, eval_every)
            if self.program.faulted:
                axes = (0, 0, 0, None, 0, None, None, 0)
            else:
                axes = (0, 0, 0, None, 0, None, 0)
            vfn = jax.vmap(fn, in_axes=axes)
            if self.mesh is not None:
                from murmura_tpu.parallel.mesh import shard_gang_multi_round

                self._fused_cache[key] = shard_gang_multi_round(
                    vfn, self.program, self.batch, self.mesh,
                    donate=self._donate,
                )
            else:
                donate_argnums = (0, 1) if self._donate else ()
                self._fused_cache[key] = jax.jit(
                    vfn, donate_argnums=donate_argnums
                )
        return self._fused_cache[key]

    def _train_fused(
        self, rounds, verbose, eval_every, chunk, checkpoint_dir=None,
        checkpoint_every=0,
    ) -> None:
        done = 0
        while done < rounds:
            k = min(chunk, rounds - done)
            step = self._fused_step(k, eval_every)
            round0 = self.current_round
            t0 = time.perf_counter()
            program_key = ("fused", k, eval_every)
            if self._tracker is not None:
                self._tracker.begin(f"gang rounds {round0}..{round0 + k - 1}")
            adj_stack = self._stage(
                np.stack(
                    [self._adjacency_for_round(round0 + i) for i in range(k)]
                ),
                self._adj_stack_s,
            )
            args = [
                self.params,
                self.agg_state,
                self._stage(self._rng, self._member_s),
                adj_stack,
                self._comp_dev,
                self._stage(np.asarray(round0, np.int32), self._repl_s),
                self._data,
            ]
            if self.program.faulted:
                args.insert(
                    5,
                    self._stage(
                        np.stack(
                            [self._alive_for_round(round0 + i) for i in range(k)]
                        ),
                        self._adj_stack_s,
                    ),
                )
            self.params, self.agg_state, rows = step(*args)
            rows = jax.device_get(rows)
            chunk_warmup = program_key not in self._warmed
            self._warmed.add(program_key)
            self.current_round = round0 + k
            elapsed = time.perf_counter() - t0
            self.round_times.extend([elapsed / k] * k)
            done += k
            for i in range(k):
                self._emit_phase_times(
                    round0 + i, "gang_fused", elapsed / k, chunk=k
                )
                # rows leaves are [B, chunk, ...]; "evaluated" is the same
                # unbatched cadence flag broadcast over the gang axis.
                if np.asarray(rows["evaluated"])[0, i]:
                    self._record_all(
                        round0 + i + 1,
                        {
                            m: v[:, i]
                            for m, v in rows.items()
                            if m != "evaluated"
                        },
                        verbose,
                    )
            if self._tracker is not None:
                self._tracker.end(allow=chunk_warmup)
            crossed_cadence = checkpoint_every and (
                self.current_round // checkpoint_every
                > round0 // checkpoint_every
            )
            if checkpoint_dir and (crossed_cadence or done >= rounds):
                self.save_checkpoint(checkpoint_dir)

    # ------------------------------------------------------------------
    # durability (durability/snapshot.py): the gang snapshots through the
    # same fsync'd path single runs use; every section carries the full
    # padded [B, ...] stack so a restore is value-only into the warm
    # compiled program (padding lanes replicate member 0's trajectory
    # exactly, so saving them costs bytes but buys bit-exactness).

    def save_checkpoint(self, directory: str) -> None:
        from murmura_tpu.durability.snapshot import save_run_snapshot

        t0 = time.perf_counter()
        save_run_snapshot(directory, self)
        for t in self.telemetry:
            if t is not None:
                t.checkpoint_event(
                    self.current_round, time.perf_counter() - t0,
                    action="save", path=str(directory),
                )

    def restore_checkpoint(self, directory: str) -> int:
        """Restore the full gang; returns the round to continue from."""
        from murmura_tpu.durability.snapshot import restore_run_snapshot

        t0 = time.perf_counter()
        round_num = restore_run_snapshot(directory, self)
        for t in self.telemetry:
            if t is not None:
                t.checkpoint_event(
                    round_num, time.perf_counter() - t0,
                    action="restore", path=str(directory),
                )
                t.emit(
                    "run_resumed", round=round_num, path=str(directory),
                    run_id=t.run_id,
                )
        return round_num

    def _durability_history(self):
        return {
            "gang_members": self.histories,
            "labels": [m.label for m in self.members],
        }

    def _durability_set_history(self, history) -> None:
        if not isinstance(history, dict) or "gang_members" not in history:
            raise ValueError(
                "snapshot carries no gang history — it was written by a "
                "single run; resume it with `murmura run --resume` instead"
            )
        labels = history.get("labels")
        ours = [m.label for m in self.members]
        if labels != ours:
            raise ValueError(
                f"gang snapshot members {labels} != this gang's {ours} — "
                "resuming into a different member set would misattribute "
                "every lane; rebuild with the sweep that wrote the snapshot"
            )
        self.histories = history["gang_members"]

    def _durability_extra_state(self):
        meta: Dict[str, Any] = {
            "gang": {
                "batch": self.batch,
                "gang_size": self.gang_size,
                "member_active": list(self.member_active),
                # Duplicated from the history payload so the member-set
                # identity check can run PRE-mutation (validate hook).
                "labels": [m.label for m in self.members],
            }
        }
        run_ids = [
            t.run_id if t is not None else None for t in self.telemetry
        ]
        if any(r is not None for r in run_ids):
            meta["telemetry_run_ids"] = run_ids
        return {}, meta

    def _durability_validate_extra(self, arrays, meta) -> None:
        gm = meta.get("gang")
        if gm is None:
            raise ValueError(
                "snapshot carries no gang section — it was written by a "
                "single run; resume it with `murmura run --resume` instead"
            )
        if int(gm["batch"]) != self.batch:
            raise ValueError(
                f"gang snapshot batch {gm['batch']} != this gang's "
                f"{self.batch} — the stacked state shapes cannot match"
            )
        labels = gm.get("labels")
        ours = [m.label for m in self.members]
        if labels is not None and labels != ours:
            # Same member count/batch but a different seed list has
            # identical stacked shapes — the shape guard cannot catch it,
            # and this must refuse BEFORE any lane is overwritten.
            raise ValueError(
                f"gang snapshot members {labels} != this gang's {ours} — "
                "resuming into a different member set would misattribute "
                "every lane; rebuild with the sweep that wrote the snapshot"
            )

    def _durability_restore_extra(self, arrays, meta) -> None:
        gm = meta["gang"]
        active = gm.get("member_active")
        if active is not None and len(active) == self.gang_size:
            self.member_active = [bool(a) for a in active]

    def reset_run(
        self,
        members: Sequence[GangMember],
        member_programs: Optional[Sequence[RoundProgram]] = None,
        telemetry_writers: Optional[Sequence] = None,
    ) -> None:
        """Value-only reset for a fresh run over the SAME warm compiled
        programs — zero recompiles on the next train().

        Two modes:

        - **Stage reset** (``member_programs=None`` — the `murmura
          frontier` stage loop): params/agg_state/RNG/histories return
          to round 0 from the retained host init arrays.  Constraints,
          each fail-loud: the gang must have been built with
          ``retain_init=True``, the new member list must be
          slot-for-slot the same seeds (data shards and init params
          were built per ORIGINAL seed), and only traced-input
          overrides (lr / attack_scale) may differ.
        - **Re-tenanting** (``member_programs`` given — the `murmura
          serve` admission path, docs/ROBUSTNESS.md "Serving"): each
          lane is spliced host-side with a NEW member's init params /
          agg state / data shards / RNG base from its own
          ``build_round_program`` output.  New seeds are allowed
          (the programs carry the per-seed values); the member count
          may be anything in ``1..batch`` (padding lanes replicate
          member 0, exactly like construction); duplicate labels are
          allowed (serve tenants are identified by submission id, not
          label).  The compiled executables are untouched — the new
          programs contribute VALUES only and are never traced, so
          every admitted tenant still runs member 0's traced math,
          which ``_check_member_compatible`` requires to be
          gang-batchable with the template's.
        """
        if member_programs is not None:
            self._admit_members(members, member_programs, telemetry_writers)
            return
        if telemetry_writers is not None:
            raise ValueError(
                "reset_run(telemetry_writers=...) is only meaningful on "
                "the re-tenanting path (member_programs given) — a stage "
                "reset keeps the gang's writers"
            )
        if self._init_params_host is None:
            raise ValueError(
                "reset_run() needs the gang built with retain_init=True "
                "(the stacked host init arrays are the reset source)"
            )
        members = list(members)
        if len(members) != self.gang_size:
            raise ValueError(
                f"reset_run got {len(members)} members for a gang of "
                f"{self.gang_size} — the bucket shape must not change "
                "(that is the whole point of the reset)"
            )
        for i, (old, new) in enumerate(zip(self.members, members)):
            if new.seed != old.seed:
                raise ValueError(
                    f"reset_run member {i} changes seed {old.seed} -> "
                    f"{new.seed} — data shards and init params were "
                    "built per original seed; only lr/attack_scale may "
                    "vary across stages"
                )
        labels = [m.label for m in members]
        if len(labels) != len(set(labels)):
            raise ValueError(
                f"reset_run members are not distinct (labels: {labels})"
            )
        self.members = members
        if "lr" in self.program.hp_inputs:
            self._data["hp_lr"] = jnp.asarray(np.asarray(
                [
                    members[i].lr if members[i].lr is not None
                    else self._base_lr
                    for i in self._indices
                ],
                np.float32,
            ))
        if "attack_scale" in self.program.hp_inputs:
            self._data["hp_attack_scale"] = jnp.asarray(np.asarray(
                [
                    members[i].attack_scale
                    if members[i].attack_scale is not None
                    else 1.0
                    for i in self._indices
                ],
                np.float32,
            ))
        self.params = jax.tree_util.tree_map(
            jnp.asarray, self._init_params_host
        )
        self.agg_state = {
            k: jnp.asarray(v) for k, v in self._init_agg_host.items()
        }
        self._rng = jnp.stack(
            [jax.random.PRNGKey(members[i].seed) for i in self._indices]
        )
        self._place_resident_state()
        self.histories = [empty_history() for _ in range(self.gang_size)]
        self._last_stats = [{} for _ in range(self.gang_size)]
        self.round_times = []
        self.current_round = 0
        self.member_active = [True] * self.gang_size

    def _admit_members(
        self,
        members: Sequence[GangMember],
        member_programs: Sequence[RoundProgram],
        telemetry_writers: Optional[Sequence],
    ) -> None:
        """The re-tenanting half of :meth:`reset_run` (serve/daemon.py):
        splice a new generation of tenants into the warm bucket's lanes
        — values only, the compiled [B, ...] executables never change
        shape (B = self.batch is fixed at construction; min_batch
        pre-grows it to the bucket's capacity)."""
        members = list(members)
        progs = list(member_programs)
        if len(progs) != len(members):
            raise ValueError("one RoundProgram per admitted member required")
        if not 1 <= len(members) <= self.batch:
            raise ValueError(
                f"cannot admit {len(members)} members into a bucket of "
                f"batch {self.batch} — the compiled shape is fixed; a "
                "larger tenant set needs a bigger bucket (a new compile)"
            )
        # The admitted programs are value sources for member 0's traced
        # math — the same batchability contract construction enforces.
        # The slot-0 member in the probe list is unused by the checker.
        _check_member_compatible(
            [self.program, *progs], [self.members[0], *members]
        )
        self.members = members
        self.gang_size = len(members)
        self._indices = list(range(self.gang_size)) + [0] * (
            self.batch - self.gang_size
        )
        stack = lambda get: _stack_trees(  # noqa: E731
            [get(p) for p in progs], self._indices
        )
        init_params_host = stack(lambda p: p.init_params)
        init_agg_host = stack(lambda p: p.init_agg_state)
        if self._init_params_host is not None:
            # Keep the stage-reset source coherent with the new tenants
            # (a frontier-style reset after an admission must reset to
            # the ADMITTED generation's init, not a stale one's).
            self._init_params_host = init_params_host
            self._init_agg_host = init_agg_host
        self.params = jax.tree_util.tree_map(jnp.asarray, init_params_host)
        self.agg_state = {
            k: jnp.asarray(v) for k, v in init_agg_host.items()
        }
        data = stack(lambda p: p.data_arrays)
        if "lr" in self.program.hp_inputs:
            data["hp_lr"] = np.asarray(
                [
                    members[i].lr if members[i].lr is not None
                    else self._base_lr
                    for i in self._indices
                ],
                np.float32,
            )
        if "attack_scale" in self.program.hp_inputs:
            data["hp_attack_scale"] = np.asarray(
                [
                    members[i].attack_scale
                    if members[i].attack_scale is not None
                    else 1.0
                    for i in self._indices
                ],
                np.float32,
            )
        self._data = {k: jnp.asarray(v) for k, v in data.items()}
        self._rng = jnp.stack(
            [jax.random.PRNGKey(members[i].seed) for i in self._indices]
        )
        self._place_resident_state()
        if telemetry_writers is not None:
            self.telemetry = list(telemetry_writers)
        if self.telemetry and len(self.telemetry) != self.gang_size:
            raise ValueError(
                f"{len(self.telemetry)} telemetry writers for "
                f"{self.gang_size} admitted members — pass one writer per "
                "member (or an empty list) when re-tenanting"
            )
        self.histories = [empty_history() for _ in range(self.gang_size)]
        self._last_stats = [{} for _ in range(self.gang_size)]
        self.round_times = []
        self.current_round = 0
        self.member_active = [True] * self.gang_size

    def freeze_member(self, member: int, reason: str) -> None:
        """Gracefully degrade one member's lane: recording stops (its
        history freezes at the current round), survivors continue, and
        the degradation is surfaced as a ``backend_degraded`` telemetry
        event.  The lane's compute continues — a vmap lane cannot be
        carved out of the compiled program, exactly like the padding
        members — so freezing never perturbs the surviving members'
        numbers.  Idempotent."""
        if not 0 <= member < self.gang_size:
            raise ValueError(
                f"member {member} out of range for gang of {self.gang_size}"
            )
        if not self.member_active[member]:
            return
        self.member_active[member] = False
        t = self.telemetry[member] if self.telemetry else None
        if t is not None:
            t.emit(
                "backend_degraded",
                member=self.members[member].label,
                reason=reason,
                round=self.current_round,
            )

    # ------------------------------------------------------------------

    def _emit_phase_times(self, round_idx, mode, wall_s, **extra) -> None:
        if self.program.pipelined:
            # The pipelined critical-path marker, mirrored from
            # Network._phase_overlap so gang members' reports render the
            # same critical-path decomposition as single runs.
            extra.setdefault("overlap", "pipelined")
        for t in self.telemetry:
            if t is not None:
                t.phase_times(
                    round_idx, mode, wall_s, gang=self.gang_size, **extra
                )

    def _record_all(self, round_num: int, metrics, verbose: bool) -> None:
        """Fan one evaluated round's [B, ...] metrics out to the per-member
        histories (padding members are dropped).  Uses the same
        record_round_metrics the single-run orchestrator uses, so a member
        row is byte-identical to its single run's."""
        in_deg = None
        if any(t is not None for t in self.telemetry):
            # The effective adjacency is member-shared — compute its
            # in-degree once per recorded round, not once per member.
            mask = np.asarray(self._adjacency_for_round(round_num - 1))
            if self.program.sparse:
                in_deg = self.topology.in_degree_from_edge_mask(mask)
            else:
                in_deg = mask.sum(axis=0)
        for s in range(self.gang_size):
            if not self.member_active[s]:
                # Frozen lane (freeze_member): the member's history stays
                # at its failure round; its compute still ran (vmap lane),
                # like a padding member's.
                continue
            member_metrics = {
                k: np.asarray(v)[s] for k, v in metrics.items()
            }
            self._last_stats[s] = record_round_metrics(
                self.histories[s], round_num, member_metrics,
                self.compromised, self.program.evidential,
                self.attack is not None,
            )
            t = self.telemetry[s] if self.telemetry else None
            if t is not None:
                t.round_event(
                    round_num, member_metrics, in_degree=in_deg,
                )
        if verbose:
            accs = np.asarray(metrics["accuracy"])[: self.gang_size]
            line = ", ".join(
                f"{self.members[s].label}={accs[s].mean():.4f}"
                for s in range(self.gang_size)
            )
            print(f"Round {round_num}: {line}", flush=True)

    def get_node_statistics(self, member: int = 0) -> Dict[int, Dict[str, Any]]:
        """Per-node aggregator statistics of one gang member."""
        n = self.program.num_nodes
        return {
            i: {k: float(v[i]) for k, v in self._last_stats[member].items()}
            for i in range(n)
        }


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="sweep",
    module="murmura_tpu.core.gang",
    mesh_axes=("seed",),
    verdicts={
        "adaptive": composes(),
        "compression": composes(),
        "dmtt": composes(),
        "faults": composes(),
        "mobility": composes(),
        "pipeline": composes(),
        "population": refuses(
            "population does not compose with sweep (gang batching) "
            "yet — run cohort-streaming experiments unganged"
        ),
        # Lifted (ISSUE 16): the gang mesh grew a "param" role —
        # make_gang_param_mesh lays ("seed", "nodes", "param") and the
        # [S, N, P] stacked state shards on its trailing axis.
        "sharding": composes(),
        "sparse": composes(
            tpu_backend=(
                "sparse topologies (exponential/one_peer) are not "
                "gang-batchable on backend: tpu yet (the gang mesh "
                "lacks the [k, N] edge-mask sharding layout) — use "
                "backend: simulation for sparse gangs, or run sparse "
                "tpu experiments unganged"
            ),
        ),
        "staleness": composes(),
    },
)
