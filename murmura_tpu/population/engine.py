"""Cohort-streaming orchestrator: millions of virtual users through one
fixed-size compiled round program.

:class:`PopulationNetwork` extends the standard orchestrator
(core/network.py) with the sampled-activation loop (docs/SCALING.md):

- the compiled round program is EXACTLY the plain N-node program — cohort
  membership arrives as input *values* (param rows, data rows), never as
  structure, so one compile covers the whole population (the fault-mask
  mechanism, MUR302; tests/test_population.py pins zero recompiles
  across cohort swaps);
- per-user model rows persist in a host-side :class:`PopulationBank`
  (memory-mapped, lazily initialized);
- cohort draws are a pure function of ``(population.seed, draw_index)``
  (population/sampler.py) — restartable and process-agreeing;
- double-buffered staging: while round ``r`` executes on device
  (dispatch is async), the host gathers round ``r+1``'s cohort rows from
  the bank and issues their H2D transfer, so the swap cost hides behind
  compute.  The only forced sync is the write-back ``device_get`` of the
  outgoing cohort at the swap boundary.

The bank stores rows as float32 regardless of the resident param dtype:
bf16 -> f32 -> bf16 round-trips are exact, numpy memmaps want a native
dtype, and the bank's disk pages are host-side where the bf16 HBM argument
does not apply.
"""

import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from murmura_tpu.core.network import Network
from murmura_tpu.ops.flatten import make_flatteners
from murmura_tpu.population.bank import PopulationBank
from murmura_tpu.population.sampler import draw_cohort


@dataclass(frozen=True)
class PopulationSpec:
    """Validated population settings (config/schema.py PopulationConfig)."""

    virtual_size: int
    sampler: str = "uniform"
    seed: int = 1234
    rounds_per_cohort: int = 1
    data_binding: str = "user"
    bank_dir: Optional[str] = None
    # First-activation model: "teleport" (a fresh user adopts the OUTGOING
    # cohort's trained slot model — arXiv:2501.15259's mechanism, the
    # reason a 1M-population run with near-zero re-activation still
    # accumulates learning) or "slot_init" (isolated per-user models from
    # the slot's seed init).
    inherit: str = "teleport"


class PopulationNetwork(Network):
    """Network whose node axis hosts a round-sampled cohort of a larger
    virtual population."""

    def __init__(self, *args, population: PopulationSpec, **kwargs):
        super().__init__(*args, **kwargs)
        self.population = population
        n = self.program.num_nodes
        if population.virtual_size < n:
            raise ValueError(
                f"virtual_size={population.virtual_size} < cohort size {n}"
            )

        template = jax.tree_util.tree_map(
            lambda l: l[0], self.program.init_params
        )
        ravel, unravel, self._flat_dim = make_flatteners(template)
        # Warmed here (one tiny compile each) so the per-round recompile
        # guard never attributes a swap-time compile to a training round.
        self._ravel_all = jax.jit(jax.vmap(ravel))
        self._unravel_all = jax.jit(jax.vmap(unravel))
        slot_flat = jax.device_get(self._ravel_all(self.program.init_params))
        self._flat_dtype = slot_flat.dtype
        # Per-slot seed-init rows: a user's first activation starts from
        # the init of the slot it lands in (bank.py module docstring).
        self._slot_init = np.asarray(slot_flat, dtype=np.float32)
        jax.block_until_ready(
            self._unravel_all(jnp.asarray(self._slot_init, self._flat_dtype))
        )

        self.bank = PopulationBank(
            population.virtual_size, self._flat_dim,
            dtype=np.float32, directory=population.bank_dir,
        )
        # Set the first time THIS instance flushes the bank into a
        # snapshot — the in-place-restore credential the validate hook
        # checks (a fresh process must instead reattach the flushed file).
        self._bank_flushed_here = False
        # Teleport composition (docs/SCALING.md): banked users resume
        # their own row, fresh users adopt the outgoing cohort's trained
        # slot row — composed ON DEVICE so the prefetched H2D copies stay
        # overlapped and no extra device_get is forced.  Warmed here so
        # the recompile guard never sees its compile inside a round.
        self._compose = jax.jit(
            lambda known, rows, current: jnp.where(known, rows, current)
        )
        jax.block_until_ready(
            self._compose(
                jnp.zeros((n, 1), bool),
                jnp.asarray(self._slot_init, self._flat_dtype),
                jnp.asarray(self._slot_init, self._flat_dtype),
            )
        )
        # Pristine host copy of the [N, ...] data arrays for user-bound
        # re-staging at swaps (rank-0 hp_* scalars and any non-node-leading
        # array are never rebound).
        self._host_data = {
            k: np.asarray(v) for k, v in self.program.data_arrays.items()
        }
        self.cohort: Optional[np.ndarray] = None
        self.cohorts_seen = 0
        self._prefetched = None  # (draw_idx, cohort, host_rows, dev_rows)

    # ------------------------------------------------------------------

    def _draw(self, draw_idx: int) -> np.ndarray:
        return draw_cohort(
            self.population.sampler,
            self.population.virtual_size,
            self.program.num_nodes,
            draw_idx,
            self.population.seed,
        )

    def _stage_cohort_rows(self, cohort: np.ndarray):
        """(dev_rows, dev_known) for a cohort: banked rows (slot seed-init
        placeholders where unbanked) plus the banked mask, both staged to
        device."""
        host_rows = self.bank.gather(cohort, self._slot_init)
        dev_rows = jax.device_put(
            jnp.asarray(host_rows).astype(self._flat_dtype)
        )
        dev_known = jax.device_put(
            jnp.asarray(self.bank.has_rows(cohort)[:, None])
        )
        return dev_rows, dev_known

    def _prefetch(self, draw_idx: int) -> None:
        """Stage the next cohort's rows while the current round computes:
        the bank gather is host work and ``device_put`` is an async H2D
        copy, both overlapping the in-flight device dispatch."""
        cohort = self._draw(draw_idx)
        self._prefetched = (draw_idx, cohort, *self._stage_cohort_rows(cohort))

    def _rebind_data(self, cohort: np.ndarray) -> None:
        """data_binding: user — each cohort member trains on the shard of
        its user id (``user mod N``), re-staged host-side at the swap."""
        n = self.program.num_nodes
        shard = cohort % n
        for key, arr in self._host_data.items():
            if arr.ndim >= 1 and arr.shape[0] == n:
                self._data[key] = self._stage(arr[shard], self._node_s)

    def _swap_to(self, draw_idx: int, round_idx: int) -> None:
        t0 = time.perf_counter()
        if self._prefetched is not None and self._prefetched[0] == draw_idx:
            _, cohort, dev_rows, dev_known = self._prefetched
        else:
            cohort = self._draw(draw_idx)
            dev_rows, dev_known = self._stage_cohort_rows(cohort)
        self._prefetched = None

        # The outgoing cohort's trained rows, device-resident (no sync).
        out_dev = self._ravel_all(self.params)
        swapped_out = 0
        if self.cohort is not None:
            # Write-back: the one forced device sync of the swap.
            self.bank.scatter(
                self.cohort,
                np.asarray(jax.device_get(out_dev), dtype=np.float32),
            )
            swapped_out = len(self.cohort)
            # Freshness patch: the prefetch staged the incoming rows
            # BEFORE this write-back (that is the point of the overlap),
            # so a user present in BOTH cohorts was staged one swap stale
            # (or as never-banked on their very first re-draw).  Re-stage
            # from the now-current bank when the cohorts overlap — rare at
            # large virtual_size (the prefetch stays fully effective),
            # mandatory for correctness at small ones.
            if np.intersect1d(self.cohort, cohort).size:
                dev_rows, dev_known = self._stage_cohort_rows(cohort)

        if self.population.inherit == "teleport":
            # Banked users resume their own row; fresh users adopt the
            # outgoing cohort's trained slot model (model teleportation,
            # arXiv:2501.15259) — before the first swap ``out_dev`` IS the
            # slot seed init, so the composition is uniform.
            new_flat = self._compose(dev_known, dev_rows, out_dev)
        else:
            new_flat = dev_rows
        self.params = self._unravel_all(new_flat)
        self._place_resident_state()
        if self.population.data_binding == "user":
            self._rebind_data(cohort)
        self.cohort = cohort
        self.cohorts_seen += 1
        if self.telemetry is not None:
            self.telemetry.emit(
                "cohort",
                round=round_idx,
                draw=draw_idx,
                swapped_out=swapped_out,
                activated_users=self.bank.activated,
                virtual_size=self.population.virtual_size,
                swap_s=round(time.perf_counter() - t0, 6),
            )

    # ------------------------------------------------------------------

    def train(
        self,
        rounds: int,
        verbose: bool = False,
        eval_every: int = 1,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        defer_metrics: bool = False,
        rounds_per_dispatch: int = 1,
    ):
        """Cohort-streaming round loop (per-round dispatch).

        ``checkpoint_dir``/``checkpoint_every`` snapshot the COMPLETE
        streaming state (durability/snapshot.py): the base sections plus
        the resident cohort's slot↔user binding, the sampler position
        (derivable from the round — draws are pure in ``(seed,
        draw_idx)``), and the state bank (memmap flushed in place when
        ``population.bank_dir`` is set, activated rows embedded in the
        snapshot otherwise) — a resumed 100k-virtual-user run continues
        across cohort swaps with zero extra recompiles.
        ``rounds_per_dispatch > 1`` falls back to per-round dispatch with
        a warning — a fused scan would pin one cohort for the whole chunk.
        """
        if rounds_per_dispatch > 1 or defer_metrics:
            import warnings

            warnings.warn(
                "population streaming dispatches per round (the cohort "
                "swap is a host decision between dispatches); "
                "rounds_per_dispatch/defer_metrics are ignored",
                stacklevel=2,
            )
        profile = self.profile_dir is not None
        if profile:
            jax.profiler.start_trace(self.profile_dir)
        try:
            with self._sanitizer_scope():
                self._train_population(
                    rounds, verbose, eval_every, checkpoint_dir,
                    checkpoint_every,
                )
        finally:
            if profile:
                jax.profiler.stop_trace()
            self._profile_window_stop(self.current_round, force=True)
            if self.telemetry is not None:
                self.telemetry.finalize(history=self.history)
        return self.history

    def _train_population(
        self, rounds, verbose, eval_every, checkpoint_dir=None,
        checkpoint_every=0,
    ) -> None:
        comp = self._stage(self.compromised, self._node_s)
        rpc = self.population.rounds_per_cohort
        last_saved = -1
        for step_i in range(rounds):
            round_idx = self.current_round
            if round_idx % rpc == 0 or self.cohort is None:
                self._swap_to(round_idx // rpc, round_idx)
            self._profile_window_start(round_idx)
            t0 = time.perf_counter()
            warmup = "step" not in self._warmed
            if self._tracker is not None:
                self._tracker.begin(f"round {round_idx}")
            adj = self._stage(self._adjacency_for_round(round_idx), self._adj_s)
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
            self.params, self.agg_state, agg_metrics = self._step(*step_args)
            self._warmed.add("step")
            self.current_round = round_idx + 1
            # Double buffer: the step above is dispatched (async); stage
            # the NEXT cohort now so its bank gather + H2D copy overlap
            # the in-flight round instead of serializing at the boundary.
            next_round = self.current_round
            if step_i + 1 < rounds and next_round % rpc == 0:
                self._prefetch(next_round // rpc)
            if self.current_round % eval_every == 0:
                if self._tracker is not None:
                    self._tracker.mark(allow=warmup)
                warmup = "eval" not in self._warmed
                metrics = {**self._eval(self.params, self._data), **agg_metrics}
                self._warmed.add("eval")
                metrics = jax.device_get(metrics)
                self._record(self.current_round, metrics, verbose)
            if self._tracker is not None:
                self._tracker.end(allow=warmup)
            wall = time.perf_counter() - t0
            self.round_times.append(wall)
            if self.telemetry is not None:
                self.telemetry.phase_times(
                    round_idx, "population", wall,
                    evaluated=bool(self.current_round % eval_every == 0),
                    cohort_draw=round_idx // rpc,
                )
                self.telemetry.memory_event(round_idx)
                self._profile_window_stop(self.current_round)
            if (
                checkpoint_dir
                and checkpoint_every
                and self.current_round % checkpoint_every == 0
            ):
                # Crash-equivalent cadence snapshot: the bank is saved
                # AS-IS (no write-back of the resident cohort — those
                # rows ride the params section), so the restored bank is
                # byte-identical to the uninterrupted run's at this round.
                self.save_checkpoint(checkpoint_dir)
                last_saved = self.current_round
        # Final write-back so the bank holds every trained row when
        # train() returns (the resident cohort stays loaded for a
        # subsequent train() call).
        if self.cohort is not None and rounds > 0:
            out_flat = jax.device_get(self._ravel_all(self.params))
            self.bank.scatter(
                self.cohort, np.asarray(out_flat, dtype=np.float32)
            )
        if checkpoint_dir and rounds > 0 and self.current_round != last_saved:
            self.save_checkpoint(checkpoint_dir)

    # ------------------------------------------------------------------
    # durability hooks (durability/snapshot.py)

    def _durability_extra_state(self):
        """The streaming state beyond the base sections: the resident
        cohort's slot↔user binding, the swap counter, and the bank.

        Bank modes: ``external`` (``population.bank_dir`` set — the
        memmap is flushed in place and only the packed activation mask
        rides the snapshot; O(U/8) bytes) or ``embedded`` (RAM/tempdir
        banks whose backing dies with the process — activated ids + rows
        are copied into the snapshot).  The sampler needs NO saved state:
        draws are a pure function of ``(population.seed, draw_idx)`` and
        ``draw_idx`` is ``round // rounds_per_cohort`` (sampler.py).
        """
        from murmura_tpu.durability.snapshot import embed_bool_mask

        arrays, meta = super()._durability_extra_state()
        p = self.population
        external = p.bank_dir is not None
        if external:
            self.bank.flush()
            self._bank_flushed_here = True
        else:
            ids = self.bank.activated_users
            arrays["population/bank_user_ids"] = ids
            arrays["population/bank_rows"] = self.bank.rows_of(ids)
        arrays["population/bank_has_row"] = embed_bool_mask(
            self.bank._has_row
        )
        if self.cohort is not None:
            arrays["population/cohort"] = np.asarray(self.cohort, np.int64)
        meta["population"] = {
            "virtual_size": p.virtual_size,
            "sampler": p.sampler,
            "seed": p.seed,
            "rounds_per_cohort": p.rounds_per_cohort,
            "data_binding": p.data_binding,
            "inherit": p.inherit,
            "cohorts_seen": self.cohorts_seen,
            "bank_mode": "external" if external else "embedded",
            "bank_path": self.bank.path,
            "activated": self.bank.activated,
        }
        return arrays, meta

    def _durability_validate_extra(self, arrays, meta) -> None:
        pm = meta.get("population")
        if pm is None or "population/bank_has_row" not in arrays:
            raise ValueError(
                "snapshot carries no population section — it was written "
                "by a plain run; drop the population block or point "
                "--checkpoint-dir at a population snapshot"
            )
        p = self.population
        mismatched = {
            k: (pm.get(k), getattr(p, k))
            for k in ("virtual_size", "sampler", "seed", "rounds_per_cohort",
                      "data_binding", "inherit")
            if pm.get(k) != getattr(p, k)
        }
        if mismatched:
            raise ValueError(
                "population snapshot/config mismatch (snapshot vs config): "
                f"{mismatched} — the cohort stream would silently diverge "
                "from the interrupted run"
            )
        if pm["bank_mode"] == "external":
            # The flushed file IS the snapshot's row data, so identity
            # matters twice over.  (a) It must be the SAME file the
            # snapshot recorded: a reattached bank of the right size
            # under a different bank_dir is some other experiment's rows
            # and would silently diverge the continued history (MUR901).
            if self.bank.path != pm["bank_path"]:
                raise ValueError(
                    f"population snapshot records its memmapped bank at "
                    f"{pm['bank_path']!r} but this config's bank_dir="
                    f"{p.bank_dir!r} opens {self.bank.path!r} — resuming "
                    "onto a different bank file would continue from some "
                    "other run's rows; keep the bank at the path the "
                    "snapshot recorded"
                )
            # (b) The live memmap must actually BE that file's data:
            # reattached = a fresh process adopted the flushed file;
            # flushed here = the SAME instance that wrote the snapshot is
            # restoring in place (the CLI retry envelope).  Path equality
            # alone is NOT enough — a fresh build whose bank file
            # vanished recreates an empty file at the same path.
            if not (self.bank.reattached or self._bank_flushed_here):
                raise ValueError(
                    f"population snapshot expects the memmapped bank at "
                    f"{pm['bank_path']!r} but no matching bank file was "
                    f"found under bank_dir={p.bank_dir!r} — the flushed "
                    "rows are the snapshot's data; restore them first"
                )

    def _durability_restore_extra(self, arrays, meta) -> None:
        from murmura_tpu.durability.snapshot import unpack_bool_mask

        pm = meta["population"]
        p = self.population
        # An external memmap bank is already reattached in place
        # (validated pre-restore); an embedded bank's rows ride the
        # snapshot and are scattered back here.
        if pm["bank_mode"] != "external":
            ids = arrays["population/bank_user_ids"]
            if len(ids):
                self.bank.scatter(ids, arrays["population/bank_rows"])
        self.bank.restore_activation(
            unpack_bool_mask(
                arrays["population/bank_has_row"], p.virtual_size
            )
        )
        self.cohorts_seen = int(pm["cohorts_seen"])
        self._prefetched = None
        cohort = arrays.get("population/cohort")
        self.cohort = (
            np.asarray(cohort, np.int64) if cohort is not None else None
        )
        if self.cohort is not None and p.data_binding == "user":
            # Re-bind each slot's data shard to its restored user — the
            # restored params are the resident cohort's rows and must
            # train on the same shards they did before the interruption.
            self._rebind_data(self.cohort)


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="population",
    module="murmura_tpu.population.engine",
    verdicts={
        "adaptive": composes(),
        # Stateless int8 survives cohort swaps; carried per-slot state
        # (EF residual / topk reference) would cross user streams.
        "compression": composes(
            carried_state=(
                "compression with carried state (error_feedback, or "
                "algorithm: topk) does not compose with population "
                "(cohort swaps reassign node slots); use stateless "
                "int8 or disable the population block"
            ),
        ),
        "dmtt": refuses(
            "population does not compose with dmtt (trust state is "
            "keyed by node identity, which cohort swaps reassign)"
        ),
        "faults": composes(),
        "mobility": composes(),
        "pipeline": refuses(
            "exchange.pipeline does not compose with population "
            "(the pipeline buffer is per-slot [N, P] carried state; "
            "cohort swaps reassign node slots, so a buffered row "
            "would be aggregated into the wrong user's stream — the "
            "compression/staleness carried-state rationale)"
        ),
    },
)
