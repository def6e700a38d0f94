"""Click CLI (reference: murmura/cli.py:34-308, a typer app; this
environment ships click, which typer wraps, so the commands are plain click).

Commands: ``run`` (simulation / tpu / distributed by config.backend),
``run-node`` (multi-machine ZMQ worker), ``list-components``.
"""

import json
from pathlib import Path
from typing import Optional

import click
from rich.console import Console
from rich.markup import escape
from rich.table import Table

from murmura_tpu.config import load_config
from murmura_tpu.utils.seed import set_seed

console = Console()


def _die_config_error(e: Exception) -> None:
    """Render a wiring-level ConfigError and exit (shared by every CLI
    path; escape(): error text may contain [bracketed] segments rich would
    otherwise swallow as markup tags)."""
    console.print(f"[bold red]Config error:[/bold red] {escape(str(e))}")
    raise SystemExit(1)


def _load_config_or_die(config_path: Path):
    """Load a config, rendering validation/parse failures as readable
    errors instead of raw tracebacks (a long-standing CLI friction)."""
    import pydantic
    import yaml

    try:
        return load_config(config_path)
    except pydantic.ValidationError as e:
        console.print(f"[bold red]Invalid config[/bold red] {config_path}:")
        for err in e.errors():
            loc = ".".join(str(p) for p in err["loc"]) or "<root>"
            console.print(f"  [yellow]{escape(loc)}[/yellow]: {escape(err['msg'])}")
        raise SystemExit(1)
    except (yaml.YAMLError, json.JSONDecodeError, ValueError) as e:
        # Malformed YAML/JSON or an unsupported file suffix.  escape():
        # error text may contain [bracketed] segments rich would otherwise
        # swallow as markup tags.
        console.print(
            f"[bold red]Cannot parse config[/bold red] {config_path}: "
            f"{escape(str(e))}"
        )
        raise SystemExit(1)


@click.group()
def app():
    """murmura_tpu: TPU-native decentralized federated learning."""


def _resolve_durability(config, checkpoint_dir, checkpoint_every, resume,
                        retries):
    """Merge the CLI durability flags over the config's ``durability:``
    block (explicit flag wins; ``None`` means "not given")."""
    d = config.durability
    if checkpoint_dir is None and d.checkpoint_dir is not None:
        checkpoint_dir = Path(d.checkpoint_dir)
    if checkpoint_every is None:
        checkpoint_every = d.checkpoint_every
    if resume is None:
        resume = d.resume
    if retries is None:
        retries = d.retries
    if resume and checkpoint_dir is None:
        raise click.UsageError("--resume requires --checkpoint-dir")
    if retries and checkpoint_dir is None:
        raise click.UsageError(
            "--retries requires --checkpoint-dir: a transient-failure "
            "retry restores from the last snapshot before re-dispatching "
            "(retrying consumed/donated buffers without a restore is "
            "never safe)"
        )
    if checkpoint_dir is not None and not resume:
        from murmura_tpu.utils.checkpoint import has_checkpoint

        if has_checkpoint(checkpoint_dir):
            # A fresh run would clobber the existing snapshot — and worse,
            # a retry before this run's first snapshot would silently
            # restore the STALE one and return the old run's history.
            raise click.UsageError(
                f"{checkpoint_dir} already holds a snapshot; pass --resume "
                "to continue that run, or point --checkpoint-dir at a "
                "clean directory"
            )
    return checkpoint_dir, checkpoint_every, resume, retries


def _train_with_retries(orchestrator, train, *, retries, config,
                        checkpoint_dir):
    """The shared retry envelope for `run` and `_run_sweep`:
    ``train()`` dispatches (computing remaining rounds itself, so a
    restored round counter is respected); on a classified-transient
    failure the orchestrator is restored from its last snapshot before
    re-dispatching — retrying consumed (donated) buffers without a
    restore is never safe, so an attempt with no snapshot to restore
    refuses loudly instead."""

    def _attempt(try_idx: int):
        if try_idx > 0:
            from murmura_tpu.utils.checkpoint import has_checkpoint

            if not has_checkpoint(checkpoint_dir):
                raise RuntimeError(
                    f"transient failure before the first snapshot landed "
                    f"in {checkpoint_dir} — nothing to restore, so a "
                    "retry is not donation-safe; rerun from scratch "
                    "(lower durability.checkpoint_every to shrink this "
                    "window)"
                )
            done = orchestrator.restore_checkpoint(str(checkpoint_dir))
            console.print(
                f"Retry {try_idx}: restored round [bold]{done}[/bold]"
            )
        return train()

    if not retries:
        return _attempt(0)
    from murmura_tpu.durability.dispatch import RetryPolicy, run_with_retry

    writers = orchestrator.telemetry
    if not isinstance(writers, (list, tuple)):
        writers = [writers]

    def _on_retry(exc, try_idx, delay):
        reason = f"{type(exc).__name__}: {exc}"[:300]
        console.print(
            f"[yellow]Transient failure ({escape(reason)}); "
            f"retry {try_idx}/{retries} in {delay:.1f}s[/yellow]"
        )
        for t in writers:
            if t is not None:
                t.emit(
                    "backend_degraded", reason=reason, retry=try_idx,
                    delay_s=round(delay, 2),
                    round=orchestrator.current_round,
                )

    return run_with_retry(
        _attempt,
        policy=RetryPolicy(
            max_retries=retries,
            base_delay_s=config.durability.retry_base_delay_s,
            max_delay_s=config.durability.retry_max_delay_s,
        ),
        on_retry=_on_retry,
    )


def _enforce_require_tpu(config, require_tpu_flag: bool) -> None:
    """The --require-tpu / durability.require_tpu / MURMURA_REQUIRE_TPU=1
    hard-fail: abort loudly instead of running on a device that is not
    a TPU."""
    from murmura_tpu.durability.dispatch import (
        BackendRequirementError,
        require_tpu,
        tpu_required,
    )

    if not (require_tpu_flag or tpu_required(config)):
        return
    try:
        require_tpu(
            source="--require-tpu" if require_tpu_flag
            else "durability.require_tpu/MURMURA_REQUIRE_TPU"
        )
    except BackendRequirementError as e:
        console.print(f"[bold red]{escape(str(e))}[/bold red]")
        raise SystemExit(2)


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--verbose/--quiet", "verbose", default=None, help="Override config verbosity")
@click.option("--output", "-o", type=click.Path(path_type=Path), default=None,
              help="Write history JSON here")
@click.option("--checkpoint-dir", type=click.Path(path_type=Path), default=None,
              help="Snapshot the complete run state here (simulation/tpu "
                   "backends; single runs, gangs and population streaming "
                   "alike — durability/snapshot.py). Default: "
                   "durability.checkpoint_dir")
@click.option("--checkpoint-every", type=int, default=None,
              help="Rounds between checkpoints (with --checkpoint-dir; "
                   "default: durability.checkpoint_every)")
@click.option("--resume/--no-resume", default=None,
              help="Resume from --checkpoint-dir if a snapshot exists "
                   "(byte-identical continuation, telemetry stream "
                   "appends; default: durability.resume)")
@click.option("--require-tpu", is_flag=True, default=False,
              help="Abort loudly unless the default JAX backend is a TPU "
                   "— never run elsewhere under its name. Env twin: "
                   "MURMURA_REQUIRE_TPU=1; config twin: "
                   "durability.require_tpu")
@click.option("--retries", type=int, default=None,
              help="Retry the training dispatch on classified-transient "
                   "errors (device/transport), restoring from the last "
                   "snapshot with exponential backoff + jitter. Requires "
                   "--checkpoint-dir. Default: durability.retries")
@click.option("--device", type=click.Choice(["cpu", "tpu"]), default=None,
              help="Force the JAX platform (reference: cli.py:37 device override)")
@click.option("--profile", "profile", is_flag=True, default=False,
              help="Capture a profiler trace (perfetto/xprof) for the "
                   "telemetry round window; with no telemetry.profile_rounds "
                   "configured the whole run is captured. Implies telemetry "
                   "(docs/OBSERVABILITY.md).")
@click.option("--seeds", "num_seeds", type=int, default=None,
              help="Gang-batch N seeds (experiment.seed .. +N-1) into one "
                   "vmapped program — sugar for `murmura sweep` with "
                   "num_seeds: N (docs/PERFORMANCE.md). 1 = normal run.")
def run(config_path: Path, verbose, output, checkpoint_dir, checkpoint_every,
        resume, require_tpu, retries, device, profile, num_seeds):
    """Run an experiment from a config file (reference: cli.py:34-60)."""
    if num_seeds is not None and num_seeds < 1:
        raise click.UsageError(
            f"--seeds must be >= 1 (got {num_seeds}); 1 = normal run, "
            "N > 1 gang-batches N seeds"
        )
    if num_seeds is not None and num_seeds > 1:
        if profile:
            raise click.UsageError(
                "--seeds (gang-batched execution) does not combine with "
                "--profile; profile a single run instead"
            )
        config = _load_config_or_die(config_path)
        if verbose is not None:
            config.experiment.verbose = verbose
        base = config.experiment.seed
        return _run_sweep(
            config, seeds=[base + i for i in range(num_seeds)],
            output=output, device=device, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            require_tpu=require_tpu, retries=retries,
        )
    if device is not None:
        # Must land before anything initializes the XLA backend.
        import jax

        jax.config.update("jax_platforms", device)
    config = _load_config_or_die(config_path)
    if verbose is not None:
        config.experiment.verbose = verbose
    checkpoint_dir, checkpoint_every, resume, retries = _resolve_durability(
        config, checkpoint_dir, checkpoint_every, resume, retries
    )
    _enforce_require_tpu(config, require_tpu)
    if profile:
        if config.backend == "distributed":
            raise click.UsageError(
                "--profile captures a device trace of the jitted round "
                "loop; backend: distributed trains on CPU worker "
                "processes (use the telemetry counters instead)"
            )
        config.telemetry.enabled = True
        if config.telemetry.profile_rounds == 0:
            config.telemetry.profile_rounds = config.experiment.rounds

    population_on = (
        config.population is not None and config.population.enabled
    )
    extra = ""
    if population_on:
        extra = (
            f", population={config.population.virtual_size} virtual users "
            f"/ {config.population.sampler} cohorts"
        )
    console.print(
        f"[bold cyan]murmura_tpu[/bold cyan] experiment "
        f"[bold]{config.experiment.name}[/bold] "
        f"(backend={config.backend}, nodes={config.topology.num_nodes}, "
        f"rounds={config.experiment.rounds}{extra})"
    )
    set_seed(config.experiment.seed)

    if config.backend == "distributed":
        if resume or checkpoint_dir is not None:
            raise click.UsageError(
                "--checkpoint-dir/--resume are not supported with "
                "backend: distributed (state lives in per-node processes)"
            )
        from murmura_tpu.distributed.runner import DistributedRunner
        from murmura_tpu.utils.factories import ConfigError

        try:
            history = DistributedRunner(config).run()
        except ConfigError as e:
            _die_config_error(e)
    else:
        from murmura_tpu.utils.factories import (
            ConfigError,
            build_network_from_config,
        )

        try:
            # checkpoint_dir (resume path) makes the telemetry stream
            # append exactly when a snapshot exists — a resumed run never
            # rotates its own events to *.prev (durability satellite).
            network = build_network_from_config(
                config,
                checkpoint_dir=(
                    str(checkpoint_dir) if resume and checkpoint_dir else None
                ),
            )
        except ConfigError as e:
            # Wiring-level config errors (data/model mismatch, unsupported
            # exchange mode, ...) — render the message, not the traceback.
            # Unexpected exceptions stay loud.
            _die_config_error(e)
        if resume:
            from murmura_tpu.utils.checkpoint import has_checkpoint

            if has_checkpoint(checkpoint_dir):
                done = network.restore_checkpoint(str(checkpoint_dir))
                console.print(f"Resumed from round [bold]{done}[/bold]")
            else:
                console.print(
                    f"[yellow]No checkpoint in {checkpoint_dir}; "
                    "starting from round 0[/yellow]"
                )

        history = _train_with_retries(
            network,
            lambda: network.train(
                rounds=max(
                    0, config.experiment.rounds - network.current_round
                ),
                verbose=config.experiment.verbose,
                checkpoint_dir=str(checkpoint_dir) if checkpoint_dir else None,
                checkpoint_every=checkpoint_every,
                rounds_per_dispatch=config.tpu.rounds_per_dispatch,
            ),
            retries=retries, config=config, checkpoint_dir=checkpoint_dir,
        )

    _display_results(history)
    if output is not None:
        output.parent.mkdir(parents=True, exist_ok=True)
        output.write_text(json.dumps(history, indent=2))
        console.print(f"History written to [bold]{output}[/bold]")
    if config.telemetry.enabled:
        from murmura_tpu.utils.factories import default_telemetry_dir

        console.print(
            f"Telemetry run written to "
            f"[bold]{default_telemetry_dir(config)}[/bold] — render it "
            "with `murmura report <dir>`"
        )
    return history


def _run_sweep(config, seeds, output, device, checkpoint_dir=None,
               checkpoint_every=None, resume=None, require_tpu=False,
               retries=None):
    """Shared gang-sweep driver (`murmura sweep` and `murmura run --seeds`):
    build the gang, optionally resume it from its durability snapshot,
    train (retry-wrapped like single runs), render the per-member summary,
    write per-member histories."""
    if device is not None:
        # Must land before anything initializes the XLA backend.
        import jax

        jax.config.update("jax_platforms", device)
    checkpoint_dir, checkpoint_every, resume, retries = _resolve_durability(
        config, checkpoint_dir, checkpoint_every, resume, retries
    )
    _enforce_require_tpu(config, require_tpu)
    from murmura_tpu.utils.factories import ConfigError, build_gang_from_config

    try:
        gang = build_gang_from_config(
            config, seeds=seeds,
            checkpoint_dir=(
                str(checkpoint_dir) if resume and checkpoint_dir else None
            ),
        )
    except ConfigError as e:
        _die_config_error(e)
    console.print(
        f"[bold cyan]murmura_tpu[/bold cyan] sweep "
        f"[bold]{config.experiment.name}[/bold] "
        f"(backend={config.backend}, nodes={config.topology.num_nodes}, "
        f"rounds={config.experiment.rounds}, "
        f"gang={gang.gang_size} member(s), batch={gang.batch})"
    )
    if resume:
        from murmura_tpu.utils.checkpoint import has_checkpoint

        if has_checkpoint(checkpoint_dir):
            done = gang.restore_checkpoint(str(checkpoint_dir))
            console.print(
                f"Resumed all {gang.gang_size} member(s) from round "
                f"[bold]{done}[/bold]"
            )
        else:
            console.print(
                f"[yellow]No checkpoint in {checkpoint_dir}; "
                "starting from round 0[/yellow]"
            )
    histories = _train_with_retries(
        gang,
        lambda: gang.train(
            rounds=max(0, config.experiment.rounds - gang.current_round),
            verbose=config.experiment.verbose,
            rounds_per_dispatch=config.tpu.rounds_per_dispatch,
            checkpoint_dir=str(checkpoint_dir) if checkpoint_dir else None,
            checkpoint_every=checkpoint_every,
        ),
        retries=retries, config=config, checkpoint_dir=checkpoint_dir,
    )

    table = Table(title="Sweep results (final round)")
    table.add_column("Member")
    table.add_column("Mean acc", justify="right")
    table.add_column("Std", justify="right")
    table.add_column("Loss", justify="right")
    for member, h in zip(gang.members, histories):
        if h["round"]:
            table.add_row(
                member.label,
                f"{h['mean_accuracy'][-1]:.4f}",
                f"{h['std_accuracy'][-1]:.4f}",
                f"{h['mean_loss'][-1]:.4f}",
            )
        else:
            table.add_row(member.label, "-", "-", "-")
    console.print(table)
    finals = [h["mean_accuracy"][-1] for h in histories if h["round"]]
    if finals:
        import numpy as np

        console.print(
            f"Across {len(finals)} member(s): mean accuracy "
            f"[bold green]{np.mean(finals):.4f}[/bold green] "
            f"± {np.std(finals):.4f}"
        )

    combined = {m.label: h for m, h in zip(gang.members, histories)}
    if output is not None:
        output.parent.mkdir(parents=True, exist_ok=True)
        output.write_text(json.dumps(combined, indent=2))
        console.print(f"Per-member histories written to [bold]{output}[/bold]")
    if config.telemetry.enabled:
        from murmura_tpu.utils.factories import default_telemetry_dir

        console.print(
            f"Per-member telemetry runs under "
            f"[bold]{default_telemetry_dir(config)}/<member>[/bold] — "
            "render one with `murmura report <dir>`"
        )
    return combined


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--seeds", "seeds", type=str, default=None,
              help="Comma-separated member seeds overriding the config's "
                   "sweep block (e.g. --seeds 1,2,3)")
@click.option("--verbose/--quiet", "verbose", default=None,
              help="Override config verbosity")
@click.option("--output", "-o", type=click.Path(path_type=Path), default=None,
              help="Write the per-member history JSON (one object keyed by "
                   "member label) here")
@click.option("--device", type=click.Choice(["cpu", "tpu"]), default=None,
              help="Force the JAX platform")
@click.option("--checkpoint-dir", type=click.Path(path_type=Path), default=None,
              help="Snapshot the FULL stacked gang state here (every "
                   "member's lane + history — durability/snapshot.py). "
                   "Default: durability.checkpoint_dir")
@click.option("--checkpoint-every", type=int, default=None,
              help="Rounds between checkpoints (with --checkpoint-dir; "
                   "default: durability.checkpoint_every)")
@click.option("--resume/--no-resume", default=None,
              help="Resume the whole gang from --checkpoint-dir if a "
                   "snapshot exists (all members continue byte-"
                   "identically; default: durability.resume)")
@click.option("--require-tpu", is_flag=True, default=False,
              help="Abort loudly unless the default JAX backend is a TPU")
@click.option("--retries", type=int, default=None,
              help="Retry the gang dispatch on classified-transient errors, "
                   "restoring all members from the last snapshot (requires "
                   "--checkpoint-dir; default: durability.retries)")
def sweep(config_path: Path, seeds, verbose, output, device, checkpoint_dir,
          checkpoint_every, resume, require_tpu, retries):
    """Gang-batched multi-seed execution (docs/PERFORMANCE.md).

    Stacks the sweep's member experiments — the config's ``sweep:`` block,
    or an explicit ``--seeds`` list — along a leading [S] axis and vmaps
    the round program over it: ONE XLA compile and one saturated device
    program cover the whole sweep.  Per-member histories are byte-identical
    on CPU to the corresponding single runs (`murmura check --ir` MUR500/
    MUR501 keep the gang collective- and recompile-clean).
    """
    config = _load_config_or_die(config_path)
    if verbose is not None:
        config.experiment.verbose = verbose
    seed_list = None
    if seeds is not None:
        try:
            seed_list = [int(s) for s in seeds.split(",") if s.strip()]
        except ValueError:
            raise click.UsageError(f"--seeds must be comma-separated ints, got {seeds!r}")
        if not seed_list:
            raise click.UsageError("--seeds parsed to an empty list")
    elif config.sweep is None:
        raise click.UsageError(
            "config has no sweep block; add one or pass --seeds 1,2,3"
        )
    return _run_sweep(
        config, seeds=seed_list, output=output, device=device,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        resume=resume, require_tpu=require_tpu, retries=retries,
    )


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--output", "-o", type=click.Path(path_type=Path),
              default=Path("frontier.json"), show_default=True,
              help="Write the frontier artifact (rule x attack x strength "
                   "curves + breaking points vs declared bounds) here")
@click.option("--device", type=click.Choice(["cpu", "tpu"]), default=None,
              help="Force the JAX platform")
@click.option("--require-tpu", is_flag=True, default=False,
              help="Abort loudly unless the default JAX backend is a TPU")
def frontier(config_path: Path, output, device, require_tpu):
    """Adversarial breaking-point search at gang speed
    (docs/ROBUSTNESS.md "The robustness frontier").

    For every (rule x adaptive attack x topology) cell of the config's
    ``frontier:`` grid (defaults cover krum/median/trimmed_mean/balance
    x adaptive-ALIE/bisection-gaussian x dense/sparse-exponential), runs
    an attack-strength x seed gang bucket with an outer successive-
    halving loop that re-aims the grid at the honest-accuracy cliff
    WITHOUT recompiling, then writes ``frontier.json`` charting each
    rule's empirical breaking point next to its MUR800 declared
    influence bound.  Render with `murmura report --frontier`.
    """
    if device is not None:
        # Must land before anything initializes the XLA backend.
        import jax

        jax.config.update("jax_platforms", device)
    config = _load_config_or_die(config_path)
    _enforce_require_tpu(config, require_tpu)
    from murmura_tpu.frontier import run_frontier, write_frontier
    from murmura_tpu.utils.factories import ConfigError

    f = config.frontier
    grid_desc = (
        f"{f.rules} x {f.attacks} x {f.topologies}" if f is not None
        else "default grid"
    )
    console.print(
        f"[bold cyan]murmura_tpu[/bold cyan] frontier "
        f"[bold]{config.experiment.name}[/bold] "
        f"(nodes={config.topology.num_nodes}, {escape(grid_desc)})"
    )
    try:
        artifact = run_frontier(
            config, progress=lambda s: console.print(f"[dim]{escape(s)}[/dim]")
        )
    except ConfigError as e:
        _die_config_error(e)
    path = write_frontier(artifact, output)
    console.print(f"Frontier artifact written to [bold]{path}[/bold]")
    from murmura_tpu.telemetry.report import render_frontier

    render_frontier(artifact, console=console)
    return artifact


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--output", "-o", type=click.Path(path_type=Path),
              default=Path("grid.json"), show_default=True,
              help="Write the cross-cell grid manifest here")
@click.option("--device", type=click.Choice(["cpu", "tpu"]), default=None,
              help="Force the JAX platform")
@click.option("--require-tpu", is_flag=True, default=False,
              help="Abort loudly unless the default JAX backend is a TPU")
@click.option("--plan-only", is_flag=True, default=False,
              help="Print the bucket plan (cells per compile-compatible "
                   "bucket) without executing anything")
def grid(config_path: Path, output, device, require_tpu, plan_only):
    """Run the config's rule x attack x topology x strength x seed grid
    through the compile-compatible scheduler (docs/ROBUSTNESS.md
    "Serving").

    Cells are partitioned into buckets by their traced jaxpr skeleton
    (the MUR203/MUR500 structural-equality key): cells share a bucket iff
    their programs are structurally equal, each bucket runs as ONE gang
    on the fused dispatch path — one compile per bucket, counted by
    CompileTracker and recorded in the manifest — and strength/seed
    become traced member inputs.  The full README grid (5 rules x
    gaussian x 5 strengths x 2 seeds = 50 cells) executes in 5 compiles.
    Render the manifest with `murmura report --grid`.
    """
    if device is not None:
        # Must land before anything initializes the XLA backend.
        import jax

        jax.config.update("jax_platforms", device)
    config = _load_config_or_die(config_path)
    _enforce_require_tpu(config, require_tpu)
    from murmura_tpu.serve.scheduler import plan_grid, run_grid, write_grid
    from murmura_tpu.utils.factories import ConfigError

    g = config.grid
    grid_desc = (
        f"{g.rules} x {g.attacks} x {g.topologies}" if g is not None
        else "default grid"
    )
    console.print(
        f"[bold cyan]murmura_tpu[/bold cyan] grid "
        f"[bold]{config.experiment.name}[/bold] "
        f"(nodes={config.topology.num_nodes}, {escape(grid_desc)})"
    )
    try:
        if plan_only:
            buckets = plan_grid(config)
            for b in buckets:
                console.print(
                    f"  bucket [bold]{b.key}[/bold] "
                    f"{b.rule} x {b.attack} x {b.topology}: "
                    f"{len(b.cells)} cells"
                )
            console.print(
                f"{sum(len(b.cells) for b in buckets)} cells in "
                f"{len(buckets)} buckets = {len(buckets)} compiles"
            )
            return
        artifact = run_grid(
            config, progress=lambda s: console.print(f"[dim]{escape(s)}[/dim]")
        )
    except ConfigError as e:
        _die_config_error(e)
    path = write_grid(artifact, output)
    console.print(
        f"Grid manifest written to [bold]{path}[/bold] "
        f"({artifact['total_cells']} cells, "
        f"{artifact['total_compiles']} compiles)"
    )
    from murmura_tpu.telemetry.report import render_grid

    render_grid(artifact, console=console)
    return artifact


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--device", type=click.Choice(["cpu", "tpu"]), default=None,
              help="Force the JAX platform")
@click.option("--require-tpu", is_flag=True, default=False,
              help="Abort loudly unless the default JAX backend is a TPU")
def serve(config_path: Path, device, require_tpu):
    """Crash-surviving multi-tenant experiment daemon
    (docs/ROBUSTNESS.md "Serving").

    Accepts experiment submissions over a local unix socket (`murmura
    submit`), multiplexes structurally-equal submissions onto warm
    compiled gang buckets (power-of-two growth via ``serve.capacity``;
    admissions are value-only splices — zero recompiles, MUR1601),
    checkpoints every tenant on the ``serve.checkpoint_every`` cadence,
    and survives SIGKILL: on restart every in-flight run resumes from
    its snapshot byte-identically (MUR1603).  State lives under
    ``serve.state_dir``; re-running this command over the same state
    dir IS the recovery path.
    """
    if device is not None:
        # Must land before anything initializes the XLA backend.
        import jax

        jax.config.update("jax_platforms", device)
    config = _load_config_or_die(config_path)
    _enforce_require_tpu(config, require_tpu)
    from murmura_tpu.serve.daemon import ServeDaemon
    from murmura_tpu.utils.factories import ConfigError

    try:
        daemon = ServeDaemon(config)
    except (ConfigError, ValueError) as e:
        _die_config_error(e)
    console.print(
        f"[bold cyan]murmura_tpu[/bold cyan] serve: listening on "
        f"[bold]{daemon.socket_path}[/bold] "
        f"(state_dir={daemon.state_dir}, capacity={daemon.capacity})"
    )
    try:
        daemon.serve_forever()
    except KeyboardInterrupt:
        daemon.close()
    console.print("murmura serve: stopped")


@app.command()
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--socket", "socket_path", required=True,
              type=click.Path(path_type=Path),
              help="The daemon's unix socket (serve.socket / "
                   "<state_dir>/daemon.sock)")
@click.option("--wait/--no-wait", default=False,
              help="Block until the submission reaches a terminal state "
                   "and print its final record")
@click.option("--poll-s", type=float, default=0.5, show_default=True,
              help="Status poll interval with --wait")
def submit(config_path: Path, socket_path, wait, poll_s):
    """Submit one experiment to a running `murmura serve` daemon.

    The submitted yaml is a plain single-experiment config (no sweep/
    frontier/grid/serve sections — the daemon owns multiplexing).
    Submissions whose configs differ only in seed / name / lr share one
    warm compiled bucket.  Socket-layer failures (a daemon mid-restart)
    are classified transient and retried with backoff
    (durability/dispatch.py).
    """
    import time as _time

    import yaml

    from murmura_tpu.serve.protocol import send_request

    with open(config_path, encoding="utf-8") as fh:
        raw = yaml.safe_load(fh)
    resp = send_request(str(socket_path), {"op": "submit", "config": raw})
    if not resp.get("ok"):
        console.print(f"[bold red]{escape(str(resp.get('error')))}[/bold red]")
        raise SystemExit(1)
    console.print(
        f"submitted [bold]{resp['id']}[/bold] "
        f"(bucket {resp['bucket']})"
    )
    if not wait:
        return resp
    while True:
        st = send_request(
            str(socket_path), {"op": "status", "id": resp["id"]},
        )
        sub = st.get("submission", {})
        if sub.get("state") in ("done", "failed", "evicted"):
            console.print(
                f"[bold]{resp['id']}[/bold] {sub['state']} "
                f"(final_accuracy={sub.get('final_accuracy')})"
            )
            if sub.get("state") != "done":
                raise SystemExit(1)
            return sub
        _time.sleep(poll_s)


@app.command("run-node")
@click.argument("config_path", type=click.Path(exists=True, path_type=Path))
@click.option("--node-id", type=int, required=True, help="This worker's node id")
@click.option("--t-start", type=float, required=True, help="Shared round-0 start time")
@click.option("--run-id", type=str, required=True, help="Run id from the head node")
@click.option("--host", type=str, default=None, help="This node's bind host")
@click.option("--resume/--no-resume", default=False,
              help="Rejoin a running experiment from this node's last "
                   "per-node checkpoint (faults.enabled crash recovery)")
def run_node(config_path: Path, node_id, t_start, run_id, host, resume):
    """Multi-machine ZMQ worker (reference: cli.py:143-208)."""
    from murmura_tpu.distributed.node_process import run_single_node
    from murmura_tpu.utils.factories import ConfigError

    config = _load_config_or_die(config_path)
    try:
        run_single_node(
            config, node_id=node_id, t_start=t_start, run_id=run_id, host=host,
            resume=resume,
        )
    except ConfigError as e:
        _die_config_error(e)


@app.command()
@click.argument(
    "paths", nargs=-1, type=click.Path(exists=True, path_type=Path)
)
@click.option(
    "--contracts/--no-contracts", default=True,
    help="Also run the cross-layer contract checks (registry/schema/test "
         "sync, topology zero-diagonal)",
)
@click.option(
    "--ir/--no-ir", "ir", default=None,
    help="Run the jaxpr/HLO IR contracts (MUR200-205) and AOT cost budgets "
         "(MUR206).  Default: on for the package check, off when explicit "
         "PATHS are given (the IR pass traces the live registry, not "
         "files).",
)
@click.option(
    "--flow/--no-flow", "flow", default=None,
    help="Run the jaxpr dataflow contracts (MUR800-804: per-neighbor "
         "influence bounds, scrub dominance, zero-free denominators).  "
         "Default: on for the package check, off when explicit PATHS are "
         "given (the flow pass traces the live registry, not files).",
)
@click.option(
    "--durability/--no-durability", "durability", default=None,
    help="Run the executable resume-determinism contract (MUR901/902: "
         "save→restore→replay byte-equality and zero-recompile restore "
         "per rule x exchange mode).  Compiles and runs tiny programs "
         "(~2 min on CPU).  Default: on for the package check, off when "
         "explicit PATHS are given.",
)
@click.option(
    "--adaptive/--no-adaptive", "adaptive", default=None,
    help="Run the adaptive-adversary contracts (MUR1000-1003: attack-"
         "state registry bijection, recompile-free adaptation, "
         "collective-inventory parity, feedback taint containment).  "
         "Compiles and runs tiny programs (~1 min on CPU).  Default: on "
         "for the package check, off when explicit PATHS are given.",
)
@click.option(
    "--staleness/--no-staleness", "staleness", default=None,
    help="Run the bounded-staleness contracts (MUR1100-1103: stale-state "
         "registry bijection, zero recompiles across staleness "
         "variation, collective-inventory parity with the drop-sync "
         "program, influence-bound/replay-hole taint runs over the "
         "staleness path).  Compiles and runs tiny programs (~1 min on "
         "CPU).  Default: on for the package check, off when explicit "
         "PATHS are given.",
)
@click.option(
    "--pipeline/--no-pipeline", "pipeline", default=None,
    help="Run the pipelined-rounds contracts (MUR1200-1203: pipeline-"
         "state registry bijection, zero recompiles across buffer "
         "swaps, collective-inventory parity with the serialized "
         "program, delayed-step influence/lagging-verdict taint runs).  "
         "Compiles and runs tiny programs (~1 min on CPU).  Default: on "
         "for the package check, off when explicit PATHS are given.",
)
@click.option(
    "--sharded/--no-sharded", "sharded", default=None,
    help="Run the param-axis sharding contracts (MUR1300-1303: sharded-P "
         "collective inventory — ppermute-only on 'nodes', one small "
         "psum over 'param' — zero recompiles across sharded rounds, "
         "shards=1 bit-parity with the unsharded program, sharded "
         "execution parity).  Compiles and runs tiny sharded programs "
         "(~1 min on CPU).  Default: on for the package check, off when "
         "explicit PATHS are given.",
)
@click.option(
    "--compose/--no-compose", "compose", default=None,
    help="Run the cross-feature composition grid (MUR1400-1403: lever-"
         "manifest/guard bijection with the executable refusal census, "
         "the generated pairwise grid — every declared-compatible pair "
         "builds, trains recompile-free and keeps collective-inventory "
         "parity — composed carried-state/stage-order parity, and "
         "flow-taint preservation on composed cells).  Compiles and "
         "runs one tiny composed program per compatible pair (~3 min "
         "on CPU).  Default: on for the package check, off when "
         "explicit PATHS are given.",
)
@click.option(
    "--memory/--no-memory", "memory", default=None,
    help="Run the static memory contracts (MUR1500-1503: committed "
         "memory_analysis() budgets per (rule x topology x feature) "
         "round-program cell against analysis/MEMORY.json, per-device "
         "peak ~P/shards across shards {1,2,4}, donation completeness "
         "per carried leaf, and the pipelined overlap-dependence "
         "proof).  AOT-compiles the full grid (~3 min on CPU; the "
         "compiles are shared across all four contracts).  Default: on "
         "for the package check, off when explicit PATHS are given.",
)
@click.option(
    "--serve/--no-serve", "serve_checks", default=None,
    help="Run the serving contracts (MUR1600-1603: bucket-key soundness "
         "— same scheduler bucket ⇔ structurally equal independently-"
         "traced jaxpr skeletons — zero recompiles across warm-bucket "
         "admissions, frozen-lane non-interference under eviction, "
         "daemon kill+recover resume completeness with byte-identical "
         "histories).  Compiles and runs tiny gangs plus an in-process "
         "daemon (~1 min on CPU).  Default: on for the package check, "
         "off when explicit PATHS are given.",
)
@click.option(
    "--observe/--no-observe", "observe_checks", default=None,
    help="Run the observability contracts (MUR1700-1703: metrics↔ledger "
         "parity — a daemon scrape equals an independent replay of the "
         "durable ledger + event streams — scrape non-interference "
         "(polling metrics/ping/list mid-generation causes zero "
         "recompiles and byte-identical tenant histories), trace-span "
         "well-formedness with phase_times reconciliation, and schema "
         "discipline — v2 events carry their migration note and v1 "
         "streams still render).  Compiles and runs in-process daemons "
         "(~1 min on CPU).  Default: on for the package check, off when "
         "explicit PATHS are given.",
)
@click.option(
    "--json", "as_json", is_flag=True, default=False,
    help="Emit findings (and budget-delta / flow-summary / "
         "compose-summary / memory-summary records) as JSON lines for "
         "editor/CI annotation instead of the greppable text format.",
)
@click.option(
    "--update-budgets", is_flag=True, default=False,
    help="Re-measure the AOT cost grid and rewrite analysis/BUDGETS.json; "
         "review the diff as perf history.",
)
@click.option(
    "--update-memory", is_flag=True, default=False,
    help="Re-measure the AOT memory grid and rewrite "
         "analysis/MEMORY.json; review the diff as residency history.",
)
def check(paths, contracts, ir, flow, durability, adaptive, staleness,
          pipeline, sharded, compose, memory, serve_checks, observe_checks,
          as_json, update_budgets, update_memory):
    """JAX-aware static analysis over PATHS (default: the installed
    murmura_tpu package).

    Runs the AST lint rules (MUR001-006: traced branches, host syncs,
    recompilation hazards, import-time allocation, dtype promotion), the
    cross-layer contract checks (MUR101-103), and — for the package check —
    the jaxpr/HLO IR contracts plus committed cost budgets (MUR200-206),
    the jaxpr dataflow contracts (MUR800-804: per-neighbor Byzantine
    influence bounds, NaN/attack scrub dominance, zero-free denominators),
    the durability contracts (MUR900 snapshot completeness via
    --contracts; MUR901/902 resume determinism via --durability), the
    adaptive-adversary contracts (MUR1000-1003 via --adaptive), the
    bounded-staleness contracts (MUR1100-1103 via --staleness), the
    pipelined-rounds contracts (MUR1200-1203 via --pipeline), the
    param-axis sharding contracts (MUR1300-1303 via --sharded), the
    cross-feature composition grid (MUR1400-1403 via --compose), the
    static memory contracts (MUR1500-1503 via --memory), the serving
    contracts (MUR1600-1603 via --serve), and the observability
    contracts (MUR1700-1703 via --observe).
    Exits non-zero when any finding survives suppression.  See
    docs/ANALYSIS.md for the rule catalogue and the
    ``# murmura: ignore[...]`` suppression syntax.
    """
    if update_budgets:
        from murmura_tpu.analysis import budgets

        path = budgets.update_budgets()
        console.print(
            f"Budgets rewritten to [bold]{path}[/bold] — review the diff "
            "as perf history"
        )
        return
    if update_memory:
        from murmura_tpu.analysis import memory as memory_mod

        path = memory_mod.update_memory()
        console.print(
            f"Memory budgets rewritten to [bold]{path}[/bold] — review "
            "the diff as residency history"
        )
        return
    from murmura_tpu.analysis import (
        format_findings,
        format_findings_json,
        run_check_detailed,
    )

    findings, records = run_check_detailed(
        list(paths) or None, contracts=contracts, ir=ir, flow=flow,
        durability=durability, adaptive=adaptive, staleness=staleness,
        pipeline=pipeline, sharded=sharded, compose=compose, memory=memory,
        serve=serve_checks, observe=observe_checks,
    )
    if as_json:
        out = format_findings_json(findings, records)
        if out:
            click.echo(out)
        if findings:
            raise SystemExit(1)
        return
    if findings:
        click.echo(format_findings(findings))
        console.print(
            f"[bold red]{len(findings)} finding(s)[/bold red] "
            "(see docs/ANALYSIS.md for rules and suppression)"
        )
        raise SystemExit(1)
    console.print("[bold green]murmura check: clean[/bold green]")


@app.command()
@click.argument(
    "run_dir", required=False, default=None,
    type=click.Path(exists=True, file_okay=False, path_type=Path),
)
@click.option(
    "--frontier", "frontier_path", default=None,
    type=click.Path(exists=True, dir_okay=False, path_type=Path),
    help="Render a frontier.json artifact (`murmura frontier`) instead of "
         "a telemetry run directory: empirical breaking point vs MUR800 "
         "declared influence bound per rule x attack x topology cell, "
         "plus each cell's honest-accuracy curve over attack strength.",
)
@click.option(
    "--grid", "grid_path", default=None,
    type=click.Path(exists=True, dir_okay=False, path_type=Path),
    help="Render a grid.json manifest (`murmura grid`) instead of a "
         "telemetry run directory: cells per compile-compatible bucket "
         "with per-bucket compile counts, and per-cell accuracy / "
         "phase-time accounting.",
)
@click.option(
    "--json", "as_json", is_flag=True, default=False,
    help="Emit the report as one JSON object (machine-readable; the same "
         "dict the tables render) instead of rich tables.",
)
@click.option(
    "--latest", "latest", is_flag=True, default=False,
    help="Report the newest run found under the current directory "
         "(telemetry_runs/, serve state dirs) instead of naming RUN_DIR — "
         "the `murmura runs` index picks it.",
)
@click.option(
    "--trace", "trace_path", default=None,
    type=click.Path(dir_okay=False, path_type=Path),
    help="Instead of tables, export the run's trace spans (submit→admit→"
         "generation→round, built from the event stream's wall-clock "
         "timestamps) as Chrome trace-event JSON — open in Perfetto "
         "(ui.perfetto.dev) or chrome://tracing.",
)
def report(run_dir: Optional[Path], frontier_path: Optional[Path],
           grid_path: Optional[Path], as_json: bool, latest: bool,
           trace_path: Optional[Path]):
    """Render a telemetry run directory (manifest.json + events.jsonl),
    or — with ``--frontier`` / ``--grid`` — a frontier artifact or a
    grid scheduler manifest.

    Works on any producer's output — a `murmura_tpu run` with
    ``telemetry.enabled``, a distributed run's Monitor-folded manifest, or
    a serve tenant's run directory.  Sections: accuracy,
    robustness/rule statistics, time breakdown by dispatch mode,
    checkpoints, device memory, per-node audit taps (e.g. krum rejection
    counts), distributed counters.  See docs/OBSERVABILITY.md;
    docs/ROBUSTNESS.md for reading the frontier tables.
    """
    if frontier_path is not None:
        from murmura_tpu.frontier import (
            frontier_break_summary,
            load_frontier,
        )
        from murmura_tpu.telemetry.report import render_frontier

        try:
            artifact = load_frontier(frontier_path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            console.print(f"[bold red]{escape(str(e))}[/bold red]")
            raise SystemExit(1)
        if as_json:
            click.echo(json.dumps({
                "grid": artifact.get("grid"),
                "summary": frontier_break_summary(artifact),
            }))
        else:
            render_frontier(artifact, console=console)
        return
    if grid_path is not None:
        from murmura_tpu.serve.scheduler import load_grid
        from murmura_tpu.telemetry.report import render_grid

        try:
            artifact = load_grid(grid_path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            console.print(f"[bold red]{escape(str(e))}[/bold red]")
            raise SystemExit(1)
        if as_json:
            click.echo(json.dumps({
                "grid": artifact.get("grid"),
                "buckets": artifact.get("buckets"),
                "total_cells": artifact.get("total_cells"),
                "total_compiles": artifact.get("total_compiles"),
            }))
        else:
            render_grid(artifact, console=console)
        return
    if run_dir is None and latest:
        from murmura_tpu.telemetry.registry import find_latest

        row = find_latest([Path(".")])
        if row is None:
            console.print(
                "[bold red]--latest: no telemetry runs found under the "
                "current directory[/bold red]"
            )
            raise SystemExit(1)
        run_dir = Path(row["path"])
        console.print(f"[dim]latest: {run_dir}[/dim]")
    if run_dir is None:
        console.print(
            "[bold red]murmura report needs a RUN_DIR (or --latest, "
            "--frontier <frontier.json> / --grid <grid.json>)[/bold red]"
        )
        raise SystemExit(1)
    if trace_path is not None:
        from murmura_tpu.telemetry.spans import write_chrome_trace

        try:
            n = write_chrome_trace(trace_path, [run_dir])
        except FileNotFoundError as e:
            console.print(f"[bold red]{escape(str(e))}[/bold red]")
            raise SystemExit(1)
        console.print(
            f"wrote [bold]{n}[/bold] trace span(s) to "
            f"[bold]{trace_path}[/bold] — open in Perfetto "
            "(ui.perfetto.dev) or chrome://tracing"
        )
        return
    from murmura_tpu.telemetry.report import build_report, render_report

    try:
        if as_json:
            rep = build_report(run_dir)
            rep.pop("manifest", None)  # the run dir already holds it
            click.echo(json.dumps(rep))
        else:
            render_report(run_dir, console=console)
    except FileNotFoundError as e:
        console.print(f"[bold red]{escape(str(e))}[/bold red]")
        raise SystemExit(1)


@app.command()
@click.argument("target", type=click.Path(exists=True, path_type=Path))
def metrics(target: Path):
    """Render a run's metrics as OpenMetrics text (ISSUE 19 leg 1).

    TARGET is either a running daemon's unix socket (the live
    ``{"op": "metrics"}`` scrape — read-only, recompile-free, MUR1701)
    or a telemetry run directory (the same registry folded offline from
    manifest.json + events.jsonl — batch and serve runs scrape
    identically).  Pipe to any OpenMetrics/Prometheus scraper, or diff
    two snapshots by eye.
    """
    import stat

    from murmura_tpu.telemetry.metrics import (
        MetricsRegistry,
        fold_run_events,
        render_openmetrics,
        scrape_socket,
    )

    if stat.S_ISSOCK(target.stat().st_mode):
        try:
            click.echo(scrape_socket(str(target)))
        except (OSError, RuntimeError) as e:
            console.print(f"[bold red]{escape(str(e))}[/bold red]")
            raise SystemExit(1)
        return
    if not target.is_dir():
        console.print(
            "[bold red]murmura metrics needs a daemon socket or a "
            "telemetry run directory[/bold red]"
        )
        raise SystemExit(1)
    reg = MetricsRegistry()
    fold_run_events(reg, target)
    click.echo(render_openmetrics(reg))


@app.command()
@click.option("--socket", "socket_path", required=True,
              type=click.Path(exists=True, path_type=Path),
              help="The daemon's unix socket (serve.socket / "
                   "<state_dir>/daemon.sock)")
@click.option("--interval", "interval_s", type=float, default=1.0,
              show_default=True, help="Refresh interval in seconds")
@click.option("--iterations", type=int, default=None,
              help="Stop after N refreshes (default: until Ctrl-C)")
def top(socket_path: Path, interval_s: float, iterations):
    """Live daemon dashboard off the read-only ops (ISSUE 19 leg 2).

    Refreshes a tenant table (state / round progress / accuracy / mean
    round time), warm-bucket occupancy, the cumulative daemon counters
    (admissions, evictions, resumes, compiles, generations), and the
    snapshot age — entirely from the ping/list/metrics protocol ops, so
    watching a daemon never perturbs it (MUR1701).
    """
    from murmura_tpu.telemetry.top import run_top

    try:
        run_top(
            str(socket_path), interval_s=interval_s, iterations=iterations,
            echo=click.echo,
        )
    except KeyboardInterrupt:
        pass
    except (OSError, RuntimeError) as e:
        console.print(f"[bold red]{escape(str(e))}[/bold red]")
        raise SystemExit(1)


@app.command()
@click.argument(
    "roots", nargs=-1, type=click.Path(exists=True, path_type=Path)
)
@click.option("--json", "as_json", is_flag=True, default=False,
              help="Emit one JSON object per indexed run (JSON lines)")
def runs(roots, as_json: bool):
    """Cross-run registry: index every telemetry artifact under ROOTS
    (default: the current directory) — run directories and serve state
    dirs (ISSUE 19 leg 3).

    One row per run/submission: kind, schema version, platform, rounds,
    best accuracy, terminal state, and whether the event stream has a
    torn tail (a crash mid-append).  Newest first; ``murmura report
    --latest`` renders the top row.
    """
    from murmura_tpu.telemetry.registry import index_runs, render_rows

    rows = index_runs([Path(r) for r in roots] or [Path(".")])
    if as_json:
        for row in rows:
            click.echo(json.dumps(row))
        return
    if not rows:
        console.print("no telemetry runs found")
        return
    click.echo(render_rows(rows))


@app.command("list-components")
@click.argument("component_type", required=False, default=None)
def list_components(component_type):
    """List available components (reference: cli.py:215-259).

    Optionally filter one category the way the reference does
    (``murmura list-components aggregators``); with no argument the whole
    table is shown.
    """
    from murmura_tpu.aggregation import AGGREGATORS
    from murmura_tpu.attacks import ATTACKS
    from murmura_tpu.topology.generators import TOPOLOGY_TYPES

    rows = {
        "topologies": ", ".join(TOPOLOGY_TYPES),
        "aggregators": ", ".join(sorted(AGGREGATORS)),
        "attacks": ", ".join(sorted(ATTACKS)),
        "backends": "simulation, tpu, distributed",
        "models": (
            "mlp, leaf.femnist[.tiny/.small/.baseline/.large/.xlarge], "
            "leaf.celeba, leaf.shakespeare, wearables.{uci_har,pamap2,ppg_dalia}"
        ),
        "datasets": (
            "synthetic, synthetic_sequences, leaf.{femnist,celeba,shakespeare}, "
            "wearables.{uci_har,pamap2,ppg_dalia}"
        ),
    }
    if component_type is not None:
        if component_type not in rows:
            console.print(f"[red]Unknown component type: {component_type}[/red]")
            console.print("Available: " + ", ".join(rows))
            raise SystemExit(1)
        rows = {component_type: rows[component_type]}

    table = Table(title="murmura_tpu components")
    table.add_column("Category", style="cyan")
    table.add_column("Options")
    for k, v in rows.items():
        table.add_row(k, v)
    console.print(table)


def _display_results(history) -> None:
    """Rich results table (reference: cli.py:266-304)."""
    if not history.get("round"):
        console.print("[yellow]No evaluation rounds recorded[/yellow]")
        return
    table = Table(title="Training results")
    table.add_column("Round", justify="right")
    table.add_column("Mean acc", justify="right")
    table.add_column("Std", justify="right")
    table.add_column("Loss", justify="right")
    n = len(history["round"])
    show = sorted(set([0, n // 2, n - 1]))
    for i in show:
        table.add_row(
            str(history["round"][i]),
            f"{history['mean_accuracy'][i]:.4f}",
            f"{history['std_accuracy'][i]:.4f}",
            f"{history['mean_loss'][i]:.4f}",
        )
    console.print(table)
    final = history["mean_accuracy"][-1]
    console.print(f"Final mean accuracy: [bold green]{final:.4f}[/bold green]")


def main():
    app()


if __name__ == "__main__":
    main()
