"""Config -> object wiring shared by the CLI and backends
(reference: murmura/utils/factories.py:16-190).

``build_network_from_config`` is the single path from a validated Config to
a ready-to-train Network for the simulation and tpu backends; the ZMQ
distributed backend reuses the component builders for its per-process nodes.
"""

import os
from pathlib import Path
from typing import Optional

import numpy as np

from murmura_tpu.aggregation import build_aggregator
from murmura_tpu.attacks import ATTACKS
from murmura_tpu.attacks.base import Attack
from murmura_tpu.config.schema import Config
from murmura_tpu.core.network import Network
from murmura_tpu.levers import refusal_reason
from murmura_tpu.core.rounds import build_round_program
from murmura_tpu.data.registry import build_federated_data
from murmura_tpu.models.registry import build_model
from murmura_tpu.topology.dynamic import MobilityModel
from murmura_tpu.topology.generators import create_topology


def select_compromised_count(n: int, pct: float, seed: int) -> int:
    """Size of the compromised set a (n, pct, seed) selection yields —
    the fail-loud guards below need the count before building anything."""
    from murmura_tpu.attacks.base import select_compromised

    return int(select_compromised(n, pct, seed).sum())


def build_attack(config: Config) -> Optional[Attack]:
    """Instantiate the attack from config (reference: factories.py:123-174).

    With ``attack.adaptive.enabled`` (schema validated it against the
    backend/type), the static attack becomes its closed-loop twin
    (attacks/adaptive.py): ``alie`` maps to adaptive ALIE, every other
    broadcast attack is wrapped in the generic scale bisection.
    """
    if not config.attack.enabled or not config.attack.type:
        return None
    n = config.topology.num_nodes
    pct = config.attack.percentage
    p = config.attack.params
    ad = config.attack.adaptive
    if ad.enabled and config.backend == "distributed":
        # Schema already rejects this; direct library construction gets
        # the same loud refusal (the adaptation loop is in-jit only).
        raise ConfigError(
            "adaptive attacks are not wired into backend: distributed"
        )
    # Compromised-set selection seed.  Defaults to the experiment seed (the
    # reference's behavior); an explicit attack.params.seed pins the
    # Byzantine placement independently of experiment.seed — the knob gang
    # sweeps (core/gang.py) rely on: a gang varies member seeds under ONE
    # traced program whose attack closures (e.g. the gaussian scatter
    # matrix) bake in a static compromised set, so the placement must not
    # follow the member seed.
    seed = int(p.get("seed", config.experiment.seed))

    def _bisect(inner: Attack) -> Attack:
        """Apply the adaptive scale-bisection wrapper when configured."""
        if not ad.enabled:
            return inner
        from murmura_tpu.attacks.adaptive import make_bisection_attack

        return make_bisection_attack(
            inner,
            scale_init=ad.scale_init,
            scale_max=ad.scale_max,
            growth=ad.growth,
            accept_target=ad.accept_target,
            ema_beta=ad.ema_beta,
        )

    if config.attack.type == "gaussian":
        # "std" is the reference's alternate key for the noise scale
        # (examples/configs/uci_har_byzantine.yaml).
        return _bisect(ATTACKS["gaussian"](
            num_nodes=n,
            attack_percentage=pct,
            noise_std=float(p.get("noise_std", p.get("std", 10.0))),
            seed=seed,
        ))
    if config.attack.type == "directed_deviation":
        return _bisect(ATTACKS["directed_deviation"](
            num_nodes=n,
            attack_percentage=pct,
            lambda_param=float(p.get("lambda_param", -5.0)),
            seed=seed,
        ))
    if config.attack.type in ("alie", "ipm"):
        # Colluding attacks: on simulation/tpu the jitted round step
        # computes the colluding vector from the TRUE honest rows
        # (omniscient variant — stronger than the papers' constructions;
        # alie.py/ipm.py docstrings).  On the ZMQ backend each colluding
        # NodeProcess instead estimates the statistics from the
        # coalition's own benign states (the papers' estimators) — see
        # NodeProcess._colluding_state.
        if config.backend == "distributed" and config.dmtt is not None:
            # DMTTNodeProcess overrides _execute_round without the
            # coalition branch; letting a colluding attack fall through to
            # the per-node apply() would silently run NO attack while the
            # experiment reports it ran — fail loud instead.
            raise ConfigError(
                f"attack type '{config.attack.type}' is not wired into "
                "the DMTT distributed round protocol; use backend: "
                "simulation/tpu, or a different attack on the "
                "distributed backend"
            )
        if config.attack.type == "alie":
            estimator = str(p.get("estimator", "omniscient"))
            if estimator not in ("omniscient", "coalition"):
                raise ConfigError(
                    f"attack.params.estimator must be 'omniscient' or "
                    f"'coalition', got {estimator!r}"
                )
            if (
                config.backend == "distributed" or estimator == "coalition"
            ) and select_compromised_count(n, pct, seed) < 2:
                # The coalition estimator (the paper's construction —
                # the ZMQ backend always, the jitted backends under
                # params.estimator: coalition) needs >= 2 colluders:
                # with one, sigma over the coalition sample is 0 and
                # mu - z*s degenerates to the colluder's benign state
                # — a silent no-attack run labeled "under ALIE" (ipm
                # has no such minimum: -eps*own is still an attack).
                raise ConfigError(
                    "the ALIE coalition estimator needs at least 2 "
                    "compromised nodes (mu/sigma over the coalition "
                    "sample is degenerate with 1); raise "
                    "attack.percentage, or use the omniscient estimator "
                    "on backend: simulation/tpu"
                )
            if ad.enabled:
                from murmura_tpu.attacks.adaptive import (
                    make_adaptive_alie_attack,
                )

                return make_adaptive_alie_attack(
                    num_nodes=n,
                    attack_percentage=pct,
                    z=p.get("z"),
                    seed=seed,
                    estimator=estimator,
                    eta=ad.eta,
                    accept_target=ad.accept_target,
                    ema_beta=ad.ema_beta,
                    z_min=ad.z_min,
                    z_cap=ad.z_cap,
                )
            return ATTACKS["alie"](
                num_nodes=n,
                attack_percentage=pct,
                z=p.get("z"),
                seed=seed,
                estimator=estimator,
            )
        if ad.enabled:
            # IPM adapts its own semantic knob — the negation factor
            # epsilon walks the acceptance signal as carried state
            # (atk_eps) — rather than riding the generic perturbation
            # bisection: the converged strength then lives on the
            # paper's epsilon axis (attacks/adaptive.py).
            from murmura_tpu.attacks.adaptive import make_adaptive_ipm_attack

            return make_adaptive_ipm_attack(
                num_nodes=n,
                attack_percentage=pct,
                epsilon=p.get("epsilon"),
                seed=seed,
                eta=ad.eta,
                accept_target=ad.accept_target,
                ema_beta=ad.ema_beta,
            )
        return ATTACKS["ipm"](
            num_nodes=n,
            attack_percentage=pct,
            epsilon=p.get("epsilon"),
            seed=seed,
        )
    if config.attack.type == "label_flip":
        if config.backend == "distributed":
            # The ZMQ NodeProcess builds its own data shard; the poison
            # transform is not wired there, and an identity state attack
            # over clean data would be a silent no-attack run labeled
            # "under label_flip" — fail loud instead.
            raise ConfigError(
                "attack type 'label_flip' is not wired into the ZMQ "
                "distributed backend (per-process data is built without "
                "the poison transform); use backend: simulation/tpu"
            )
        ff = float(p.get("flip_fraction", 1.0))
        if not 0.0 < ff <= 1.0:
            raise ConfigError(
                f"attack.params.flip_fraction must be in (0, 1], got {ff}"
            )
        return ATTACKS["label_flip"](
            num_nodes=n,
            attack_percentage=pct,
            flip_fraction=ff,
            seed=seed,
        )
    if config.attack.type == "topology_liar":
        inner = None
        inner_type = p.get("model_attack_type")
        if inner_type == "gaussian":
            inner = ATTACKS["gaussian"](
                num_nodes=n,
                attack_percentage=pct,
                noise_std=float(p.get("noise_std", 10.0)),
                seed=seed,
            )
        elif inner_type == "directed_deviation":
            inner = ATTACKS["directed_deviation"](
                num_nodes=n,
                attack_percentage=pct,
                lambda_param=float(p.get("lambda_param", -5.0)),
                seed=seed,
            )
        elif inner_type is not None:
            # Fail loud: a typo'd or unsupported inner attack must not
            # silently degrade to topology-lies-only (the experiment would
            # measure the wrong threat model).  'alie' is deliberately not
            # wired here: DMTT liars already coordinate through claims, and
            # the colluding model vector would need the full-network view
            # inside the per-claim transform.
            raise ConfigError(
                f"topology_liar model_attack_type '{inner_type}' is not "
                "supported; use 'gaussian' or 'directed_deviation' (or omit "
                "for topology lies only)"
            )
        return ATTACKS["topology_liar"](
            num_nodes=n, attack_percentage=pct, seed=seed, model_attack=inner
        )
    return None


def build_mobility(config: Config) -> Optional[MobilityModel]:
    """MobilityModel from config.mobility (reference: factories.py:177-190)."""
    if config.mobility is None:
        return None
    m = config.mobility
    return MobilityModel(
        num_nodes=config.topology.num_nodes,
        area_size=m.area_size,
        comm_range=m.comm_range,
        max_speed=m.max_speed,
        seed=m.seed,
        ensure_connected=m.ensure_connected,
    )


def build_fault_schedule(config: Config):
    """FaultSchedule from config.faults, or None when the model is off.

    The single construction path for EVERY consumer — the simulation/tpu
    orchestrator, each ZMQ node process, and the runner's FaultInjector —
    so the deterministic schedule is identical across processes and
    backends by construction (faults/schedule.py module docstring).
    """
    f = config.faults
    if not f.enabled:
        return None
    from murmura_tpu.faults.schedule import FaultSchedule

    return FaultSchedule(
        config.topology.num_nodes,
        crash_prob=f.crash_prob,
        recovery_prob=f.recovery_prob,
        min_down_rounds=f.min_down_rounds,
        link_drop_prob=f.link_drop_prob,
        straggler_prob=f.straggler_prob,
        straggler_factor=f.straggler_factor,
        seed=f.seed,
    )


def default_telemetry_dir(config: Config) -> str:
    """The run directory a telemetry-enabled config writes to when
    ``telemetry.dir`` is unset — shared by every consumer (Network wiring,
    the Monitor process, the CLI's report hint) so they agree on one path."""
    import os

    return config.telemetry.dir or os.path.join(
        "murmura_runs", config.experiment.name
    )


def build_telemetry_writer(config: Config, run_id=None, resume: bool = False):
    """TelemetryWriter from config.telemetry, or None when off.

    The single construction path for every consumer (the simulation/tpu
    orchestrator and the ZMQ Monitor process), so the manifest schema and
    run-dir resolution cannot drift between backends.  ``resume`` marks an
    intentional continuation (checkpoint restore) — the event stream
    appends; a fresh run into the same dir rotates the stale stream
    instead (writer.py).
    """
    t = config.telemetry
    if not t.enabled:
        return None
    from murmura_tpu.telemetry.writer import TelemetryWriter

    return TelemetryWriter(
        default_telemetry_dir(config),
        run_id=run_id,
        config=config,
        record_taps=True,
        phase_times=t.phase_times,
        memory_stats=t.memory_stats,
        profile_dir=t.profile_dir,
        profile_start_round=t.profile_start_round,
        profile_rounds=t.profile_rounds,
        resume=resume,
    )


def build_compression_spec(config: Config):
    """Trace-time CompressionSpec from config.compression, or None when
    off — the single construction path for every consumer (single runs and
    gangs), so codec semantics cannot drift between them."""
    c = config.compression
    if c.algorithm == "none":
        return None
    from murmura_tpu.ops.compress import CompressionSpec

    return CompressionSpec(
        algorithm=c.algorithm,
        block=c.block,
        topk_ratio=c.topk_ratio,
        error_feedback=c.error_feedback,
    )


def build_staleness_spec(config: Config, topology):
    """Trace-time StalenessSpec from config.exchange, or None when off —
    the single construction path for every consumer (single runs and
    gangs), so the base-graph/age semantics cannot drift between them.

    The base mask is the UNFAULTED exchange graph re-added stale edges
    are drawn from: the topology's static [N, N] mask (dense mode) or
    the all-active [k, N] edge mask (the static sparse exponential
    family; one_peer's round-varying mask was rejected at schema
    validation).
    """
    e = config.exchange
    if e.max_staleness <= 0:
        return None
    from murmura_tpu.core.stale import StalenessSpec
    from murmura_tpu.topology.sparse import SparseTopology

    if isinstance(topology, SparseTopology):
        base = np.ones(
            (len(topology.offsets), topology.num_nodes), np.float32
        )
    else:
        base = np.asarray(topology.mask(), dtype=np.float32)
    return StalenessSpec(
        max_staleness=e.max_staleness,
        discount=e.staleness_discount,
        base_mask=base,
    )


def pallas_agg_enabled(config: Config, node_axis_sharded: bool) -> bool:
    """Whether to route this build's aggregation through the fused Pallas
    kernels (tpu.pallas_agg, env twin MURMURA_PALLAS_AGG=1).  Never on a
    sharded NODE axis — pallas_call does not decompose under GSPMD, so
    that path keeps the lax kernels.  A sharded *param* axis is fine: the
    entry points themselves run shard-local grids under shard_map
    (ops/pallas_agg.py sharded-axis policy), so the toggle stays honest
    per axis rather than per mesh."""
    import os

    if node_axis_sharded:
        return False
    return bool(config.tpu.pallas_agg) or os.environ.get(
        "MURMURA_PALLAS_AGG"
    ) == "1"


def build_fault_spec(config: Config):
    """Trace-time FaultSpec from config.faults, or None when off."""
    f = config.faults
    if not f.enabled:
        return None
    from murmura_tpu.faults.schedule import FaultSpec

    return FaultSpec(
        nan_quarantine=f.nan_quarantine,
        nan_inject_nodes=tuple(f.nan_inject_nodes),
        nan_inject_from_round=f.nan_inject_from_round,
    )


class ConfigError(ValueError):
    """Wiring-level configuration error: the config validated structurally
    but its pieces cannot work together (data/model mismatch, unsupported
    exchange mode, ...).  The CLI renders these as messages, not
    tracebacks; unexpected ValueErrors stay loud."""


def resolved_param_dtype(config: Config) -> Optional[str]:
    """tpu.param_dtype with the documented large-N auto default: bfloat16
    from 64 nodes up (halves the [N, P] resident state and the SGD
    update's HBM traffic), float32 below, explicit setting always wins."""
    if config.backend != "tpu":
        return None
    if config.tpu.param_dtype is not None:
        return config.tpu.param_dtype
    return "bfloat16" if config.topology.num_nodes >= 64 else "float32"


def resolve_model(config: Config, data):
    """Build the model for a config with data-aware parameter sync and a
    fail-fast shape check.

    Shared by the in-process backends (build_network_from_config) and the
    ZMQ worker processes (NodeProcess._build_node), so every backend gets
    the wearables input_dim auto-sync and the data/model consistency error
    instead of a raw XLA dot_general failure rounds later.
    """
    model_params = dict(config.model.params)
    if config.backend == "tpu":
        # MXU mixed precision: bfloat16 matmul/conv inputs, float32 params
        # and accumulation (tpu.compute_dtype, default bfloat16).
        model_params.setdefault("compute_dtype", config.tpu.compute_dtype)
    if (
        "wearables." in config.model.factory
        and "input_dim" not in model_params
        and data.x.ndim == 3
    ):
        # Window params on the data side (window_size, include_heart_rate)
        # change the sample dimensionality; keep the model input in sync
        # unless the user pinned it explicitly.
        model_params["input_dim"] = int(data.x.shape[-1])
    model = build_model(config.model.factory, model_params)

    # Compare element counts, not shapes: models accept layout-equivalent
    # inputs (e.g. [28, 28] images for a [28, 28, 1] CNN input).
    sample_shape = tuple(data.x.shape[2:])
    if (
        model.input_shape
        and sample_shape
        and int(np.prod(sample_shape)) != int(np.prod(model.input_shape))
    ):
        raise ConfigError(
            f"data/model mismatch: adapter '{config.data.adapter}' yields "
            f"samples of shape {sample_shape} "
            f"({int(np.prod(sample_shape))} values) but model factory "
            f"'{config.model.factory}' expects input_shape "
            f"{tuple(model.input_shape)} ({int(np.prod(model.input_shape))} "
            "values); set model.params.input_dim (or the adapter's shape "
            "params) so they agree"
        )
    return model


# The one fixed compile-cache location when JAX_COMPILATION_CACHE_DIR is
# unset: inside the checkout (git-ignored) because the path is part of the
# cache key — a directory that moves between runs never hits.
COMPILATION_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def apply_compilation_cache() -> Optional[str]:
    """The one compile-cache rule; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of that variable
    is the only cache setting and nothing here touches the config.  Unset:
    the persistent cache goes to :data:`COMPILATION_CACHE_DIR`.  Every
    entry point that compiles round programs (``build_network_from_config``,
    the ZMQ workers, the ``check`` sweeps, ``chip_smoke.py``) calls this
    and nothing else configures the cache.

    When that fixed directory cannot be created or written — the package
    installed non-editable under a read-only prefix, where ``parents[2]`` is
    not a checkout — there is no persistent cache: a warning names the
    variable to set and ``None`` is returned.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    try:
        COMPILATION_CACHE_DIR.mkdir(parents=True, exist_ok=True)
        writable = os.access(COMPILATION_CACHE_DIR, os.W_OK | os.X_OK)
    except OSError:
        writable = False
    if not writable:
        import warnings

        warnings.warn(
            f"compile cache directory {COMPILATION_CACHE_DIR} is not "
            "writable: running without a persistent compile cache (set "
            "JAX_COMPILATION_CACHE_DIR to place one)",
            stacklevel=2,
        )
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILATION_CACHE_DIR))
    return str(COMPILATION_CACHE_DIR)


def _node_axis_sharded(config: Config, mesh=None) -> bool:
    """Whether the round step will run with the NODE axis sharded over a
    mesh — selects circulant shift lowerings (AggContext.node_axis_sharded).
    An explicitly passed mesh is authoritative (it IS the thing this flag
    describes) and is read per axis: a ("seed", "nodes", "param") mesh
    whose node axis is size 1 is NOT node-sharded however many param
    shards it carries.  Otherwise ``tpu.num_devices: null`` means "all
    available", so the device count is only known at build time — with
    param sharding configured, the node axis gets what the planned layout
    leaves it (parallel/mesh.plan_param_layout)."""
    if config.backend != "tpu":
        return False
    if mesh is not None:
        from murmura_tpu.parallel.mesh import mesh_node_axis

        return mesh_node_axis(mesh) > 1
    nd = config.tpu.num_devices
    if nd is None:
        import jax

        nd = jax.device_count()
    if config.tpu.param_shards > 1:
        from murmura_tpu.parallel.mesh import plan_param_layout

        try:
            _, nodes_ax, _ = plan_param_layout(
                config.topology.num_nodes, config.tpu.param_shards, nd
            )
        except ValueError:
            # Unfactorable layouts fail loudly at mesh build; the lowering
            # flag just needs a consistent answer until then.
            return nd > 1
        return nodes_ax > 1
    return nd > 1


def _gang_member_programs(config: Config, members, *, topology, attack,
                          sparse, node_axis_sharded, gang_param_shards):
    """Per-member RoundPrograms for a gang: data, init params and RNG are
    built per member seed while the attack placement / topology closures
    stay shared (the gang parity contract, core/gang.py).  Extracted from
    :func:`build_gang_from_config` so `murmura serve` can build a fresh
    generation's programs for value-only admission into a warm bucket
    (``GangNetwork.reset_run(member_programs=...)``) without constructing
    — and re-jitting — a new GangNetwork."""
    from murmura_tpu.core.gang import gang_hp_inputs
    from murmura_tpu.core.rounds import build_round_program as _build_program

    hp_inputs = gang_hp_inputs(members)
    n = config.topology.num_nodes
    rounds = config.experiment.rounds

    dmtt = None
    if config.dmtt is not None:
        from murmura_tpu.dmtt.protocol import DMTTParams

        dmtt = DMTTParams(**config.dmtt.model_dump(exclude={"allow_static"}))

    model = None
    agg = None
    probe_size = config.training.batch_size
    member_programs = []
    for i, member in enumerate(members):
        data = build_federated_data(
            config.data.adapter,
            config.data.params,
            num_nodes=n,
            seed=member.seed,
            max_samples=config.training.max_samples,
        )
        if attack is not None and attack.data_poison_fn is not None:
            if data.x_test is None:
                raise ConfigError(
                    "data-poisoning attacks need a clean eval split: this "
                    "adapter/config evaluates on the training shard "
                    "(holdout_fraction: 0.0); set holdout_fraction > 0 or "
                    "use an adapter with test shards"
                )
            data.y = attack.data_poison_fn(data.y, data.mask, data.num_classes)
        if i == 0:
            model = resolve_model(config, data)
            agg_params = dict(config.aggregation.params)
            if sparse:
                # Sparse topologies always run the [k, N] edge-mask
                # engine (the build_network_from_config wiring, shared
                # semantics — see the comment there).
                agg_params["exchange_offsets"] = list(topology.offsets)
                agg_params["sparse_exchange"] = True
            elif config.backend == "tpu" and config.tpu.exchange == "ppermute":
                if config.mobility is not None or config.dmtt is not None:
                    raise ConfigError(
                        "tpu.exchange: ppermute requires a static circulant "
                        "topology (mobility/dmtt graphs change per round)"
                    )
                offsets = topology.circulant_offsets()
                if offsets is None:
                    raise ConfigError(
                        f"tpu.exchange: ppermute requires a circulant "
                        f"topology (ring/k-regular); "
                        f"'{config.topology.type}' is not"
                    )
                agg_params["exchange_offsets"] = offsets
            if (
                config.aggregation.algorithm
                in ("krum", "median", "trimmed_mean", "geometric_median")
                and not sparse
                and config.mobility is None
                and config.dmtt is None
            ):
                agg_params.setdefault(
                    "max_candidates",
                    int(topology.mask().sum(axis=1).max()) + 1,
                )
            if config.aggregation.algorithm == "evidential_trust":
                probe_size = int(agg_params.get("max_eval_samples", 100))
            from murmura_tpu.ops.flatten import model_dimension, padded_dim
            import jax

            model_dim = model_dimension(
                jax.eval_shape(model.init, jax.random.PRNGKey(0))
            )
            if pallas_agg_enabled(config, node_axis_sharded):
                agg_params.setdefault("pallas", True)
            # Param-axis sharding pads the flat width (the
            # build_network_from_config contract): rules sizing buffers
            # from the flat dimension must see the padded width.
            agg_flat_dim = padded_dim(model_dim, gang_param_shards)
            if (
                gang_param_shards > 1
                and config.compression.algorithm == "int8"
                and (agg_flat_dim // gang_param_shards)
                % config.compression.block
            ):
                raise ConfigError(
                    f"compression.block={config.compression.block} does "
                    f"not divide the shard-local flat width "
                    f"{agg_flat_dim // gang_param_shards} (model_dim "
                    f"{model_dim} padded to {agg_flat_dim} over "
                    f"tpu.param_shards={gang_param_shards}) — "
                    + refusal_reason("compression", "sharding", "int8_block")
                )
            agg = build_aggregator(
                config.aggregation.algorithm, agg_params,
                model_dim=agg_flat_dim, total_rounds=rounds,
            )
        member_programs.append(_build_program(
            model,
            agg,
            data,
            local_epochs=config.training.local_epochs,
            batch_size=config.training.batch_size,
            lr=member.lr if member.lr is not None else config.training.lr,
            total_rounds=rounds,
            attack=attack,
            seed=member.seed,
            probe_size=probe_size,
            annealing_rounds=max(1, rounds // 2),
            lambda_weight=0.1,
            dmtt=dmtt,
            param_dtype=resolved_param_dtype(config),
            node_axis_sharded=node_axis_sharded,
            faults=build_fault_spec(config),
            audit_taps=config.telemetry.audit_taps,
            hp_inputs=hp_inputs,
            sparse_offsets=tuple(topology.offsets) if sparse else None,
            compression=build_compression_spec(config),
            staleness=build_staleness_spec(config, topology),
            pipeline=config.exchange.pipeline,
            param_shards=gang_param_shards,
        ))
    return member_programs


def build_gang_member_programs(config: Config, members):
    """Public per-member program builder for the serve admission path
    (serve/daemon.py): build ONE generation's RoundPrograms — per-seed
    data shards, init params, per-member lr — exactly as
    :func:`build_gang_from_config` would, without constructing a gang.
    The returned programs are VALUE sources for an existing warm bucket
    (``GangNetwork.reset_run(member_programs=...)``); they are never
    traced, so they must come from a config whose structural fingerprint
    matches the bucket template's (serve/scheduler.py enforces this)."""
    if config.backend == "distributed":
        raise ConfigError(
            "gang-batched serving needs the jitted backends; backend: "
            "distributed trains in per-node OS processes"
        )
    n = config.topology.num_nodes
    topology = create_topology(
        config.topology.type,
        num_nodes=n,
        p=config.topology.p,
        k=config.topology.k,
        seed=config.topology.seed,
    )
    from murmura_tpu.topology.sparse import SparseTopology

    sparse = isinstance(topology, SparseTopology)
    attack = build_attack(config)
    return _gang_member_programs(
        config, members,
        topology=topology,
        attack=attack,
        sparse=sparse,
        node_axis_sharded=_node_axis_sharded(config, None),
        gang_param_shards=(
            config.tpu.param_shards if config.backend == "tpu" else 1
        ),
    )


def build_gang_from_config(config: Config, seeds=None, mesh=None,
                           checkpoint_dir=None, retain_init=False,
                           min_batch=1):
    """Gang wiring (core/gang.py): one traced round program, S stacked
    member experiments — the ``murmura sweep`` / ``murmura run --seeds``
    path.

    Mirrors :func:`build_network_from_config` except that data, initial
    params, RNG bases and (optionally) traced scalar hyperparameters are
    built per member and stacked along a leading [S] axis, while the
    attack placement, topology, mobility and fault schedule stay shared
    (their seeds are independent of the experiment seed by construction —
    ``attack.params.seed`` defaults to the BASE config's experiment seed
    here so member programs share the attack's static closures).

    ``seeds``: explicit member-seed override (the CLI ``--seeds`` flag);
    otherwise ``config.sweep`` defines the members.
    """
    import os

    from murmura_tpu.core.gang import (
        GangNetwork,
        next_bucket,
        resolve_members,
    )

    if config.backend == "distributed":
        raise ConfigError(
            "gang-batched sweeps need the jitted backends; backend: "
            "distributed trains in per-node OS processes (run seeds as "
            "separate invocations there)"
        )
    if config.backend == "tpu" and config.tpu.multihost and mesh is None:
        from murmura_tpu.parallel.mesh import init_multihost

        init_multihost(
            coordinator_address=config.tpu.coordinator_address,
            num_processes=config.tpu.num_processes,
            process_id=config.tpu.process_id,
        )
    apply_compilation_cache()

    try:
        members = resolve_members(config, seeds)
    except ValueError as e:
        raise ConfigError(str(e))
    bucket = config.sweep.bucket if config.sweep is not None else True
    batch = (
        next_bucket(max(len(members), min_batch))
        if bucket else len(members)
    )

    n = config.topology.num_nodes
    topology = create_topology(
        config.topology.type,
        num_nodes=n,
        p=config.topology.p,
        k=config.topology.k,
        seed=config.topology.seed,
    )
    from murmura_tpu.topology.sparse import SparseTopology

    sparse = isinstance(topology, SparseTopology)
    if sparse and config.backend == "tpu":
        # The [k, N] edge mask rides the gang's vmap unbatched exactly
        # like the dense [N, N] matrix (lifted for ISSUE 11 — the
        # frontier sweeps sparse exponential graphs), but the gang MESH
        # still shards adjacency on node rows: the sparse mask needs the
        # edge_mask_sharding layout, which the gang path has not wired.
        raise ConfigError(refusal_reason("sparse", "sweep", "tpu_backend"))
    if config.population is not None and config.population.enabled:
        # The CLI `--seeds N` path reaches here with sweep=None, so the
        # schema's population x sweep validator never saw this pair.
        raise ConfigError(refusal_reason("population", "sweep"))
    # ONE attack for the whole gang: its compromised placement is seeded by
    # attack.params.seed (default: the base experiment seed), never by the
    # member seed — member programs share the attack's static closures
    # (e.g. the gaussian scatter matrix).  A single run reproduces a gang
    # member exactly by pinning attack.params.seed to this gang's base.
    attack = build_attack(config)
    mobility = build_mobility(config)

    gang_param_shards = (
        config.tpu.param_shards if config.backend == "tpu" else 1
    )
    if config.backend == "tpu" and mesh is None:
        if gang_param_shards > 1:
            # The sharding x sweep lift (ISSUE 16): a 4-D-role
            # ("seed", "nodes", "param") mesh so the gang's [S, N, P]
            # stacked state shards its trailing flat axis too.
            from murmura_tpu.parallel.mesh import make_gang_param_mesh

            mesh = make_gang_param_mesh(
                batch, n, gang_param_shards, config.tpu.num_devices
            )
        else:
            from murmura_tpu.parallel.mesh import make_gang_mesh

            mesh = make_gang_mesh(batch, n, config.tpu.num_devices)
    node_axis_sharded = (
        mesh is not None and dict(mesh.shape).get("nodes", 1) > 1
    )

    member_programs = _gang_member_programs(
        config, members,
        topology=topology,
        attack=attack,
        sparse=sparse,
        node_axis_sharded=node_axis_sharded,
        gang_param_shards=gang_param_shards,
    )

    writers = None
    if config.telemetry.enabled:
        # A gang resuming from an existing snapshot appends to its
        # members' event streams (the build_network_from_config contract,
        # automatically keyed off the snapshot's existence).
        gang_resume = False
        if checkpoint_dir is not None:
            from murmura_tpu.utils.checkpoint import has_checkpoint

            gang_resume = has_checkpoint(checkpoint_dir)
        base_dir = default_telemetry_dir(config)
        writers = []
        for member in members:
            mcfg = config.model_copy(deep=True)
            mcfg.experiment.seed = member.seed
            mcfg.telemetry.dir = os.path.join(base_dir, member.label)
            writers.append(build_telemetry_writer(mcfg, resume=gang_resume))

    try:
        return GangNetwork(
            program=member_programs[0],
            member_programs=member_programs,
            members=members,
            topology=topology,
            attack=attack,
            mobility=mobility,
            fault_schedule=build_fault_schedule(config),
            backend=(
                config.backend
                if config.backend in ("simulation", "tpu")
                else "simulation"
            ),
            mesh=mesh,
            num_devices=config.tpu.num_devices,
            bucket=bucket,
            base_lr=config.training.lr,
            recompile_guard=config.tpu.recompile_guard,
            transfer_guard=config.tpu.transfer_guard,
            telemetry_writers=writers,
            retain_init=retain_init,
            min_batch=min_batch,
        )
    except ValueError as e:
        # Gang-batchability failures (ragged member shapes, unfactorable
        # mesh) are wiring-level config errors — render as messages.
        raise ConfigError(str(e))


def build_network_from_config(
    config: Config, mesh=None, telemetry_resume: bool = False,
    checkpoint_dir=None,
) -> Network:
    """Full wiring: data + model + aggregator + attack -> Network.

    ``telemetry_resume``: this Network will continue a prior run (the CLI
    --resume path) — its telemetry appends to the run dir's existing event
    stream instead of rotating it.

    ``checkpoint_dir``: the durability snapshot location this run will
    resume from, when given.  It makes the telemetry-resume decision
    AUTOMATIC: the event stream appends exactly when a snapshot actually
    exists there (a resumed run must never rotate its own stream to
    ``*.prev``; a --resume with no snapshot yet is a fresh run and must
    rotate a stale one) — the caller no longer has to keep two flags in
    sync.
    """
    if checkpoint_dir is not None:
        from murmura_tpu.utils.checkpoint import has_checkpoint

        telemetry_resume = has_checkpoint(checkpoint_dir)
    if config.backend == "tpu" and config.tpu.multihost and mesh is None:
        # Must run before ANY jax call that initializes the XLA backend
        # (the eval_shape below would); jax.distributed.initialize refuses
        # to join a run after backend init.
        from murmura_tpu.parallel.mesh import init_multihost

        init_multihost(
            coordinator_address=config.tpu.coordinator_address,
            num_processes=config.tpu.num_processes,
            process_id=config.tpu.process_id,
        )

    apply_compilation_cache()

    n = config.topology.num_nodes
    seed = config.experiment.seed
    rounds = config.experiment.rounds

    data = build_federated_data(
        config.data.adapter,
        config.data.params,
        num_nodes=n,
        seed=seed,
        max_samples=config.training.max_samples,
    )
    model = resolve_model(config, data)

    topology = create_topology(
        config.topology.type,
        num_nodes=n,
        p=config.topology.p,
        k=config.topology.k,
        seed=config.topology.seed,
    )
    attack = build_attack(config)
    if attack is not None and attack.data_poison_fn is not None:
        if data.x_test is None:
            # Without a held-out split, evaluation falls back to the
            # training arrays — compromised nodes would be scored against
            # their own flipped labels and metric distortion would read
            # as attack damage.  Fail loud instead of measuring nonsense.
            raise ConfigError(
                "data-poisoning attacks need a clean eval split: this "
                "adapter/config evaluates on the training shard "
                "(holdout_fraction: 0.0); set holdout_fraction > 0 or "
                "use an adapter with test shards"
            )
        data.y = attack.data_poison_fn(data.y, data.mask, data.num_classes)
    mobility = build_mobility(config)

    # Probe sizing: evidential trust uses max_eval_samples
    # (evidential_trust.py:62-63); loss-probe rules use one training batch
    # (ubar.py:169).
    agg_params = dict(config.aggregation.params)

    from murmura_tpu.topology.sparse import SparseTopology

    sparse = isinstance(topology, SparseTopology)
    if sparse:
        # Sparse topologies (exponential/one_peer) ALWAYS run the [k, N]
        # edge-mask engine: the circulant rule paths with mask weights and
        # a round program whose adjacency input is the per-offset mask —
        # nothing O(N^2) is built on any backend (tpu.exchange is moot;
        # both settings route here).  Mobility/dmtt combinations were
        # rejected at schema validation.
        agg_params["exchange_offsets"] = list(topology.offsets)
        agg_params["sparse_exchange"] = True
    elif config.backend == "tpu" and config.tpu.exchange == "ppermute":
        # O(degree) neighbor exchange via circular shifts (circulant paths
        # in all six rules; krum assembles its candidate-pair distances
        # from rolled delta vectors instead of the global Gram matrix).
        if mobility is not None or config.dmtt is not None:
            raise ConfigError(
                "tpu.exchange: ppermute requires a static circulant topology "
                "(mobility/dmtt graphs change per round)"
            )
        offsets = topology.circulant_offsets()
        if offsets is None:
            raise ConfigError(
                f"tpu.exchange: ppermute requires a circulant topology "
                f"(ring/k-regular); '{config.topology.type}' is not"
            )
        agg_params["exchange_offsets"] = offsets
    if (
        config.aggregation.algorithm in ("krum", "median", "trimmed_mean", "geometric_median")
        and not sparse
        and mobility is None
        and config.dmtt is None
    ):
        # Static graph: bound the per-node candidate block at max-degree+1
        # so the candidate-gathering rules work on [N, m, ...] instead of
        # per-node [N, N, ...] copies (O(N^3) at m = N).  Dynamic graphs
        # (mobility/DMTT TopB) have no static degree bound and keep the
        # dense default.
        agg_params.setdefault(
            "max_candidates", int(topology.mask().sum(axis=1).max()) + 1
        )
    if config.aggregation.algorithm == "evidential_trust":
        probe_size = int(agg_params.get("max_eval_samples", 100))
    else:
        probe_size = config.training.batch_size

    # Need model_dim for sketchguard before building the program: derive from
    # a throwaway init (cheap, host-side).
    import jax

    from murmura_tpu.ops.flatten import model_dimension, padded_dim

    model_dim = model_dimension(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))
    )
    if pallas_agg_enabled(config, _node_axis_sharded(config, mesh)):
        # Fused Pallas aggregation kernels (ops/pallas_agg.py); rules that
        # have no kernel path ignore the param.
        agg_params.setdefault("pallas", True)
    # Param-axis sharding pads the flat width; rules that size buffers
    # from the flat dimension (sketchguard's tables, krum candidate math)
    # must see the PADDED width — the width their [N, P] operand will
    # actually have.  The pad columns are exact zeros, inert everywhere.
    param_shards = config.tpu.param_shards if config.backend == "tpu" else 1
    agg_flat_dim = padded_dim(model_dim, param_shards)
    if (
        param_shards > 1
        and config.compression.algorithm == "int8"
        and (agg_flat_dim // param_shards) % config.compression.block
    ):
        # The build_round_program backstop raises the same refusal; here
        # it renders as a config message with the concrete numbers.
        raise ConfigError(
            f"compression.block={config.compression.block} does not "
            f"divide the shard-local flat width "
            f"{agg_flat_dim // param_shards} (model_dim {model_dim} "
            f"padded to {agg_flat_dim} over tpu.param_shards="
            f"{param_shards}) — "
            + refusal_reason("compression", "sharding", "int8_block")
        )
    agg = build_aggregator(
        config.aggregation.algorithm, agg_params, model_dim=agg_flat_dim,
        total_rounds=rounds,
    )

    dmtt = None
    if config.dmtt is not None:
        from murmura_tpu.dmtt.protocol import DMTTParams

        dmtt = DMTTParams(**config.dmtt.model_dump(exclude={"allow_static"}))

    program = build_round_program(
        model,
        agg,
        data,
        local_epochs=config.training.local_epochs,
        batch_size=config.training.batch_size,
        lr=config.training.lr,
        total_rounds=rounds,
        attack=attack,
        seed=seed,
        probe_size=probe_size,
        annealing_rounds=max(1, rounds // 2),
        lambda_weight=0.1,
        dmtt=dmtt,
        param_dtype=resolved_param_dtype(config),
        node_axis_sharded=_node_axis_sharded(config, mesh),
        faults=build_fault_spec(config),
        audit_taps=config.telemetry.audit_taps,
        sparse_offsets=tuple(topology.offsets) if sparse else None,
        compression=build_compression_spec(config),
        staleness=build_staleness_spec(config, topology),
        pipeline=config.exchange.pipeline,
        param_shards=param_shards,
    )

    if config.backend == "tpu" and mesh is None:
        if param_shards > 1:
            from murmura_tpu.parallel.mesh import make_param_mesh

            mesh = make_param_mesh(
                n, param_shards, config.tpu.num_devices
            )
        else:
            from murmura_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(config.tpu.num_devices)

    net_kwargs = dict(
        program=program,
        topology=topology,
        attack=attack,
        mobility=mobility,
        backend=config.backend if config.backend in ("simulation", "tpu") else "simulation",
        mesh=mesh,
        seed=seed,
        profile_dir=config.tpu.profile_dir,
        recompile_guard=config.tpu.recompile_guard,
        transfer_guard=config.tpu.transfer_guard,
        fault_schedule=build_fault_schedule(config),
        telemetry=build_telemetry_writer(config, resume=telemetry_resume),
    )
    spec = build_population_spec(config)
    if spec is not None:
        from murmura_tpu.population import PopulationNetwork

        return PopulationNetwork(**net_kwargs, population=spec)
    return Network(**net_kwargs)


def build_population_spec(config: Config):
    """PopulationSpec from config.population, or None when off — the
    single construction path for every consumer, so cohort-draw semantics
    cannot drift between the orchestrator and any future tooling."""
    p = config.population
    if p is None or not p.enabled:
        return None
    from murmura_tpu.population import PopulationSpec

    return PopulationSpec(
        virtual_size=p.virtual_size,
        sampler=p.sampler,
        seed=p.seed,
        rounds_per_cohort=p.rounds_per_cohort,
        data_binding=p.data_binding,
        bank_dir=p.bank_dir,
        inherit=p.inherit,
    )
