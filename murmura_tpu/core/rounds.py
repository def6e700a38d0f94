"""The FL round as one jitted program.

The reference executes a round as Python orchestration — per-node
``local_train`` loops (murmura/core/node.py:59-109), a state snapshot, attack
application, per-node aggregation calls, then per-node evaluation
(murmura/core/network.py:80-199).  Here the whole round body is one traced
function over stacked [N, ...] pytrees:

    round_step(params, agg_state, key, adj, compromised, round_idx, data)
        -> (params', agg_state', metrics)

- local training is a ``lax.scan`` over the per-epoch batch schedule with
  per-node effective batch sizes / step counts as masks (reproducing the
  reference's ragged DataLoaders, network.py:278-287);
- compromised nodes skip training via an update mask instead of a Python
  ``if`` (network.py:99-101);
- the attack transforms the *broadcast* tensor only (network.py:108-119);
- aggregation is an adjacency-masked rule over the gathered [N, P] tensor;
- evaluation is a vmapped masked sweep including evidential uncertainty
  (node.py:111-196).

Under ``backend: simulation`` this runs vmapped on one device; under
``backend: tpu`` the same function is jitted with the node axis sharded over
a mesh so the gather rides ICI (see parallel/mesh.py).
"""

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from murmura_tpu.aggregation.base import AggContext, AggregatorDef
from murmura_tpu.aggregation.probe import combined_probe_metric, pairwise_probe_eval
from murmura_tpu.attacks.adaptive import AdaptiveAttack, acceptance_feedback
from murmura_tpu.attacks.base import Attack
from murmura_tpu.data.base import FederatedArrays
from murmura_tpu.faults.schedule import FaultSpec
from murmura_tpu.dmtt.protocol import (
    DMTTParams,
    dmtt_round_update,
    init_dmtt_state,
)
from murmura_tpu.models.core import Model
from murmura_tpu.core.pipeline import (
    ADJ_KEY as PIPE_ADJ_KEY,
    BCAST_KEY as PIPE_BCAST_KEY,
    OWN_KEY as PIPE_OWN_KEY,
    VALID_KEY as PIPE_VALID_KEY,
    init_pipeline_state,
    pipeline_state_keys,
)
from murmura_tpu.core.stale import (
    CACHE_KEY as STALE_CACHE_KEY,
    STALE_STATE_KEYS,
    StalenessSpec,
    init_stale_state,
    make_stale_fold,
)
from murmura_tpu.ops.compress import (
    COMPRESS_STATE_KEYS,
    CompressionSpec,
    compress_exchange,
    init_compress_state,
)
from murmura_tpu.ops.flatten import make_flatteners, make_sharded_flatteners
from murmura_tpu.parallel.mesh import constrain_flat, constrain_replicated
from murmura_tpu.ops.losses import (
    evidential_loss,
    masked_cross_entropy,
    masked_next_token_cross_entropy,
    uncertainty_metrics,
)


DMTT_STATE_KEYS = (
    "dmtt_c_hat",
    "dmtt_alpha",
    "dmtt_beta",
    "dmtt_collab",
    "dmtt_selected",
)


@dataclass(frozen=True)
class RoundProgram:
    """A compiled round step plus the pieces needed to drive it.

    ``train_step`` is the per-round program (local SGD + attack + exchange +
    aggregation); ``eval_step`` is the full test-set sweep, compiled
    separately so the orchestrator pays for it only on recorded rounds
    (``eval_every``) instead of fusing it into every round the way the
    reference's loop does (murmura/core/network.py:80-94).
    """

    train_step: Callable  # (params, agg_state, key, adj, compromised, round_idx, data)
    eval_step: Callable  # (params, data) -> eval metrics
    init_params: Any  # stacked [N, ...] pytree
    init_agg_state: Dict[str, np.ndarray]
    data_arrays: Dict[str, np.ndarray]
    num_nodes: int
    model_dim: int
    evidential: bool
    # Built with a FaultSpec: train_step takes an extra [N] ``alive`` mask
    # after ``compromised`` (dead nodes freeze via the update mask, NaN
    # sentinel quarantines non-finite updates).  False => the signature and
    # traced program are byte-identical to pre-faults builds.
    faulted: bool = False
    # Traced-scalar hyperparameters lifted from closure constants into
    # ``data_arrays["hp_*"]`` inputs (build_round_program(hp_inputs=...)) so
    # a gang (core/gang.py) can vary them per member under vmap.  () =>
    # the traced program is byte-identical to pre-gang builds.
    hp_inputs: Tuple[str, ...] = ()
    # Sparse exchange mode (topology/sparse.py; docs/SCALING.md): when
    # non-empty, the program's adjacency input is the [k, N] per-offset
    # edge mask of a SparseTopology instead of the dense [N, N] matrix —
    # nothing O(N^2) enters the lowered HLO (MUR600).  () => byte-identical
    # to pre-sparse builds.
    sparse_offsets: Tuple[int, ...] = ()
    # Compressed exchange (ops/compress.py; docs/PERFORMANCE.md): the
    # broadcast tensor is quantized in-jit before the exchange (int8 blocks
    # or top-k delta), receivers dequantize before rule math, and the
    # quantization residual optionally rides ``agg_state`` as error
    # feedback.  None (default) => the traced program is byte-identical to
    # pre-compression builds.
    compression: Optional[CompressionSpec] = None
    # Closed-loop adaptive attack (attacks/adaptive.py;
    # docs/ROBUSTNESS.md): the attack's adaptation state rides
    # ``agg_state`` under ATTACK_STATE_KEYS and each round's acceptance
    # taps update it in-jit.  False (default) => the traced program is
    # byte-identical to pre-adaptive builds.
    adaptive_attack: bool = False
    # Bounded-staleness gossip (core/stale.py; docs/ROBUSTNESS.md
    # "Bounded staleness"): a per-sender payload cache + age stamp ride
    # ``agg_state`` under STALE_STATE_KEYS, and disrupted base-graph
    # edges are re-added with the (discounted) cached payload while its
    # age stays within ``max_staleness``.  None (default) => the traced
    # program is byte-identical to pre-staleness builds.
    staleness: Optional[StalenessSpec] = None
    # Pipelined rounds (core/pipeline.py; docs/PERFORMANCE.md "Pipelined
    # rounds"): round r's local training overlaps round r-1's
    # exchange + aggregation through a double-buffered pipeline stage
    # riding ``agg_state`` under PIPELINE_STATE_KEYS — one-round-delayed
    # averaging (arXiv:2002.01119).  False (default) => the traced
    # program is byte-identical to pre-pipeline builds.
    pipelined: bool = False
    # The training-only stage of the round — the delayed-averaging
    # reference hook (core/pipeline.run_delayed_reference): same
    # signature as ``train_step`` but returns ``(own_flat, train_ok)``,
    # the post-scrub trained [N, P] flat params and the [N] quarantine
    # verdict (1.0 = clean).  A pure sub-computation of ``train_step``
    # (jit DCEs the attack/codec/exchange stages), present on every
    # build.
    train_flat: Optional[Callable] = None
    # Param-axis sharding (parallel/mesh.py, docs/PERFORMANCE.md
    # "Param-axis sharding"): the flat vector is zero-padded so this
    # shard count divides its width, and on a ("seed", "nodes", "param")
    # mesh every [N, flat_dim] tensor — broadcast, stale cache, pipeline
    # buffers, EF residual/top-k reference, the aggregation output —
    # shards its columns over the param axis.  1 (default) => flat_dim ==
    # model_dim and the traced program is byte-identical to pre-sharding
    # builds (MUR1302).
    param_shards: int = 1
    # Padded flat width (== model_dim unless param_shards pads it).
    flat_dim: int = 0

    @property
    def sparse(self) -> bool:
        return bool(self.sparse_offsets)

    @property
    def stale(self) -> bool:
        return self.staleness is not None


# From this size on the stacked initial state is drawn in one program and
# kept on the host (``build_round_program``): a size no model of the paper's
# reaches at the node counts one chip holds.
LARGE_STATE_BYTES = 1 << 30


def _state_bytes(model: Model, n: int, dtype) -> int:
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return n * jnp.dtype(dtype).itemsize * sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes)
    )


def _broadcast_to_leaf(mask: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def build_round_program(
    model: Model,
    agg: AggregatorDef,
    data: FederatedArrays,
    *,
    local_epochs: int = 1,
    batch_size: int = 64,
    lr: float = 0.01,
    total_rounds: int = 20,
    attack: Optional[Attack] = None,
    seed: int = 42,
    probe_size: Optional[int] = None,
    annealing_rounds: Optional[int] = None,
    lambda_weight: float = 0.1,
    eval_chunk: int = 1024,
    dmtt: Optional[DMTTParams] = None,
    param_dtype: Optional[str] = None,
    node_axis_sharded: bool = False,
    faults: Optional[FaultSpec] = None,
    audit_taps: bool = False,
    hp_inputs: Tuple[str, ...] = (),
    sparse_offsets: Optional[Tuple[int, ...]] = None,
    compression: Optional[CompressionSpec] = None,
    staleness: Optional[StalenessSpec] = None,
    pipeline: bool = False,
    param_shards: int = 1,
) -> RoundProgram:
    """Trace-ready round step for a network of ``data.num_nodes`` nodes.

    Args:
        probe_size: samples per node handed to probe-based aggregators
            (UBAR's one batch — ubar.py:169; evidential trust's
            max_eval_samples — evidential_trust.py:62-63).
        annealing_rounds: evidential-loss KL annealing horizon (reference
            wiring: rounds // 2, factories.py:114).
        dmtt: when set, the trust protocol runs inside the round step —
            TOPO_CLAIM verification, Beta trust, TopB collaborator selection
            gate the exchange mask handed to the aggregator
            (murmura/dmtt/node_process.py:150-250).
        faults: when set, the round step takes an extra per-round ``alive``
            mask (after ``compromised``) and gains the operational-fault
            semantics (docs/ROBUSTNESS.md): dead nodes freeze params via
            the update mask exactly like compromised ones; an in-jit
            numerical sentinel quarantines nodes whose post-training
            update is non-finite (masked out of the exchange, params
            rolled back to the pre-round value); a node with zero alive
            neighbors degrades to self-model.  ``None`` (default) leaves
            the traced program byte-identical to pre-faults builds.
        audit_taps: telemetry.audit_taps — aggregation rules surface
            per-node decision tensors (``tap_*`` stats) and the fault
            sentinel emits per-node quarantine/scrub/alive flags, all
            riding the normal history-output path as ``agg_tap_*``
            metrics.  Taps are collective- and recompile-clean by
            contract (``murmura check --ir`` MUR400/MUR402); False
            (default) leaves the traced program byte-identical.
        hp_inputs: scalar hyperparameters to lift from trace-time closure
            constants into round-program *inputs* riding ``data_arrays``
            (gang-batched execution, core/gang.py — a vmapped gang member
            gets its own value from the [S]-leading stacked entry):
            ``"lr"`` => the SGD step reads ``d["hp_lr"]``;
            ``"attack_scale"`` => the attack's broadcast perturbation is
            scaled by ``d["hp_attack_scale"]``
            (``own + scale * (attacked - own)``; requires an attack).
            () (default) leaves the traced program byte-identical.
    """
    n = data.num_nodes
    num_classes = data.num_classes or model.num_classes
    evidential = model.evidential

    # Param-axis sharding (tpu.param_shards; docs/PERFORMANCE.md
    # "Param-axis sharding"): the flat vector pads to a multiple of the
    # shard count and every [N, P]-shaped tensor of the round shards its
    # columns over the mesh's "param" axis.  Mode rejections are loud and
    # config-time, like every other exchange-mode combination above.
    param_shards = int(param_shards)
    if param_shards < 1:
        raise ValueError(f"param_shards must be >= 1, got {param_shards}")
    if param_shards > 1:
        if dmtt is not None:
            raise ValueError(
                "param-axis sharding does not compose with DMTT (the "
                "N x N claim cross-evaluation unravels every broadcast "
                "row into a full model per pair — there is no sharded "
                "formulation of that sweep)"
            )
        if compression is not None and compression.algorithm == "topk":
            raise ValueError(
                "param-axis sharding does not compose with topk "
                "compression: the per-row global top-k needs the full "
                "[P] row resident on one device, defeating the shard — "
                "use the int8 codec (its per-block scales shard with P)"
            )

    # Sparse exchange mode: the adjacency input is the [k, N] per-offset
    # edge mask of a SparseTopology (edge i <- (i + o) % N active), never a
    # dense [N, N] matrix.  Every adjacency manipulation below then runs in
    # edge-mask space via rolls of [N] node flags (which lower to boundary
    # ppermutes on a sharded node axis, like the circulant rules' rolls).
    sparse_offsets = (
        tuple(int(o) for o in sparse_offsets) if sparse_offsets else ()
    )
    sparse = bool(sparse_offsets)
    if sparse and dmtt is not None:
        raise ValueError(
            "sparse exchange mode does not compose with DMTT (claim "
            "verification needs the dense per-round exchange graph)"
        )
    if compression is not None and dmtt is not None:
        raise ValueError(
            "compressed exchange does not compose with DMTT (the claim "
            "cross-evaluation consumes the uncompressed broadcast — a "
            "compressed probe sweep would verify against different models "
            "than the rules aggregate)"
        )

    # Bounded-staleness gossip (core/stale.py): the exchange layer that
    # serves a disrupted sender's last delivered payload (age-bounded,
    # optionally discount-weighted) instead of dropping its edges.
    if staleness is not None:
        if faults is None:
            raise ValueError(
                "bounded staleness (exchange.max_staleness) requires the "
                "fault model (build_round_program(faults=...)): without "
                "a fault schedule nothing ever misses a round and the "
                "cache layer would be dead weight in every program"
            )
        if dmtt is not None:
            raise ValueError(
                "bounded staleness does not compose with DMTT (the "
                "exchange graph is trust-gated per round; serving a "
                "cached row would bypass the round's claim verification)"
            )
        if staleness.base_mask is None:
            raise ValueError(
                "StalenessSpec.base_mask must carry the static base "
                "exchange graph (the topology mask / all-active sparse "
                "edge mask) — re-added edges are drawn from it"
            )
        expect = (
            (len(sparse_offsets or ()), n) if sparse_offsets else (n, n)
        )
        if tuple(np.shape(staleness.base_mask)) != expect:
            raise ValueError(
                f"staleness base mask shape "
                f"{tuple(np.shape(staleness.base_mask))} does not match "
                f"this build's exchange layout {expect}"
            )
    # Closed-loop adaptive attack (attacks/adaptive.py): the attacker's
    # adaptation state rides agg_state (ATTACK_STATE_KEYS) and the audit
    # taps ARE its feedback channel, so tapping is forced on — taps are
    # collective- and recompile-inert by contract (MUR400/402), so this
    # changes metrics surface, never communication.  attack=None or a
    # static attack leaves every adaptive branch below untaken: the
    # traced program is byte-identical to pre-adaptive builds.
    adaptive = isinstance(attack, AdaptiveAttack)
    if adaptive:
        if dmtt is not None:
            raise ValueError(
                "adaptive attacks do not compose with DMTT (the claims "
                "channel is a second feedback path the adaptation state "
                "does not model)"
            )
        audit_taps = True

    # Pipelined rounds (core/pipeline.py): round r's delayed aggregation
    # of the buffered round-(r-1) exchange overlaps round r's training.
    if pipeline:
        if dmtt is not None:
            raise ValueError(
                "pipelined rounds do not compose with DMTT (the claim "
                "exchange + trust gate runs between production and "
                "aggregation every round; delaying the aggregation would "
                "verify claims against a different round's graph)"
            )
        if adaptive:
            raise ValueError(
                "pipelined rounds do not compose with adaptive attacks: "
                "the acceptance feedback would observe round r-1's "
                "aggregation while the attack state already advanced at "
                "round r's production, changing the closed loop's timing "
                "semantics — run adaptive experiments serialized"
            )

    # Built after the adaptive block so the fold's audit taps follow the
    # final audit_taps value (adaptive attacks force tapping on).
    if staleness is not None:
        stale_fold = make_stale_fold(
            staleness, sparse_offsets=tuple(sparse_offsets or ()),
            audit=audit_taps,
        )
    else:
        stale_fold = None

    def _sender_view(vec):  # murmura: traced
        """[k, N] sender-side view of a [N] node flag: row j holds
        vec[(i + offsets[j]) % N] at column i."""
        return jnp.stack([jnp.roll(vec, -o) for o in sparse_offsets])

    def _edges_mask_both(adj, vec):  # murmura: traced
        """Drop edges whose receiver OR sender has flag 0."""
        if sparse:
            return adj * vec[None, :] * _sender_view(vec)
        return adj * vec[:, None] * vec[None, :]

    def _edges_mask_sender(adj, vec):  # murmura: traced
        """Drop edges whose sender has flag 0."""
        if sparse:
            return adj * _sender_view(vec)
        return adj * vec[None, :]

    def _in_degree(adj):  # murmura: traced
        return adj.sum(axis=0) if sparse else adj.sum(axis=1)

    hp_inputs = tuple(hp_inputs)
    unknown_hp = set(hp_inputs) - {"lr", "attack_scale"}
    if unknown_hp:
        raise ValueError(f"unknown hp_inputs: {sorted(unknown_hp)}")
    if "attack_scale" in hp_inputs and attack is None:
        raise ValueError(
            "hp_inputs includes 'attack_scale' but no attack is configured "
            "— there is no broadcast perturbation to scale"
        )

    # ---- static per-node batch schedule (network.py:278-287) -------------
    eff_batch = data.effective_batch(batch_size)  # [N]
    steps = data.steps_per_epoch(batch_size)  # [N]
    max_steps = int(steps.max())
    global_batch = int(eff_batch.max())

    if annealing_rounds is None:
        annealing_rounds = max(1, total_rounds // 2)

    # ---- initial stacked params ------------------------------------------
    init_keys = jax.random.split(jax.random.PRNGKey(seed), n)
    resident = jnp.dtype(
        jnp.float32 if param_dtype in (None, "float32") else param_dtype
    )
    large_state = _state_bytes(model, n, resident) >= LARGE_STATE_BYTES
    if large_state:
        # A state of a GiB or more is drawn and cast in one program (leaf
        # by leaf it would stand in float32 beside its cast, and compile a
        # program a leaf's shape), and the program's record of it goes to
        # the host: the network's own copy is the one on the device, and a
        # second would stay there for the whole run.
        init_params = jax.device_get(jax.jit(
            lambda keys: jax.tree_util.tree_map(
                lambda l: l.astype(resident), jax.vmap(model.init)(keys)
            )
        )(init_keys))
    else:
        init_params = jax.vmap(model.init)(init_keys)
        if param_dtype not in (None, "float32"):
            # tpu.param_dtype=bfloat16: store the stacked [N, ...] state (and
            # therefore the gathered/exchanged [N, P] tensor) in bf16 — halves
            # resident HBM and ICI bytes at the cost of parameter precision.
            # compute_dtype independently controls matmul input precision.
            dt = jnp.dtype(param_dtype)
            init_params = jax.tree_util.tree_map(
                lambda l: l.astype(dt), init_params
            )
    template = jax.tree_util.tree_map(lambda l: l[0], init_params)
    if param_shards > 1:
        ravel, unravel, model_dim, flat_dim = make_sharded_flatteners(
            template, param_shards
        )
    else:
        ravel, unravel, model_dim = make_flatteners(template)
        flat_dim = model_dim
    # A state of a GiB or more under a rule that can take it leaf by leaf,
    # with nothing between training and the rule that needs the [N, P] row
    # (an attack, the fault sentinels, a codec, the stale cache, the
    # pipeline's buffers, the trust protocol's probes): the round never
    # flattens it (``_round_body_by_leaf``).  Shapes and the job decide;
    # every other program is what it was.
    by_leaf = (
        agg.leafwise and large_state
        and attack is None and faults is None and compression is None
        and staleness is None and dmtt is None and not pipeline
        and param_shards == 1 and not agg.init_state(n)
    )
    if param_shards > 1 and compression is not None:
        # int8 per-block scales must shard WITH the payload: a quant block
        # straddling a shard boundary would compute its scale from two
        # shards' columns (a silent cross-shard amax collective every
        # round) — reject at config time, loudly.
        local = flat_dim // param_shards
        if local % compression.block:
            raise ValueError(
                f"compression.block={compression.block} does not divide "
                f"the shard-local flat width {local} (flat_dim "
                f"{flat_dim} over {param_shards} param shards) — a quant "
                "block straddling a shard boundary would compute its "
                "scale across shards; pick a block dividing "
                f"{local} (or adjust tpu.param_shards)"
            )

    # ---- probe batches for loss/trust-probe rules ------------------------
    p_size = int(min(data.max_samples, probe_size or global_batch))
    probe_x = data.x[:, :p_size]
    probe_y = data.y[:, :p_size]
    probe_mask = data.mask[:, :p_size]

    eval_x, eval_y, eval_mask = data.eval_arrays

    data_arrays = {
        "x": data.x,
        "y": data.y,
        "mask": data.mask,
        "num_samples": data.num_samples.astype(np.int32),
        "eff_batch": eff_batch,
        "steps": steps,
        "probe_x": probe_x,
        "probe_y": probe_y,
        "probe_mask": probe_mask,
        "eval_x": eval_x,
        "eval_y": eval_y,
        "eval_mask": eval_mask,
    }
    # Lifted scalar hyperparameters ride the data dict (one input pytree to
    # thread, one sharding rule: rank-0 leaves replicate).  The defaults
    # reproduce the closure-constant behavior exactly — x * 1.0 and a
    # traced scalar holding the same f32 value multiply bit-identically.
    if "lr" in hp_inputs:
        data_arrays["hp_lr"] = np.asarray(lr, np.float32)
    if "attack_scale" in hp_inputs:
        data_arrays["hp_attack_scale"] = np.asarray(1.0, np.float32)

    # ---- per-node loss ----------------------------------------------------
    def loss_of_outputs(outputs, yb, mb, round_idx):  # murmura: traced
        if evidential:
            lambda_t = (
                jnp.minimum(1.0, round_idx / max(1, annealing_rounds)) * lambda_weight
            )
            return evidential_loss(outputs, yb, mb, num_classes, lambda_t)
        loss, _ = masked_cross_entropy(outputs, yb, mb)
        return loss

    def node_loss(params_i, xb, yb, mb, key, round_idx):  # murmura: traced
        outputs = model.apply(params_i, xb, key, True)
        return loss_of_outputs(outputs, yb, mb, round_idx)

    def summed_loss(params, xb, yb, mb, keys, round_idx):  # murmura: traced
        # Nodes share nothing in apply_stacked, so the gradient of the sum
        # with respect to the stacked tree is the per-node gradients.
        outputs = model.apply_stacked(params, xb, keys, True)
        losses = jax.vmap(loss_of_outputs, in_axes=(0, 0, 0, None))(
            outputs, yb, mb, round_idx
        )
        return losses.sum()

    # A model that offers a stacked forward (models/core.py Model) trains
    # and evaluates through it; every other model through vmap(apply).
    if model.apply_train is not None:
        # Trained by ``local_training_by_node`` below, and evaluated one
        # node after another too.
        def stacked_apply(params, x):  # murmura: traced
            return jax.lax.map(
                lambda px: model.apply(px[0], px[1], None, False), (params, x)
            )
    elif model.apply_stacked is not None:
        grads_fn = jax.grad(summed_loss)

        def stacked_apply(params, x):  # murmura: traced
            return model.apply_stacked(params, x, None, False)
    else:
        grads_fn = jax.vmap(jax.grad(node_loss), in_axes=(0, 0, 0, 0, 0, None))
        stacked_apply = jax.vmap(lambda p, x: model.apply(p, x, None, False))

    def local_training(params, d, honest, key, round_idx):  # murmura: traced
        """local_epochs x masked-batch SGD (reference: node.py:59-109)."""

        def epoch_body(params, epoch_key):
            perm_key, step_key = jax.random.split(epoch_key)
            # Shuffle valid samples to the front: invalid slots sort last.
            # The draw is pinned replicated under a param-sharded mesh
            # (identity otherwise): the legacy threefry lowering is
            # sharding-dependent, and an output partitioned over "param"
            # would shuffle DIFFERENT batches than the unsharded program
            # (parallel/mesh.constrain_replicated).
            u = constrain_replicated(
                jax.random.uniform(perm_key, d["mask"].shape)
            ) + (1.0 - d["mask"]) * 10.0
            perm = jnp.argsort(u, axis=1)  # [N, S]

            def step_body(params, t):
                j = jnp.arange(global_batch)
                pos = t * d["eff_batch"][:, None] + j[None, :]
                pos = pos % jnp.maximum(d["num_samples"], 1)[:, None]
                idx = jnp.take_along_axis(perm, pos, axis=1)  # [N, B]
                xb = jax.vmap(lambda xs, ii: xs[ii])(d["x"], idx)
                yb = jax.vmap(lambda ys, ii: ys[ii])(d["y"], idx)
                batch_mask = (j[None, :] < d["eff_batch"][:, None]).astype(jnp.float32)

                node_keys = jax.random.split(jax.random.fold_in(step_key, t), n)
                grads = grads_fn(
                    params, xb, yb, batch_mask, node_keys, round_idx
                )
                update = honest * (t < d["steps"]).astype(jnp.float32)  # [N]
                # lr is a closure constant unless lifted to an input
                # (hp_inputs — gang members vary it per member under vmap).
                eff_lr = d["hp_lr"] if "lr" in hp_inputs else lr
                # Update math in float32, cast back: keeps bf16 params
                # (tpu.param_dtype) dtype-stable through the scan carry and
                # rounds once per step instead of per multiply.
                with jax.named_scope("murmura.update"):
                    new_params = jax.tree_util.tree_map(
                        lambda p, g: (
                            p - eff_lr * _broadcast_to_leaf(update, p) * g.astype(jnp.float32)
                        ).astype(p.dtype),
                        params,
                        grads,
                    )
                return new_params, None

            params, _ = jax.lax.scan(step_body, params, jnp.arange(max_steps))
            return params, None

        epoch_keys = jax.random.split(key, local_epochs)
        params, _ = jax.lax.scan(epoch_body, params, epoch_keys)
        return params

    def node_step_loss(params_i, xb, yb, mb, key):  # murmura: traced
        """One node's loss with the part that is the model's own, and the
        step's counts summed over the samples the batch's mask keeps
        (``Model.apply_train``)."""
        outputs, auxiliary = model.apply_train(params_i, xb, key)
        with jax.named_scope("murmura.head"):
            loss, _ = masked_next_token_cross_entropy(outputs, yb, mb)
            own = (auxiliary["loss"] * mb).sum() / jnp.maximum(mb.sum(), 1.0)
        counts = jax.tree_util.tree_map(
            lambda c: jnp.tensordot(mb, c.astype(jnp.float32), axes=1),
            auxiliary["step"],
        )
        return loss + own, counts

    def local_training_by_node(params, d, honest, key):  # murmura: traced
        """``local_training`` for a model with a training rule of its own
        (``Model.apply_train``): the same batch schedule, keys and update
        mask, one node's step after another in one loop over (node, epoch,
        step).  A node's parameters leave the stacked state for a step and
        go back in place, so one node's gradients and activations are live
        at a time; after each step it takes, ``Model.after_step`` moves
        what takes no gradient.  Returns the state and the nodes'
        ``Model.step_metrics`` (each [N])."""
        orders, step_keys = [], []
        epoch_keys = jax.random.split(key, local_epochs)
        for e in range(local_epochs):
            perm_key, step_key = jax.random.split(epoch_keys[e])
            u = constrain_replicated(
                jax.random.uniform(perm_key, d["mask"].shape)
            ) + (1.0 - d["mask"]) * 10.0
            orders.append(jnp.argsort(u, axis=1))
            step_keys.append(step_key)
        orders, step_keys = jnp.stack(orders), jnp.stack(step_keys)  # [E, N, S], [E]
        eff_lr = d["hp_lr"] if "lr" in hp_inputs else lr
        j = jnp.arange(global_batch)
        grad_fn = jax.value_and_grad(node_step_loss, has_aux=True)
        row = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        template_i = jax.tree_util.tree_map(lambda l: l[0], params)
        (_, counts0), _ = jax.eval_shape(
            grad_fn, template_i, d["x"][0, :global_batch], d["y"][0, :global_batch],
            jnp.zeros((global_batch,), jnp.float32), step_keys[0],
        )
        steps_a_node = local_epochs * max_steps

        def one_step(k, carry):
            params, counted = carry
            i, e, t = (
                k // steps_a_node, (k % steps_a_node) // max_steps, k % max_steps
            )
            eff, count = row(d["eff_batch"], i), row(d["num_samples"], i)
            idx = row(row(orders, e), i)[(t * eff + j) % jnp.maximum(count, 1)]
            node_key = row(
                jax.random.split(jax.random.fold_in(row(step_keys, e), t), n), i
            )
            p = jax.tree_util.tree_map(lambda l: row(l, i), params)
            (_, counts), grads = grad_fn(
                p, row(d["x"], i)[idx], row(d["y"], i)[idx],
                (j < eff).astype(jnp.float32), node_key,
            )
            update = row(honest, i) * (t < row(d["steps"], i)).astype(jnp.float32)
            with jax.named_scope("murmura.update"):
                stepped = jax.tree_util.tree_map(
                    lambda l, g: l.astype(jnp.float32)
                    - eff_lr * update * g.astype(jnp.float32),
                    p, grads,
                )
            moved = stepped
            if model.after_step is not None:
                moved = model.after_step(stepped, counts)
            with jax.named_scope("murmura.update"):
                params = jax.tree_util.tree_map(
                    lambda whole, l, m: jax.lax.dynamic_update_index_in_dim(
                        whole,
                        jnp.where(update > 0, m, l.astype(jnp.float32)).astype(l.dtype),
                        i, 0,
                    ),
                    params, p, moved,
                )
            counted = jax.tree_util.tree_map(
                lambda a, c: a.at[i].add(update * c), counted, counts
            )
            return params, counted

        counted = jax.tree_util.tree_map(
            lambda c: jnp.zeros((n,) + c.shape, c.dtype), counts0
        )
        params, counted = jax.lax.fori_loop(
            0, n * steps_a_node, one_step, (params, counted)
        )
        stats = {}
        if model.step_metrics is not None:
            stats = jax.vmap(model.step_metrics)(params, counted)
        return params, stats

    def train_nodes(params, d, train_mask, key, round_idx):  # murmura: traced
        """The round's local training by the path the model takes: the
        trained state and the nodes' counters of the round ({} for a model
        without ``Model.apply_train``)."""
        with jax.named_scope("murmura.train"):
            if model.apply_train is not None:
                return local_training_by_node(params, d, train_mask, key)
            return local_training(params, d, train_mask, key, round_idx), {}

    # ---- evaluation (node.py:111-196) ------------------------------------
    def evaluate(params, x, y, mask):  # murmura: traced
        s = x.shape[1]
        chunk = min(eval_chunk, s)
        n_chunks = -(-s // chunk)
        pad = n_chunks * chunk - s
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            y = jnp.pad(y, [(0, 0), (0, pad)] + [(0, 0)] * (y.ndim - 2))
            mask = jnp.pad(mask, [(0, 0), (0, pad)])

        def chunk_rows(outputs, yc, mc):  # one node's chunk
            cnt = mc.sum()
            if yc.ndim == 2:
                # One target a position: a sample's loss and accuracy are
                # the means over its positions.
                loss, acc = masked_next_token_cross_entropy(outputs, yc, mc)
                return {"loss": loss * cnt, "correct": acc * cnt, "count": cnt}
            if evidential:
                unc = uncertainty_metrics(outputs)
                probs = unc["probs"]
                nll = -jnp.log(
                    jnp.take_along_axis(probs, yc[:, None], axis=-1)[:, 0] + 1e-10
                )
                return {
                    "loss": (nll * mc).sum(),
                    "correct": (
                        (jnp.argmax(outputs, -1) == yc).astype(jnp.float32) * mc
                    ).sum(),
                    "vacuity": (unc["vacuity"] * mc).sum(),
                    "entropy": (unc["entropy"] * mc).sum(),
                    "strength": (unc["strength"] * mc).sum(),
                    "count": cnt,
                }
            logp = jax.nn.log_softmax(outputs, -1)
            nll = -jnp.take_along_axis(logp, yc[:, None], axis=-1)[:, 0]
            return {
                "loss": (nll * mc).sum(),
                "correct": (
                    (jnp.argmax(outputs, -1) == yc).astype(jnp.float32) * mc
                ).sum(),
                "count": cnt,
            }

        def chunk_body(carry, sl):
            xc, yc, mc = (
                jax.lax.dynamic_slice_in_dim(a, sl * chunk, chunk, 1)
                for a in (x, y, mask)
            )
            outputs = stacked_apply(params, xc)  # [N, chunk, K]
            return carry, jax.vmap(chunk_rows)(outputs, yc, mc)

        _, rows = jax.lax.scan(chunk_body, 0, jnp.arange(n_chunks))  # [chunks, N]
        total = jnp.maximum(rows["count"].sum(0), 1.0)
        out = {k: v.sum(0) / total for k, v in rows.items() if k != "count"}
        out["accuracy"] = out.pop("correct")
        return out

    # ---- the round --------------------------------------------------------
    ctx = AggContext(
        apply_fn=model.apply,
        unravel=unravel,
        evidential=evidential,
        num_classes=num_classes,
        total_rounds=total_rounds,
        node_axis_sharded=node_axis_sharded,
        audit=audit_taps,
    )

    attack_apply = attack.apply if attack is not None else None
    claims_fn = attack.claims_fn if attack is not None else None

    if faults is not None and faults.nan_inject_nodes:
        _inject_rows = np.zeros(n, dtype=np.float32)
        _inject_rows[list(faults.nan_inject_nodes)] = 1.0
    else:
        _inject_rows = None

    # Whether rules with quantized exchange kernels receive the Int8Blocks
    # payload itself.  Both the stale fold and the pipeline buffer carry
    # ONE decoded [N, P] row per sender (a fresh/stale row mix — or a
    # buffered one — cannot be expressed inside one Int8Blocks payload),
    # so either layer forces the receiver-side dequantized path: wire
    # bytes are unchanged (the codec still runs, EF still telescopes) but
    # the MUR700 s8-collective property is a stale-off AND pipeline-off
    # contract (docs/PERFORMANCE.md).
    quantized_payload = (
        agg.quantized_exchange and stale_fold is None and not pipeline
    )

    def _produce_exchange(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
        """Steps 1-2d of the round: local training, the broadcast with
        attack + sentinel scrubs, the codec, and the stale fold — the
        *production* of one round's exchange, shared verbatim by the
        serialized and pipelined bodies (and, via ``train_flat``, the
        delayed-averaging reference) so the three cannot drift.

        Returns a dict with the trained ``params`` pytree, the
        post-scrub ``own_flat``/``bcast``/``adj`` triple exactly as the
        serialized aggregation would consume it, the quarantine
        bookkeeping (``pre_flat``/``finite``), the updated ``agg_state``
        (codec/stale keys), the per-stage stats dicts, and the adaptive
        attack's consumed state.
        """
        train_key, attack_key = jax.random.split(key)
        honest = 1.0 - compromised

        # 1. local training (compromised nodes frozen — network.py:99-101 —
        # except under data-poisoning attacks, whose compromised nodes
        # must train on their poisoned shards; Attack.trains_locally)
        if attack is not None and attack.trains_locally:
            train_mask = jnp.ones_like(honest)
        else:
            train_mask = honest
        if alive is not None:
            # Dead nodes freeze via the update mask, exactly like
            # compromised ones; pre-round snapshot for quarantine rollback
            # and the dead-node param freeze below.  The adjacency is
            # re-masked by alive IN-JIT even though the orchestrator's
            # masked_adjacency already folds it host-side (idempotent:
            # alive*alive == alive) — the program must not depend on a
            # two-sources-of-truth contract between its adj and alive
            # inputs to keep dead nodes out of the exchange.  (Sparse
            # exchange mode runs the same fold in [k, N] edge-mask space.)
            adj = _edges_mask_both(adj, alive)
            train_mask = train_mask * alive
            with jax.named_scope("murmura.flatten"):
                pre_flat = constrain_flat(jax.vmap(ravel)(params))
        # named_scope brackets label the `# murmura: traced` phases in
        # profiler traces (xprof/perfetto op names; the device rows that
        # the orchestrator's host spans, telemetry/host_spans.py, lie
        # beside) — metadata only, the lowered program is identical (the
        # telemetry-off byte-identity contract, tests/test_telemetry.py).
        # murmura.flatten names the parameter tree's way to [N, P] and
        # back, which belongs to no stage: it first occurs before
        # murmura.train in a faulted program and after it otherwise, so
        # it is no levers.STAGE_ORDER label (docs/OBSERVABILITY.md "Host
        # spans and device scopes").
        params, train_stats = train_nodes(
            params, d, train_mask, train_key, round_idx
        )

        # 2. snapshot + attack on outgoing states (network.py:105-119).
        # constrain_flat pins the [N, P] tensors to ("nodes", "param")
        # when a param-sharded mesh scope is active (parallel/mesh.py) —
        # identity otherwise, so unsharded programs are byte-identical.
        with jax.named_scope("murmura.flatten"):
            own_flat = constrain_flat(jax.vmap(ravel)(params))
        fault_stats = {}
        if _inject_rows is not None:
            # Deterministic divergence injection (chaos testing): scheduled
            # nodes emit a NaN update from the configured round on.
            inject = _inject_rows * (
                round_idx >= faults.nan_inject_from_round
            ).astype(jnp.float32)
            own_flat = jnp.where(
                inject[:, None] > 0, jnp.full_like(own_flat, jnp.nan), own_flat
            )
        if faults is not None and faults.nan_quarantine:
            # Numerical sentinel: a non-finite update quarantines the node
            # for the round.  Its row is REPLACED (not just masked) before
            # any rule math — masked aggregation alone cannot contain a
            # NaN row because 0 * nan == nan in every Gram/matmul path —
            # and its exchange edges are zeroed both ways.  The
            # where-style replacement here (and the attack-scrub stage
            # below) is a STATIC contract: `murmura check --flow` MUR803
            # interval-analyzes this faulted round program with
            # divergence-capable seeds and fails if non-finiteness can
            # reach the output params — switching either scrub back to a
            # multiplicative mask fails the check, not just the runtime.
            finite = jnp.isfinite(own_flat).all(axis=1)
            alive_f = alive if alive is not None else jnp.ones_like(compromised)
            fault_stats["quarantined"] = (
                (1.0 - finite.astype(jnp.float32)) * alive_f
            ).sum()
            if audit_taps:
                # Per-node quarantine flags (telemetry.audit_taps): WHICH
                # node diverged, not just how many — elementwise over
                # node-local rows, so no collectives are added (MUR400).
                fault_stats["tap_quarantined"] = (
                    1.0 - finite.astype(jnp.float32)
                ) * alive_f
            own_flat = jnp.where(finite[:, None], own_flat, pre_flat)
            fin = finite.astype(adj.dtype)
            adj = _edges_mask_both(adj, fin)
        else:
            finite = None
        bcast_finite = None
        attack_state = None
        if attack_apply is not None:
            # Cast back: float32 attack noise must not promote the exchanged
            # [N, P] tensor when params are stored bfloat16 (tpu.param_dtype).
            with jax.named_scope("murmura.exchange"):
                if adaptive:
                    # Closed-loop attack: last round's adaptation state
                    # (carried in agg_state under ATTACK_STATE_KEYS — the
                    # feedback update below writes the next round's) sets
                    # this round's strength per compromised row.
                    attack_state = {
                        k: agg_state[k] for k in attack.state_keys
                    }
                    bcast = attack.apply_adaptive(
                        own_flat, compromised, attack_key, round_idx,
                        attack_state,
                    ).astype(own_flat.dtype)
                else:
                    bcast = attack_apply(
                        own_flat, compromised, attack_key, round_idx
                    ).astype(own_flat.dtype)
            if "attack_scale" in hp_inputs:
                # Per-member attack intensity (gang sweeps): scale the
                # perturbation the attack added to the broadcast.  For
                # additive attacks (gaussian/directed/alie/ipm noise or
                # deviation terms) this is the attack's own magnitude
                # knob; scale 0 turns the member's attack off.  Placed
                # BEFORE the sentinel scrub so an amplified-to-inf
                # perturbation is still contained.
                scale = d["hp_attack_scale"].astype(jnp.float32)
                bcast = (
                    own_flat.astype(jnp.float32)
                    + scale * (bcast - own_flat).astype(jnp.float32)
                ).astype(own_flat.dtype)
            if finite is not None:
                # Second sentinel stage: the pre-training check cannot see
                # an ATTACK that overflows to inf/NaN (huge noise_std,
                # crafted states).  Mask such broadcast rows out of
                # everyone's exchange and replace them with the sender's
                # (already-scrubbed) own state so no rule math sees a
                # non-finite row.  No rollback: the sender's own params
                # are untouched by its broadcast.  Counted separately from
                # `quarantined` (which implies a rollback) so the
                # containment is visible in history, not silent.
                bfin = jnp.isfinite(bcast).all(axis=1)
                bcast_finite = bfin
                bcast = jnp.where(bfin[:, None], bcast, own_flat)
                adj = _edges_mask_sender(adj, bfin.astype(adj.dtype))
                fault_stats["attack_scrubbed"] = (
                    1.0 - bfin.astype(jnp.float32)
                ).sum()
                if audit_taps:
                    fault_stats["tap_attack_scrubbed"] = 1.0 - bfin.astype(
                        jnp.float32
                    )
        else:
            bcast = own_flat

        # 2c. compressed exchange (ops/compress.py; docs/PERFORMANCE.md):
        # the outgoing broadcast — post-attack, post-sentinel, so the codec
        # only ever sees finite values — is quantized in-jit; the rule
        # receives either the int8 payload (rules whose exchange kernels
        # move compressed data, AggregatorDef.quantized_exchange) or the
        # receiver-side dequantized tensor.  Error-feedback residual and
        # the top-k reference estimate ride ``agg_state`` (same shapes and
        # dtypes every round: donation-clean, recompile-free — MUR701/702).
        compress_stats = {}
        if compression is not None:
            with jax.named_scope("murmura.compress"):
                # With staleness (or the pipeline buffer) armed the rule
                # consumes the receiver-side dequantized tensor even for
                # quantized_exchange rules — see the quantized_payload
                # comment above.
                bcast, _decoded, comp_updates, compress_stats = (
                    compress_exchange(
                        compression, bcast, agg_state, quantized_payload,
                    )
                )
            agg_state = {**agg_state, **comp_updates}

        # 2d. bounded-staleness fold (core/stale.py; docs/ROBUSTNESS.md):
        # between scrub and aggregation, disrupted senders' base-graph
        # edges are re-added with the cached payload while its age stays
        # within the bound.  scrub_ok taint-kills a caught row's cached
        # copy for the round (MUR1103) — quarantine and attack-scrub
        # apply to cached rows exactly as to fresh ones.
        stale_stats = {}
        if stale_fold is not None:
            with jax.named_scope("murmura.stale"):
                scrub_ok = jnp.ones_like(compromised)
                if finite is not None:
                    scrub_ok = scrub_ok * finite.astype(jnp.float32)
                if bcast_finite is not None:
                    scrub_ok = scrub_ok * bcast_finite.astype(jnp.float32)
                # Receiver eligibility mirrors the fresh-exchange folds:
                # dead receivers (alive) and quarantined ones (finite —
                # _edges_mask_both zeroed their edges BOTH ways) get no
                # re-added stale in-edges.  bcast_finite does NOT gate
                # the receiver side: an attack-scrubbed sender still
                # aggregates normally (_edges_mask_sender).
                recv_ok = (
                    alive if alive is not None
                    else jnp.ones_like(compromised)
                )
                if finite is not None:
                    recv_ok = recv_ok * finite.astype(jnp.float32)
                bcast, adj, stale_updates, stale_stats = stale_fold(
                    bcast, adj,
                    {k: agg_state[k] for k in STALE_STATE_KEYS},
                    recv_ok, scrub_ok,
                )
            agg_state = {**agg_state, **stale_updates}

        return {
            "params": params,
            "own_flat": own_flat,
            "bcast": constrain_flat(bcast),
            "adj": adj,
            "pre_flat": pre_flat if alive is not None else None,
            "finite": finite,
            "agg_state": agg_state,
            "attack_state": attack_state,
            "fault_stats": fault_stats,
            "compress_stats": compress_stats,
            "stale_stats": stale_stats,
            "train_stats": train_stats,
        }

    def _step_ctx(d) -> AggContext:  # murmura: traced
        return AggContext(
            apply_fn=ctx.apply_fn,
            unravel=ctx.unravel,
            probe_x=d["probe_x"],
            probe_y=d["probe_y"],
            probe_mask=d["probe_mask"],
            evidential=ctx.evidential,
            num_classes=ctx.num_classes,
            total_rounds=ctx.total_rounds,
            node_axis_sharded=ctx.node_axis_sharded,
            audit=ctx.audit,
        )

    def _round_body(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
        prod = _produce_exchange(
            params, agg_state, key, adj, compromised, alive, round_idx, d
        )
        params = prod["params"]
        own_flat = prod["own_flat"]
        bcast = prod["bcast"]
        adj = prod["adj"]
        pre_flat = prod["pre_flat"]
        finite = prod["finite"]
        agg_state = prod["agg_state"]
        attack_state = prod["attack_state"]
        fault_stats = prod["fault_stats"]
        compress_stats = prod["compress_stats"]
        stale_stats = prod["stale_stats"]

        step_ctx = _step_ctx(d)

        # 2b. DMTT: claim exchange + trust update gate the exchange mask
        # (murmura/dmtt/node_process.py:187-241).  The N x N probe cross-eval
        # is computed once here and shared with probe-based aggregation rules
        # via ctx.probe_cross.
        dmtt_stats = {}
        if dmtt is not None:
            if claims_fn is not None:
                claims = claims_fn(adj, compromised)
            else:
                claims = adj
            cross = pairwise_probe_eval(
                bcast, step_ctx, combined_probe_metric(evidential)
            )
            exchange, dmtt_state, dmtt_stats = dmtt_round_update(
                {k: agg_state[k] for k in DMTT_STATE_KEYS},
                adj,
                claims,
                cross["accuracy"],
                cross["vacuity"],
                dmtt,
            )
            agg_state = {**agg_state, **dmtt_state}
            adj = exchange
            step_ctx = dataclasses.replace(step_ctx, probe_cross=cross)

        # 3. adjacency-masked aggregation (network.py:121-139)
        reserved = set(DMTT_STATE_KEYS) | set(COMPRESS_STATE_KEYS)
        if stale_fold is not None:
            reserved |= set(STALE_STATE_KEYS)
        if adaptive:
            reserved |= set(attack.state_keys)
        rule_state = {
            k: v for k, v in agg_state.items() if k not in reserved
        }
        with jax.named_scope("murmura.aggregate"):
            new_flat, rule_state, agg_stats = agg.aggregate(
                own_flat, bcast, adj, round_idx, rule_state, step_ctx
            )
        new_flat = constrain_flat(new_flat)
        agg_state = {**agg_state, **rule_state}

        # 3b. adaptive-attack feedback (attacks/adaptive.py): the attacker
        # reads the acceptance taps the rule just emitted for its own rows
        # (scrub/quarantine flags fold in as rejections; dead rows are not
        # observations) and writes the next round's strength back into its
        # ATTACK_STATE_KEYS slice of agg_state.  Everything is elementwise
        # over node-local rows — the feedback path adds no collectives and
        # no recompiles (MUR1001/1002, analysis/adaptive.py).
        attack_round_stats = {}
        if adaptive:
            accept, observed = acceptance_feedback(
                agg_stats, fault_stats, _in_degree(adj), alive
            )
            attack_state = attack.update_attack_state(
                attack_state, accept, observed, compromised
            )
            agg_state = {**agg_state, **attack_state}
            attack_round_stats = dict(
                attack.strength_stats(attack_state, compromised)
            )
            attack_round_stats["atk_accept"] = accept * compromised

        if alive is not None:
            # Zero alive neighbors (everyone crashed/dropped/straggled)
            # degrades to self-model — some rules divide by degree and
            # jnp.where cleanly discards whatever they produced there.
            deg = _in_degree(adj)
            new_flat = jnp.where((deg > 0)[:, None], new_flat, own_flat)
            # Dead nodes' params freeze at the pre-round value (their
            # process is gone; nothing may advance) and quarantined nodes
            # roll back their divergent local step.
            keep = alive > 0
            if finite is not None:
                keep = keep & finite
            new_flat = jnp.where(keep[:, None], new_flat, pre_flat)
            fault_stats["alive"] = alive.sum()
            if audit_taps:
                fault_stats["tap_alive"] = alive
        with jax.named_scope("murmura.flatten"):
            params = jax.vmap(unravel)(new_flat)

        metrics = {f"agg_{k}": v for k, v in agg_stats.items()}
        metrics.update({f"agg_{k}": v for k, v in dmtt_stats.items()})
        metrics.update({f"agg_{k}": v for k, v in fault_stats.items()})
        metrics.update({f"agg_{k}": v for k, v in compress_stats.items()})
        metrics.update({f"agg_{k}": v for k, v in stale_stats.items()})
        metrics.update({f"agg_{k}": v for k, v in attack_round_stats.items()})
        metrics.update({f"agg_{k}": v for k, v in prod["train_stats"].items()})
        return params, agg_state, metrics

    # Reserved agg_state keys a pipelined aggregation must never hand to
    # the rule (the serialized body's ``reserved`` plus the pipeline's
    # own buffer keys; dmtt/adaptive were rejected above).
    pipe_keys = pipeline_state_keys(stale=staleness is not None)
    pipe_reserved = (
        set(COMPRESS_STATE_KEYS) | set(pipe_keys)
    )
    if stale_fold is not None:
        pipe_reserved |= set(STALE_STATE_KEYS)

    def _round_body_pipelined(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
        """One pipelined round (core/pipeline.py; docs/PERFORMANCE.md
        "Pipelined rounds"): stage A aggregates the BUFFERED round-(r-1)
        exchange, stage B produces round r's exchange (training included)
        with no data dependence on stage A, and stage C applies the
        delayed displacement and swaps the buffer.  Stage A is issued
        first so its collectives on the buffered tensor precede the
        training scan in program order — XLA's async dispatch can overlap
        them with the training matmuls (the tentpole's point)."""
        # ---- stage A: delayed aggregation of the buffered exchange ----
        valid = agg_state[PIPE_VALID_KEY]
        buf_own = agg_state[PIPE_OWN_KEY]
        if stale_fold is not None:
            # Buffer reuse (core/stale.py): after round r-1 the stale
            # fold's payload cache holds exactly the post-fold broadcast
            # the delayed aggregation must consume — read it instead of
            # carrying a duplicate [N, P] buffer.  Read BEFORE stage B
            # advances the cache to round r's payload.
            buf_bcast = agg_state[STALE_CACHE_KEY].astype(buf_own.dtype)
        else:
            buf_bcast = agg_state[PIPE_BCAST_KEY]
        buf_adj = agg_state[PIPE_ADJ_KEY]
        if sparse:
            # Stored node-leading [N, k] for mesh placement
            # (init_pipeline_state); the rules consume [k, N].
            buf_adj = buf_adj.T
        rule_state = {
            k: v for k, v in agg_state.items() if k not in pipe_reserved
        }
        step_ctx = _step_ctx(d)
        with jax.named_scope("murmura.aggregate"):
            # The buffered exchange belongs to round r-1; rules with
            # round schedules (BALANCE tightening, trust annealing) see
            # the round the payload was produced in.  Round 0's buffer
            # is the invalid placeholder — clamped index, output and
            # rule-state update all where-discarded below.
            agg_ridx = jnp.maximum(round_idx - 1.0, 0.0)
            agg_out, rule_state_new, agg_stats = agg.aggregate(
                buf_own, buf_bcast, buf_adj, agg_ridx, rule_state, step_ctx
            )
            agg_out = constrain_flat(agg_out)
        if alive is not None:
            # The serialized zero-alive-neighbor guard, applied at the
            # buffered graph (a sender-isolated receiver at round r-1
            # degrades to self-model there, exactly as the serialized
            # round r-1 would have).
            deg_b = _in_degree(buf_adj)
            agg_out = jnp.where((deg_b > 0)[:, None], agg_out, buf_own)
        # The displacement the serialized round r-1 would have applied.
        # where, not multiply: a hypothetical non-finite value in the
        # warm-up placeholder aggregation must be DISCARDED, not scaled
        # (0 * inf == nan — the fault sentinels' static-scrub contract).
        disp = jnp.where(
            valid > 0, agg_out - buf_own, jnp.zeros_like(buf_own)
        )
        # Warm-up exactness for carried rule state too: the round-0
        # placeholder aggregation must not write trust/threshold state.
        rule_state = {
            k: (
                jnp.where(valid > 0, v, rule_state[k])
                if k in rule_state else v
            )
            for k, v in rule_state_new.items()
        }

        # ---- stage B: production of round r's exchange ----------------
        prod = _produce_exchange(
            params, agg_state, key, adj, compromised, alive, round_idx, d
        )
        own_flat = prod["own_flat"]
        pre_flat = prod["pre_flat"]
        finite = prod["finite"]
        agg_state = prod["agg_state"]
        fault_stats = prod["fault_stats"]

        # ---- stage C: combine + buffer swap ---------------------------
        with jax.named_scope("murmura.pipeline"):
            new_flat = own_flat + disp.astype(own_flat.dtype)
            if alive is not None:
                # Dead nodes freeze and quarantined nodes roll back —
                # own_flat already equals pre_flat on those rows, so the
                # keep-mask reduces to discarding the delayed
                # displacement (mirrored bit-for-bit by
                # core/pipeline.run_delayed_reference).
                keep = alive > 0
                if finite is not None:
                    keep = keep & finite
                new_flat = jnp.where(keep[:, None], new_flat, pre_flat)
                fault_stats["alive"] = alive.sum()
                if audit_taps:
                    fault_stats["tap_alive"] = alive
            with jax.named_scope("murmura.flatten"):
                params = jax.vmap(unravel)(new_flat)
        buffer_updates = {
            PIPE_OWN_KEY: own_flat,
            PIPE_ADJ_KEY: prod["adj"].T if sparse else prod["adj"],
            PIPE_VALID_KEY: jnp.ones_like(valid),
        }
        if stale_fold is None:
            buffer_updates[PIPE_BCAST_KEY] = prod["bcast"]
        agg_state = {**agg_state, **rule_state, **buffer_updates}

        metrics = {f"agg_{k}": v for k, v in agg_stats.items()}
        metrics.update({f"agg_{k}": v for k, v in fault_stats.items()})
        metrics.update(
            {f"agg_{k}": v for k, v in prod["compress_stats"].items()}
        )
        metrics.update(
            {f"agg_{k}": v for k, v in prod["stale_stats"].items()}
        )
        metrics.update({f"agg_{k}": v for k, v in prod["train_stats"].items()})
        # 0.0 on the warm-up round: this round's agg_* stats describe
        # the invalid placeholder aggregation, not a real exchange.
        metrics["agg_pipe_valid"] = valid
        return params, agg_state, metrics

    def _round_body_by_leaf(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
        """The round of a large state under a rule that can take it leaf by
        leaf (``by_leaf`` above): local training, then the rule on each
        stacked leaf as it lies, [N, ...] (no reshape: on a TPU that is a
        relayout of the leaf).  What it gives is
        ``_round_body``'s state; what it never builds is the [N, P] row
        and the copies of it the exchange keeps side by side."""
        train_key, _ = jax.random.split(key)
        params, train_stats = train_nodes(
            params, d, 1.0 - compromised, train_key, round_idx
        )
        step_ctx, agg_stats = _step_ctx(d), {}

        def mixed(leaf):
            with jax.named_scope("murmura.aggregate"):
                new, _, stats = agg.aggregate(
                    leaf, leaf, adj, round_idx, {}, step_ctx
                )
            agg_stats.update(stats)
            return new

        params = jax.tree_util.tree_map(mixed, params)
        metrics = {f"agg_{k}": v for k, v in agg_stats.items()}
        metrics.update({f"agg_{k}": v for k, v in train_stats.items()})
        return params, agg_state, metrics

    body = _round_body_pipelined if pipeline else _round_body
    if by_leaf:
        body = _round_body_by_leaf
    if faults is None:
        def train_round(params, agg_state, key, adj, compromised, round_idx, d):  # murmura: traced
            return body(
                params, agg_state, key, adj, compromised, None, round_idx, d
            )

        def train_flat(params, agg_state, key, adj, compromised, round_idx, d):  # murmura: traced
            prod = _produce_exchange(
                params, agg_state, key, adj, compromised, None, round_idx, d
            )
            ok = (
                prod["finite"].astype(jnp.float32)
                if prod["finite"] is not None
                else jnp.ones_like(compromised)
            )
            return prod["own_flat"], ok
    else:
        def train_round(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
            return body(
                params, agg_state, key, adj, compromised, alive, round_idx, d
            )

        def train_flat(params, agg_state, key, adj, compromised, alive, round_idx, d):  # murmura: traced
            prod = _produce_exchange(
                params, agg_state, key, adj, compromised, alive, round_idx, d
            )
            ok = (
                prod["finite"].astype(jnp.float32)
                if prod["finite"] is not None
                else jnp.ones_like(compromised)
            )
            return prod["own_flat"], ok

    def eval_step(params, d):  # murmura: traced
        # evaluation (network.py:141-199) — held-out arrays when the data
        # loader provided them (eval_arrays), else the training shard.
        with jax.named_scope("murmura.eval"):
            return evaluate(params, d["eval_x"], d["eval_y"], d["eval_mask"])

    init_agg_state = {
        k: np.asarray(v) for k, v in agg.init_state(n).items()
    }
    if dmtt is not None:
        init_agg_state.update(
            {k: np.asarray(v) for k, v in init_dmtt_state(n).items()}
        )
    if compression is not None:
        # Error-feedback residual (zeros) and/or the top-k reference
        # estimate, which adopts the protocol-known initial broadcast (a
        # real deployment sends full states once at setup) so round 0's
        # delta is already sparse.  Stored in the resident param dtype —
        # both shapes are [N, P] and round-stable, so donation aliases hold.
        clash = set(COMPRESS_STATE_KEYS) & set(init_agg_state)
        if clash:
            raise ValueError(
                f"aggregator '{agg.name}' carries state keys {sorted(clash)}"
                " reserved for the compressed exchange"
            )
        init_flat = np.asarray(jax.vmap(ravel)(init_params))
        init_agg_state.update(
            init_compress_state(compression, init_flat, init_flat.dtype)
        )
    if staleness is not None:
        # The payload cache + age stamps ride agg_state under the
        # reserved STALE_STATE_KEYS slice — same [N, P]/[N] shapes and
        # dtypes every round, so the scan carry, gang vmap, donation
        # aliases and durability snapshots all hold without special
        # cases (the COMPRESS_STATE_KEYS story).
        clash = set(STALE_STATE_KEYS) & set(init_agg_state)
        if clash:
            raise ValueError(
                f"aggregator '{agg.name}' carries state keys "
                f"{sorted(clash)} reserved for the bounded-staleness "
                "exchange"
            )
        leaf = jax.tree_util.tree_leaves(init_params)[0]
        # flat_dim, not model_dim: the cache row must match the (padded)
        # exchanged width so it shards over "param" with the broadcast.
        init_agg_state.update(
            init_stale_state(staleness, n, flat_dim, leaf.dtype)
        )
    if adaptive:
        # Adaptation state rides agg_state under the attack's reserved
        # ATTACK_STATE_KEYS slice — same shapes/dtypes every round, so the
        # scan carry, gang vmap, donation aliases and durability snapshots
        # all hold without special cases (the COMPRESS_STATE_KEYS story).
        clash = set(attack.state_keys) & set(init_agg_state)
        if clash:
            raise ValueError(
                f"aggregator '{agg.name}' carries state keys "
                f"{sorted(clash)} reserved for the adaptive attack"
            )
        init_agg_state.update(
            {
                k: np.asarray(v)
                for k, v in attack.init_attack_state(n).items()
            }
        )
    if pipeline:
        # The double-buffered pipeline stage rides agg_state under the
        # reserved PIPELINE_STATE_KEYS slice — same shapes/dtypes every
        # round, so the scan carry, gang vmap, donation aliases and
        # durability snapshots all hold without special cases (the
        # COMPRESS/STALE_STATE_KEYS story).  With staleness armed the
        # broadcast buffer is the stale cache (buffer reuse —
        # core/pipeline.pipeline_state_keys).
        clash = set(pipe_keys) & set(init_agg_state)
        if clash:
            raise ValueError(
                f"aggregator '{agg.name}' carries state keys "
                f"{sorted(clash)} reserved for the pipelined exchange"
            )
        leaf = jax.tree_util.tree_leaves(init_params)[0]
        init_agg_state.update(
            init_pipeline_state(
                n, flat_dim, leaf.dtype,
                sparse_offsets=sparse_offsets,
                stale=staleness is not None,
            )
        )

    return RoundProgram(
        train_step=train_round,
        eval_step=eval_step,
        init_params=init_params,
        init_agg_state=init_agg_state,
        data_arrays=data_arrays,
        num_nodes=n,
        model_dim=model_dim,
        evidential=evidential,
        faulted=faults is not None,
        hp_inputs=hp_inputs,
        sparse_offsets=sparse_offsets,
        compression=compression,
        adaptive_attack=adaptive,
        staleness=staleness,
        pipelined=pipeline,
        train_flat=train_flat,
        param_shards=param_shards,
        flat_dim=flat_dim,
    )


def build_multi_round(program: RoundProgram, chunk: int, eval_every: int):
    """Fuse ``chunk`` FL rounds into one ``lax.scan`` program.

    The SURVEY §7 end state: the round loop itself lives on the device and
    metrics come back as device-resident history arrays after the scan —
    one dispatch per ``chunk`` rounds instead of per round.  Evaluation runs
    under ``lax.cond`` only on rounds where ``(round + 1) % eval_every == 0``
    (cond executes a single branch, so skipped rounds pay zero eval FLOPs,
    same as the separately-dispatched path).

    Returns a function
        (params, agg_state, base_key, adj_stack[chunk, N, N], compromised,
         round0, data) -> (params', agg_state', rows)
    where ``rows`` is a [chunk, ...] metrics pytree: per-round ``agg_*``
    stats, eval metrics (zeros on unevaluated rounds), and an ``evaluated``
    flag the orchestrator uses to select history rows.  ``adj_stack`` holds
    the per-round adjacency (host-computed G^t for mobility; the static mask
    tiled otherwise); per-round RNG is ``fold_in(base_key, round)`` so a
    fused run consumes the same independent streams regardless of chunking.

    Faulted programs (``program.faulted``) additionally take a per-round
    ``alive_stack`` [chunk, N] after ``compromised`` — the fault-schedule
    twin of ``adj_stack``, riding the same scan xs.
    """
    as_struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    eval_struct = jax.eval_shape(
        program.eval_step,
        jax.tree_util.tree_map(as_struct, program.init_params),
        {k: as_struct(v) for k, v in program.data_arrays.items()},
    )

    def _body(carry, i, adj, alive, compromised, base_key, round0, data):
        params, agg_state = carry
        r = round0 + i
        key = jax.random.fold_in(base_key, r)
        step_args = [params, agg_state, key, adj, compromised]
        if alive is not None:
            step_args.append(alive)
        params, agg_state, m = program.train_step(
            *step_args, r.astype(jnp.float32), data,
        )
        do_eval = (r + 1) % eval_every == 0
        ev = jax.lax.cond(
            do_eval,
            lambda p: program.eval_step(p, data),
            lambda p: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), eval_struct
            ),
            params,
        )
        rows = {**m, **ev, "evaluated": do_eval}
        return (params, agg_state), rows

    if program.faulted:
        def multi_round(params, agg_state, base_key, adj_stack, compromised, alive_stack, round0, data):  # murmura: traced
            def body(carry, xs):
                i, adj, alive = xs
                return _body(
                    carry, i, adj, alive, compromised, base_key, round0, data
                )

            (params, agg_state), rows = jax.lax.scan(
                body, (params, agg_state),
                (jnp.arange(chunk), adj_stack, alive_stack),
            )
            return params, agg_state, rows
    else:
        def multi_round(params, agg_state, base_key, adj_stack, compromised, round0, data):  # murmura: traced
            def body(carry, xs):
                i, adj = xs
                return _body(
                    carry, i, adj, None, compromised, base_key, round0, data
                )

            (params, agg_state), rows = jax.lax.scan(
                body, (params, agg_state), (jnp.arange(chunk), adj_stack)
            )
            return params, agg_state, rows

    return multi_round
