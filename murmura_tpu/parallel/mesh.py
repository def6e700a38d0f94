"""Node-axis sharding over a device mesh — the ``backend: tpu`` engine.

This is the TPU-native replacement for the reference's entire distributed
communication backend (murmura/distributed/: ZeroMQ PUSH/PULL sockets,
torch.save serialization, wall-clock round sync — node_process.py:193-276):
the stacked network state's leading ``nodes`` axis is sharded over a 1-D
``jax.sharding.Mesh``, the round step is jitted global-view, and XLA lowers
the neighbor exchange (every ``adj @ bcast`` / gathered [N, P] read in the
aggregation rules) into all-gather/reduce collectives over ICI.  No sockets,
no serialization, no deadlines — the collective IS the synchronization.

Multi-host scale-out: the same program runs under ``jax.distributed`` with a
mesh spanning hosts; XLA routes intra-slice traffic over ICI and cross-slice
traffic over DCN.  Tested virtually via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see tests/ and
__graft_entry__.dryrun_multichip).
"""

import contextlib
import functools
from typing import Any, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def backend_initialized() -> bool:
    """Whether an XLA backend already exists in this process — checked
    WITHOUT creating one (``jax.devices()`` would).

    This is the runtime twin of the MUR005 lint rule (analysis/lint.py):
    module-import-time ``jnp.*`` work initializes the backend before
    :func:`init_multihost` can pin the platform/topology, and the resulting
    jax.distributed failure modes are far less legible than failing here.
    """
    from jax._src import xla_bridge

    # The helper jax.distributed itself uses (private: jax._src has no
    # stability guarantee; MUR005 remains the static line of defense).
    return bool(xla_bridge.backends_are_initialized())


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join a multi-host JAX run (tpu.multihost: true).

    After this, ``jax.devices()`` spans every host of the slice and the same
    jitted round program runs SPMD with XLA routing intra-slice collectives
    over ICI and cross-slice over DCN. Arguments default to the standard
    JAX coordination env vars (JAX_COORDINATOR_ADDRESS etc. / TPU metadata).
    Must run before anything initializes the XLA backend; a duplicate call
    in the same process is ignored.
    """
    if jax.distributed.is_initialized():
        return
    if backend_initialized():
        raise RuntimeError(
            "init_multihost called after an XLA backend was already "
            "initialized in this process: jax.distributed cannot join a "
            "run once single-process devices exist.  Something executed a "
            "jax computation (often a module-import-time jnp.* call — the "
            "MUR005 lint class, `python -m murmura_tpu check`) before the "
            "mesh setup; move it inside a function"
        )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first ``num_devices`` devices, axis name ``nodes``."""
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    return Mesh(np.array(devices), ("nodes",))


def make_shardings(mesh: Mesh):
    """(node_sharded, replicated) NamedSharding pair for the mesh."""
    return NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())


def _shard_leading_axis(tree: Any, node_sharding, replicated) -> Any:
    """Sharding pytree: leading-axis 'nodes' on every array leaf, replicating
    scalars and rank-0 leaves."""

    def spec(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1:
            return node_sharding
        return replicated

    return jax.tree_util.tree_map(spec, tree)


# --------------------------------------------------------------------------
# Param-axis sharding (docs/PERFORMANCE.md "Param-axis sharding"): a third
# mesh axis splits the flattened parameter vector so every [N, P]-shaped
# tensor of the round (the broadcast, the stale cache and pipeline buffers,
# the EF residual / top-k reference, the aggregation output) is resident at
# N x P/shards per device — the ZeRO-style cross-replica weight-update
# sharding of arXiv:2004.13336 applied to the gossip round.  The model
# pytree itself stays node-sharded (training needs each node's full model);
# it is the flat [N, P] aggregation-side state that hits the memory wall
# first, and that is what shards here.
# --------------------------------------------------------------------------


def plan_param_layout(
    num_nodes: int, param_shards: int, n_dev: int
) -> Tuple[int, int, int]:
    """(seed, nodes, param) axis sizes for a param-sharded single-run mesh.

    Largest-dividing-factor fallback (the :func:`make_gang_mesh` policy):
    prefer the full requested ``param_shards`` on the param axis, else the
    largest divisor of it that also divides the device count while leaving
    a node axis that divides ``num_nodes``.  ``param_shards=1`` degrades to
    the plain node layout.  Raises when no factorization fits.
    """
    if param_shards < 1:
        raise ValueError(f"param_shards must be >= 1, got {param_shards}")
    for s in sorted(
        (d for d in range(1, param_shards + 1) if param_shards % d == 0),
        reverse=True,
    ):
        if n_dev % s:
            continue
        nodes_ax = n_dev // s
        if nodes_ax <= num_nodes and num_nodes % nodes_ax == 0:
            return 1, nodes_ax, s
    raise ValueError(
        f"cannot lay {num_nodes} nodes x {param_shards} param shards onto "
        f"{n_dev} devices: no (nodes, param) factorization divides both "
        "axes — adjust tpu.num_devices or tpu.param_shards"
    )


def make_param_mesh(
    num_nodes: int, param_shards: int, num_devices: Optional[int] = None
) -> Mesh:
    """3-D ("seed", "nodes", "param") mesh for a param-sharded single run.

    The seed axis is size 1 (gangs get theirs from :func:`make_gang_mesh`);
    the node and param axes factor the device count by
    :func:`plan_param_layout`.  Every P("nodes")-spec'd consumer of the
    1-D mesh works unchanged on this mesh (absent axes replicate), so the
    orchestrator's sharding helpers are layout-agnostic.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    seed_ax, nodes_ax, param_ax = plan_param_layout(
        num_nodes, param_shards, len(devices)
    )
    sel = np.array(devices[: seed_ax * nodes_ax * param_ax])
    return Mesh(
        sel.reshape(seed_ax, nodes_ax, param_ax), ("seed", "nodes", "param")
    )


def mesh_param_shards(mesh: Optional[Mesh]) -> int:
    """Size of the mesh's ``param`` axis (1 when absent or no mesh)."""
    if mesh is None:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get("param", 1))


def mesh_node_axis(mesh: Optional[Mesh]) -> int:
    """Size of the mesh's ``nodes`` axis (the whole mesh for legacy
    unnamed consumers passing a 1-D mesh)."""
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("nodes", mesh.devices.size))


def flat_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a flat [N, P] round tensor on a param-sharded mesh:
    rows over ``nodes``, columns over ``param``."""
    return NamedSharding(mesh, P("nodes", "param"))


# Trace-time ambient scope: (mesh, flat_dim) while a program jitted over a
# mesh is being traced; flat_dim is None unless the mesh shards the param
# axis.  core/rounds.py pins its [N, P] intermediates through
# :func:`constrain_flat`, aggregation/base.py aligns its P-chunk loops
# through :func:`active_param_shards`, ops/pallas_agg.py picks shard-local
# grids through :func:`active_param_scope`, and models/cnn.py keeps a group
# of the folded convolution inside one device's block of nodes through
# :func:`nodes_a_device` — one context, four consumers, zero plumbing
# through rule signatures.  Off-scope (simulation backend, one device)
# every hook is the identity, and without a flat_dim (gang vmap, shards=1)
# the three param hooks are, keeping those programs byte-identical (MUR1302).
_MESH_SCOPE: List[Tuple[Mesh, Optional[int]]] = []


@contextlib.contextmanager
def param_axis_scope(mesh: Mesh, flat_dim: Optional[int] = None):
    """Activate the trace scope (see module note above); the param-axis
    hooks engage only with a ``flat_dim``."""
    _MESH_SCOPE.append((mesh, None if flat_dim is None else int(flat_dim)))
    try:
        yield
    finally:
        _MESH_SCOPE.pop()


def _traced_on(mesh: Mesh, fn, flat_dim: Optional[int] = None):
    """``fn`` under its name, traced inside ``param_axis_scope``."""

    @functools.wraps(fn)
    def scoped(*args):  # murmura: traced
        with param_axis_scope(mesh, flat_dim):
            return fn(*args)

    return scoped


def active_param_scope() -> Optional[Tuple[Mesh, int]]:
    """(mesh, flat_dim) of the innermost active scope if it shards the
    param axis, or None."""
    if _MESH_SCOPE and _MESH_SCOPE[-1][1] is not None:
        return _MESH_SCOPE[-1]
    return None


def active_param_shards(p: Optional[int] = None) -> int:
    """Param-shard count of the active scope (1 off-scope).  With ``p``
    given, returns 1 unless the shard count divides ``p`` — callers
    slicing a [*, p] tensor must not assume shard alignment the tensor
    does not have (e.g. the int8 codec's block-padded width)."""
    scope = active_param_scope()
    if scope is None:
        return 1
    shards = mesh_param_shards(scope[0])
    if p is not None and p % shards:
        return 1
    return shards


def nodes_a_device(n: int) -> int:
    """How many of ``n`` nodes one device holds under the mesh whose
    program is being traced: its block of the node axis (``n`` off-scope;
    1 where the axis does not split evenly, which ``shard_step`` refuses)."""
    shards = mesh_node_axis(_MESH_SCOPE[-1][0]) if _MESH_SCOPE else 1
    return n // shards if n % shards == 0 else 1


def constrain_flat(x):
    """Pin a flat [N, P] round tensor to ("nodes", "param") when a
    param-axis scope is active; identity otherwise (and for any value
    whose trailing width is not the scope's flat_dim).  Traced as a no-op
    off-scope, so unsharded programs are byte-identical."""
    scope = active_param_scope()
    if scope is None:
        return x
    mesh, flat_dim = scope
    if getattr(x, "ndim", 0) == 2 and x.shape[-1] == flat_dim:
        return jax.lax.with_sharding_constraint(x, flat_sharding(mesh))
    return x


def constrain_replicated(x):
    """Pin a value REPLICATED across the active param-sharded mesh;
    identity off-scope.

    The one consumer is the round program's RNG draws (core/rounds.py
    ``local_training``): the legacy (non-partitionable) threefry lowering
    is sharding-DEPENDENT — the same key produces different uniforms when
    GSPMD partitions the output over a ("nodes", "param") mesh than on one
    device — so an unpinned draw would give every mesh layout its own
    batch permutations, breaking cross-layout comparability (and the
    shards=1-vs-sharded parity MUR1303 measures).  Replicating the draw
    keeps the bits byte-identical to the unsharded program; the arrays are
    [N, S]-scale (batch schedule), so the cost is noise next to the [N, P]
    state the param axis exists to shard."""
    scope = active_param_scope()
    if scope is None:
        return x
    mesh = scope[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def state_sharding_specs(tree: Any, mesh: Mesh, flat_dim: int) -> Any:
    """Sharding pytree for param-sharded resident state: [N, flat_dim]
    leaves split ("nodes", "param") — the stale cache, pipeline buffers,
    EF residual and top-k reference — everything else keeps the
    leading-axis ``nodes`` layout of :func:`_shard_leading_axis`."""
    node_s, repl = make_shardings(mesh)
    flat_s = flat_sharding(mesh)

    def spec(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return repl
        if leaf.ndim == 2 and leaf.shape[-1] == flat_dim:
            return flat_s
        return node_s

    return jax.tree_util.tree_map(spec, tree)


def _shard_round_fn(
    fn, program, mesh: Mesh, adj_sharding, donate: bool, alive_sharding=None
):
    """Shared jit wrapper for round-shaped programs.

    Both the per-round step and the fused multi-round scan take
    (params, agg_state, key, <adjacency>, compromised, round, data) and
    return (params, agg_state, metrics); only the adjacency argument's
    sharding differs.  Faulted programs (``program.faulted``) take an
    extra per-round alive mask after ``compromised`` whose sharding is
    supplied as ``alive_sharding`` ([N] node-sharded for the step,
    [chunk, N] second-axis-sharded for the fused scan).  Outputs:
    params/agg_state stay node-sharded; the small per-node metrics arrays
    are replicated so the orchestrator's device_get works when the mesh
    spans multiple processes (multi-host: a node-sharded output would span
    non-addressable devices).
    """
    node_ax = mesh_node_axis(mesh)
    if program.num_nodes % node_ax != 0:
        raise ValueError(
            f"num_nodes={program.num_nodes} not divisible by mesh node "
            f"axis {node_ax}"
        )
    node_s, repl = make_shardings(mesh)

    param_ax = mesh_param_shards(mesh)
    scope_dim = None
    if param_ax > 1:
        # Param-sharded layout: the program must have been built with a
        # matching shard count — its flat width is padded to a multiple of
        # program.param_shards, and the mesh axis must divide that pad.
        shards = getattr(program, "param_shards", 1)
        flat_dim = getattr(program, "flat_dim", program.model_dim)
        if shards % param_ax or flat_dim % param_ax:
            raise ValueError(
                f"mesh param axis {param_ax} does not divide the round "
                f"program's param_shards={shards} (flat width {flat_dim}) "
                "— build the program with "
                f"build_round_program(param_shards={param_ax}) (config: "
                "tpu.param_shards) so the flat pad matches the mesh"
            )
        params_s = state_sharding_specs(program.init_params, mesh, flat_dim)
        agg_s = state_sharding_specs(program.init_agg_state, mesh, flat_dim)
        # The [N, P] intermediates inside the round body (own_flat, the
        # broadcast, the aggregation output) are pinned by constrain_flat
        # at trace time: the ambient scope carries the flat width, so
        # rounds.py / aggregation kernels see the layout.
        scope_dim = flat_dim
    else:
        params_s = _shard_leading_axis(program.init_params, node_s, repl)
        agg_s = _shard_leading_axis(program.init_agg_state, node_s, repl)
    data_s = _shard_leading_axis(program.data_arrays, node_s, repl)

    in_shardings = [
        params_s,  # params
        agg_s,  # agg_state
        repl,  # rng key
        adj_sharding,  # adjacency (per-round rows or stacked)
        node_s,  # compromised mask
        repl,  # round index
        data_s,  # data dict
    ]
    if program.faulted:
        in_shardings.insert(5, alive_sharding)  # alive mask / alive stack
    return jax.jit(
        _traced_on(mesh, fn, scope_dim),
        in_shardings=tuple(in_shardings),
        out_shardings=(params_s, agg_s, repl),
        donate_argnums=(0, 1) if donate else (),
    )


def shard_step(step, program, mesh: Mesh, donate: bool = True):
    """Jit a RoundProgram step with the node axis sharded over ``mesh``.

    Args:
        step: the traced round function (params, agg_state, key, adj,
            compromised, round_idx, data) -> (params, agg_state, metrics)
            — faulted programs take an [N] alive mask after compromised.
        program: RoundProgram (for example structures to derive shardings).
        mesh: 1-D ``nodes`` mesh; program.num_nodes must be divisible by its
            size.

    Returns:
        The compiled step with in/out shardings pinned.
    """
    node_s, _ = make_shardings(mesh)
    adj_s = edge_mask_sharding(mesh) if program.sparse else node_s
    return _shard_round_fn(
        step, program, mesh, adj_s, donate, alive_sharding=node_s
    )


def adj_stack_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of the fused-dispatch adjacency stack [chunk, N, N]: sharded
    on its *second* axis (each device holds its nodes' rows for every round
    of the chunk).  Shared by :func:`shard_multi_round` and the
    orchestrator's explicit input staging (Network._stage)."""
    return NamedSharding(mesh, P(None, "nodes"))


def edge_mask_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of the sparse [k, N] per-offset edge mask
    (topology/sparse.py): the node axis is SECOND, the small static offset
    axis replicates — each device holds its nodes' columns of every offset
    row."""
    return NamedSharding(mesh, P(None, "nodes"))


def sparse_adj_stack_sharding(mesh: Mesh) -> NamedSharding:
    """Fused-dispatch sparse edge-mask stack [chunk, k, N]: node axis third."""
    return NamedSharding(mesh, P(None, None, "nodes"))


def shard_multi_round(multi_round, program, mesh: Mesh, donate: bool = True):
    """Jit a fused multi-round scan (core.rounds.build_multi_round) over
    ``mesh`` with the same node-axis layout as :func:`shard_step`.  The
    faulted alive_stack [chunk, N] shares the adj_stack's layout: sharded
    on its second (node) axis."""
    adj_s = (
        sparse_adj_stack_sharding(mesh) if program.sparse
        else adj_stack_sharding(mesh)
    )
    return _shard_round_fn(
        multi_round, program, mesh, adj_s, donate,
        alive_sharding=adj_stack_sharding(mesh),
    )


# --------------------------------------------------------------------------
# Gang-batched execution (core/gang.py): the [B] experiment axis joins the
# mesh as a second dimension.
# --------------------------------------------------------------------------


def make_gang_mesh(
    batch: int, num_nodes: int, num_devices: Optional[int] = None
) -> Mesh:
    """2-D ("seed", "nodes") mesh for a gang of ``batch`` members.

    Layout policy (ISSUE 5): **seed-major** when the whole gang fits —
    ``batch * num_nodes <= devices`` puts every (member, node) pair on its
    own device (maximum parallelism, zero per-member serialization);
    otherwise the largest seed-axis factor that divides both the device
    count and the gang, falling back to a pure node-sharded mesh with the
    seed axis replicated (size 1) — each device then holds all B members of
    its node rows, which is the right layout when N is large and B small.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    n_dev = len(devices)
    if batch * num_nodes <= n_dev:
        sel = np.array(devices[: batch * num_nodes])
        return Mesh(sel.reshape(batch, num_nodes), ("seed", "nodes"))
    for s in sorted(range(1, n_dev + 1), reverse=True):
        if n_dev % s == 0 and s <= batch and batch % s == 0:
            if num_nodes % (n_dev // s) == 0:
                return Mesh(
                    np.array(devices).reshape(s, n_dev // s),
                    ("seed", "nodes"),
                )
    raise ValueError(
        f"cannot lay a gang of {batch} members x {num_nodes} nodes onto "
        f"{n_dev} devices: no (seed, nodes) factorization divides both "
        "axes — adjust tpu.num_devices or the gang size"
    )


def plan_gang_param_layout(
    batch: int, num_nodes: int, param_shards: int, n_dev: int
) -> Tuple[int, int, int]:
    """(seed, nodes, param) axis sizes for a param-sharded GANG mesh —
    the sharding x sweep lift (ISSUE 16).

    Same largest-dividing-factor policy as :func:`plan_param_layout`:
    prefer the full requested ``param_shards`` on the param axis (else
    its largest divisor that divides the device count), then lay the
    remaining devices as a ("seed", "nodes") gang plane under the
    :func:`make_gang_mesh` policy — seed-major when the whole gang fits,
    otherwise the largest seed factor whose node remainder divides N.
    Raises when no factorization fits.
    """
    if param_shards < 1:
        raise ValueError(f"param_shards must be >= 1, got {param_shards}")
    for s in sorted(
        (d for d in range(1, param_shards + 1) if param_shards % d == 0),
        reverse=True,
    ):
        if n_dev % s:
            continue
        rem = n_dev // s
        if batch * num_nodes <= rem:
            return batch, num_nodes, s
        for g in sorted(range(1, rem + 1), reverse=True):
            if rem % g == 0 and g <= batch and batch % g == 0:
                if num_nodes % (rem // g) == 0:
                    return g, rem // g, s
    raise ValueError(
        f"cannot lay a gang of {batch} members x {num_nodes} nodes x "
        f"{param_shards} param shards onto {n_dev} devices: no "
        "(seed, nodes, param) factorization divides all three axes — "
        "adjust tpu.num_devices, tpu.param_shards or the gang size"
    )


def make_gang_param_mesh(
    batch: int,
    num_nodes: int,
    param_shards: int,
    num_devices: Optional[int] = None,
) -> Mesh:
    """3-D ("seed", "nodes", "param") mesh for a param-sharded gang —
    :func:`make_gang_mesh` composed with :func:`make_param_mesh`'s param
    role, so the gang's [S, N, P] stacked state shards its trailing flat
    axis too.  ``param_shards=1`` still yields the 3-D mesh (param axis
    size 1), keeping one code path; every P("seed", "nodes")-spec'd
    consumer works unchanged (absent/size-1 axes replicate)."""
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    seed_ax, node_ax, param_ax = plan_gang_param_layout(
        batch, num_nodes, param_shards, len(devices)
    )
    sel = np.array(devices[: seed_ax * node_ax * param_ax])
    return Mesh(
        sel.reshape(seed_ax, node_ax, param_ax), ("seed", "nodes", "param")
    )


def _shard_gang_leading(
    tree: Any, mesh: Mesh, flat_dim: Optional[int] = None
) -> Any:
    """Sharding pytree for *stacked* [B, ...] gang state: [B, N, ...]
    leaves split ("seed", "nodes"), [B] per-member leaves split ("seed",),
    rank-0 leaves replicate.  Leaves whose second axis is not the node
    axis (or not divisible by it) stay seed-sharded only.  On a
    param-sharded gang mesh (``flat_dim`` given), [B, N, flat_dim] leaves
    additionally split their trailing flat axis over ("param",)."""
    gang2d = NamedSharding(mesh, P("seed", "nodes"))
    member = NamedSharding(mesh, P("seed"))
    repl = NamedSharding(mesh, P())
    node_ax = mesh.shape["nodes"]
    param_ax = mesh_param_shards(mesh)
    gang3d = (
        NamedSharding(mesh, P("seed", "nodes", "param"))
        if param_ax > 1 else gang2d
    )

    def spec(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return repl
        if leaf.ndim >= 2 and leaf.shape[1] % node_ax == 0:
            if (
                flat_dim is not None
                and leaf.ndim == 3
                and leaf.shape[2] == flat_dim
            ):
                return gang3d
            return gang2d
        return member

    return jax.tree_util.tree_map(spec, tree)


def _gang_spec_from_template(
    tree: Any, mesh: Mesh, flat_dim: Optional[int] = None
) -> Any:
    """Sharding pytree for stacked gang inputs derived from the UNSTACKED
    per-member template (program.init_params / init_agg_state /
    data_arrays): a member leaf of rank >= 1 gains the gang axis in front
    ([B, N, ...] -> ("seed", "nodes")); a rank-0 member leaf becomes a [B]
    per-member vector (("seed",)).  On a param-sharded gang mesh
    (``flat_dim`` given), [N, flat_dim] member leaves stack to
    [B, N, flat_dim] split ("seed", "nodes", "param")."""
    gang2d = NamedSharding(mesh, P("seed", "nodes"))
    member = NamedSharding(mesh, P("seed"))
    node_ax = mesh.shape["nodes"]
    param_ax = mesh_param_shards(mesh)
    gang3d = (
        NamedSharding(mesh, P("seed", "nodes", "param"))
        if param_ax > 1 else gang2d
    )

    def spec(leaf):
        leaf = np.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] % node_ax == 0:
            if (
                flat_dim is not None
                and leaf.ndim == 2
                and leaf.shape[-1] == flat_dim
            ):
                return gang3d
            return gang2d
        return member

    return jax.tree_util.tree_map(spec, tree)


def gang_node_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of member-shared node-leading arrays (the [N, N] adjacency,
    the [N] alive mask): node rows split over the ``nodes`` axis, values
    replicated along ``seed``."""
    return NamedSharding(mesh, P("nodes"))


def gang_adj_stack_sharding(mesh: Mesh) -> NamedSharding:
    """Fused-dispatch [chunk, N, N] adjacency stack (shared across
    members): sharded on the node (second) axis, replicated along seed."""
    return NamedSharding(mesh, P(None, "nodes"))


def _shard_gang_round_fn(
    vfn, program, batch: int, mesh: Mesh, adj_sharding, donate: bool,
    alive_sharding,
):
    """Jit a vmapped round-shaped gang program with in/out shardings pinned
    — the gang twin of :func:`_shard_round_fn`.  The vmapped signature is
    the single-run one with the stacked args gaining a [B] leading axis
    (params, agg_state, keys, compromised, data) and the member-shared args
    (adjacency, alive, round index) unbatched."""
    seed_ax, node_ax = mesh.shape["seed"], mesh.shape["nodes"]
    if batch % seed_ax != 0:
        raise ValueError(
            f"gang batch={batch} not divisible by mesh seed axis {seed_ax}"
        )
    if program.num_nodes % node_ax != 0:
        raise ValueError(
            f"num_nodes={program.num_nodes} not divisible by mesh node "
            f"axis {node_ax}"
        )
    member = NamedSharding(mesh, P("seed"))
    repl = NamedSharding(mesh, P())
    gang2d = NamedSharding(mesh, P("seed", "nodes"))

    param_ax = mesh_param_shards(mesh)
    flat_dim = None
    if param_ax > 1:
        # Param-sharded gang layout (the sharding x sweep lift): the
        # member program must have been built with a matching shard
        # count, exactly as in :func:`_shard_round_fn`.  Unlike the
        # single-run path the trace scope carries NO flat width here — under the
        # gang vmap the [N, P] intermediates carry a leading member axis
        # the scope's rank-2 constraints do not expect; the jit-boundary
        # shardings pin the [B, N, P] layout and GSPMD propagates it
        # through the vmapped body.
        shards = getattr(program, "param_shards", 1)
        flat_dim = getattr(program, "flat_dim", program.model_dim)
        if shards % param_ax or flat_dim % param_ax:
            raise ValueError(
                f"gang mesh param axis {param_ax} does not divide the "
                f"round program's param_shards={shards} (flat width "
                f"{flat_dim}) — build the program with "
                f"build_round_program(param_shards={param_ax}) (config: "
                "tpu.param_shards) so the flat pad matches the mesh"
            )

    params_s = _gang_spec_from_template(program.init_params, mesh, flat_dim)
    agg_s = _gang_spec_from_template(program.init_agg_state, mesh, flat_dim)
    data_s = _gang_spec_from_template(program.data_arrays, mesh)

    in_shardings = [
        params_s,  # stacked params [B, N, ...]
        agg_s,  # stacked agg state
        member,  # per-member rng keys [B, 2]
        adj_sharding,  # shared adjacency (rows or stack)
        gang2d,  # stacked compromised masks [B, N]
        repl,  # round index
        data_s,  # stacked data dict
    ]
    if program.faulted:
        in_shardings.insert(5, alive_sharding)
    return jax.jit(
        _traced_on(mesh, vfn),
        in_shardings=tuple(in_shardings),
        out_shardings=(params_s, agg_s, repl),
        donate_argnums=(0, 1) if donate else (),
    )


def shard_gang_step(vstep, program, batch: int, mesh: Mesh, donate: bool = True):
    """Jit the vmapped per-round gang step over a ("seed", "nodes") mesh."""
    return _shard_gang_round_fn(
        vstep, program, batch, mesh, gang_node_sharding(mesh), donate,
        alive_sharding=gang_node_sharding(mesh),
    )


def shard_gang_multi_round(
    vmulti, program, batch: int, mesh: Mesh, donate: bool = True
):
    """Jit the vmapped fused gang scan; the shared [chunk, N, N] adjacency
    stack (and [chunk, N] alive stack) shard on their node axis."""
    return _shard_gang_round_fn(
        vmulti, program, batch, mesh, gang_adj_stack_sharding(mesh), donate,
        alive_sharding=gang_adj_stack_sharding(mesh),
    )


def shard_gang_eval_step(veval, program, batch: int, mesh: Mesh):
    """Jit the vmapped gang eval step; metrics replicate for the same
    multi-host device_get reason as :func:`shard_eval_step`."""
    params_s = _gang_spec_from_template(program.init_params, mesh)
    data_s = _gang_spec_from_template(program.data_arrays, mesh)
    repl = NamedSharding(mesh, P())
    return jax.jit(
        _traced_on(mesh, veval),
        in_shardings=(params_s, data_s),
        out_shardings=repl,
    )


def shard_eval_step(eval_step, program, mesh: Mesh):
    """Jit a RoundProgram eval step (params, data) -> metrics over ``mesh``.

    Compiled separately from the train step so the orchestrator only pays
    the full test-set sweep on recorded rounds (``eval_every``).  Metrics
    come out replicated for the same multi-host device_get reason as
    :func:`shard_step`.
    """
    node_s, repl = make_shardings(mesh)
    params_s = _shard_leading_axis(program.init_params, node_s, repl)
    data_s = _shard_leading_axis(program.data_arrays, node_s, repl)
    return jax.jit(
        _traced_on(mesh, eval_step),
        in_shardings=(params_s, data_s),
        out_shardings=repl,
    )


# ---------------------------------------------------------------------------
# Composition manifest (murmura_tpu/levers.py; `murmura check --compose`).
# The single source of truth for this lever's cross-feature verdicts —
# guard sites in config/schema.py and utils/factories.py cite
# refusal_reason() so user-facing messages and the analyzer's grid can
# never drift apart (MUR1400).
# ---------------------------------------------------------------------------
from murmura_tpu.levers import LeverManifest, composes, refuses

LEVER_MANIFEST = LeverManifest(
    name="sharding",
    module="murmura_tpu.parallel.mesh",
    mesh_axes=("param",),
    verdicts={
        "adaptive": composes(),
        "compression": composes(
            topk=(
                "tpu.param_shards does not compose with compression."
                "algorithm: topk (the per-row global top-k needs the "
                "full [P] row resident on one device, defeating the "
                "shard); use the int8 codec — its per-block scales "
                "shard with P"
            ),
            int8_block=(
                "a quant block straddling a shard boundary would "
                "compute its scale across shards; pick a block that "
                "divides the shard-local width"
            ),
        ),
        "dmtt": refuses(
            "tpu.param_shards does not compose with dmtt (the N x N "
            "claim cross-evaluation unravels every broadcast row into "
            "a full model per pair — there is no sharded formulation "
            "of that sweep)"
        ),
        "faults": composes(),
        "mobility": composes(),
        "pipeline": composes(),
        "population": refuses(
            "tpu.param_shards does not compose with population yet "
            "(the memmapped user bank stages full [P] rows per cohort "
            "swap; a sharded bank is ROADMAP item 5's sharded-bank "
            "leg)"
        ),
    },
)
