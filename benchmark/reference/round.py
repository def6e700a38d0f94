"""The plain reference of one decentralized-learning job: per round, every
honest node trains on its own shard (masked-batch SGD), compromised nodes
broadcast an attacked state, every node aggregates its neighbours'
broadcasts by the job's rule, and every node is evaluated.

It imports nothing of the program.  Its inputs are the cell's inputs (the
initial parameters, the data, the graph, the compromised set, the seed)
and the job as the workload file states it.  The model, its loss, the rule and
the attack are modules of this directory found by name.

The batch schedule is part of the job's definition: round r draws from
``fold_in(PRNGKey(seed), r)``; the first half of its split is the training
key, split once per local epoch, and the first half of an epoch key orders
the node's samples by ``argsort(uniform)``; batch t takes positions
``t * batch .. (t + 1) * batch`` of that order.
"""

import importlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import stored


@dataclass
class Job:
    """What the reference needs of a cell, in plain values."""

    model: str  # module of this directory with apply(params, x, dtype)
    rule: str
    rule_params: Dict[str, Any]
    attack: Optional[str]
    attack_params: Dict[str, Any]
    lr: float
    batch_size: int
    local_epochs: int
    total_rounds: int
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    # A fault planted for the control readings and the tests:
    # "half_batch" trains on the first half of every batch;
    # "no_exchange" leaves the rule out: every node keeps what it trained.
    fault: Optional[str] = None
    node_block: int = 32
    # ``loss_<loss>.py`` of this directory: the training loss and the
    # evaluation; ``loss_params`` as the configuration states them.
    loss: str = "label"
    loss_params: Dict[str, Any] = field(default_factory=dict)


def _module(kind: str, name: str):
    return importlib.import_module(
        f"benchmark.reference.{kind}{name.replace('.', '_')}"
    )


def batch_schedule(seed: int, round_idx: int, data: dict, job: Job):
    """Sample indices [E, T, N, B], the batch mask [N, B] and the steps'
    update mask [T, N] of one round."""
    mask = jnp.asarray(data["mask"], jnp.float32)
    eff = np.asarray(data["eff_batch"])
    steps = np.asarray(data["steps"])
    count = np.maximum(np.asarray(data["num_samples"]), 1)
    width, n_steps = int(eff.max()), int(steps.max())
    key = jax.random.fold_in(jax.random.PRNGKey(seed), np.uint32(round_idx))
    train_key, attack_key = jax.random.split(key)
    j = np.arange(width)
    epochs = []
    for epoch_key in jax.random.split(train_key, job.local_epochs):
        perm_key, _ = jax.random.split(epoch_key)
        order = np.asarray(jnp.argsort(
            jax.random.uniform(perm_key, mask.shape) + (1.0 - mask) * 10.0, axis=1
        ))
        pos = [(t * eff[:, None] + j[None, :]) % count[:, None] for t in range(n_steps)]
        epochs.append(np.stack([np.take_along_axis(order, p, axis=1) for p in pos]))
    batch_mask = (j[None, :] < eff[:, None]).astype(np.float32)
    if job.fault == "half_batch":
        batch_mask = batch_mask * (j[None, :] < np.maximum(eff // 2, 1)[:, None])
    live = (np.arange(n_steps)[:, None] < steps[None, :]).astype(np.float32)
    return np.stack(epochs).astype(np.int32), batch_mask, live, attack_key


def _loss(job: Job, part: str):
    """``training`` or ``evaluation`` of the job's loss for its model."""
    return getattr(_module("loss_", job.loss), part)(
        _module("", job.model).apply, job.compute_dtype, job.loss_params
    )


def make_trainer(job: Job):
    """Jitted ``(params, x, y, idx [E*T, n, B], bmask [n, B], upd [E*T, n])
    -> params`` for a block of nodes."""
    grad = jax.vmap(jax.grad(_loss(job, "training")))

    @jax.jit
    def train(params, x, y, idx, bmask, upd):
        def step(params, xs):
            ii, u = xs
            xb = jax.vmap(lambda a, i: a[i])(x, ii)
            yb = jax.vmap(lambda a, i: a[i])(y, ii)
            p32 = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
            g = grad(p32, xb, yb, bmask)
            bc = lambda leaf: u.reshape(u.shape + (1,) * (leaf.ndim - 1))
            return jax.tree_util.tree_map(
                lambda p, gg: stored(p - job.lr * bc(p) * gg, job.param_dtype),
                p32, g,
            ), None

        return jax.lax.scan(step, params, (idx, upd))[0]

    return train


def make_evaluator(job: Job):
    """Jitted ``(params, x, y, m) -> (loss [n], accuracy [n])`` for a block
    of nodes' held-out samples."""
    evaluate = _loss(job, "evaluation")

    @jax.jit
    def evaluate_block(params, x, y, m):
        def node(p, xi, yi, mi):
            p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
            return evaluate(p, xi, yi, mi)

        return jax.vmap(node)(params, x, y, m)

    return evaluate_block


def _after(tree) -> float:
    """The host's clock once ``tree`` is computed."""
    jax.block_until_ready(tree)
    return time.perf_counter()


def _blocks(n: int, size: int):
    return [slice(a, min(a + size, n)) for a in range(0, n, size)]


def _cut(tree, sl):
    return jax.tree_util.tree_map(lambda l: l[sl], tree)


@jax.jit
def _norm_of_difference(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


def leaf_norms(params, start) -> Dict[str, float]:
    """Norm of ``params - start`` for every stacked leaf, by its path.  One
    leaf is on the device at a time, so host copies cost it no memory."""
    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    flat0 = jax.tree_util.tree_leaves(start)
    for (path, leaf), leaf0 in zip(flat, flat0):
        out[jax.tree_util.keystr(path)] = float(
            _norm_of_difference(jnp.asarray(leaf), jnp.asarray(leaf0))
        )
    return out


def _rows(leaf):
    leaf = jnp.asarray(leaf)
    return leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)


@jax.jit
def _dots(a, b):
    return jnp.dot(_rows(a), _rows(b).T, precision=jax.lax.Precision.HIGHEST)


def held(x, dtype):
    """``x`` (float32) rounded as a state resident in ``dtype`` holds it.
    ``reduce_precision`` and no pair of casts: inside a jitted program a TPU
    keeps the float32 value through ``astype(bfloat16).astype(float32)``."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


@jax.jit
def _update_norms(weights, state, start, trained):
    """Norms of ``state - W start`` and of the reference's mix of
    ``trained`` less ``W start``.  The reference's mix is rounded once to
    the resident dtype, as a round's new state is when it is stored, so
    that both sides carry the same rounding of the same blend: under a
    rule that averages, that rounding is a quarter of the update's norm."""
    mix = lambda w, x: jnp.dot(w, x, precision=jax.lax.Precision.HIGHEST)
    dtype = jnp.asarray(state).dtype
    state, start, trained = _rows(state), _rows(start), _rows(trained)
    got = state - mix(weights, start)
    want = held(mix(weights, trained), dtype) - mix(weights, start)
    return jnp.sqrt(jnp.sum(got * got)), jnp.sqrt(jnp.sum(want * want))


def eval_loss(state, inputs: dict, job: Job) -> float:
    """The mean evaluation loss of a stacked state (on the host or the
    device) over the nodes' held-out samples, a block of nodes at a time."""
    data = inputs["data"]
    evaluate = make_evaluator(job)
    n = int(np.asarray(data["num_samples"]).shape[0])
    losses = [
        evaluate(_cut(state, sl), *(jnp.asarray(data[k][sl])
                                    for k in ("eval_x", "eval_y", "eval_mask")))[0]
        for sl in _blocks(n, job.node_block)
    ]
    return float(jnp.concatenate(losses).mean())


def first_update(state_first, inputs: dict, job: Job, trained_first) -> dict:
    """The first round's training, taken out of a state that the rule has
    mixed since.

    A round leaves ``state = W trained`` where ``W`` (rows summing to 1)
    is how the rule mixed the nodes' trained states.  The nodes start from
    independent draws, so the share of node c's start in node i's state,
    ``<state_i, start_c> / |start_c|^2``, gives ``W`` back once the rule's
    reference snaps it to the weights the rule can give (``recover``); a
    compromised node does not train, so what it sends is its start, times
    the attack's factor (additive noise aside).
    Then ``state - W start`` is the mixed training update as the run made
    it, and ``W trained - W start`` (the mix rounded as the run stores it)
    is the same mix of the reference's updates: the gap between their norms, leaf by leaf, says how the run
    trained, whichever neighbours it chose where the choice was close.

    Returns ``{"got": {leaf: norm}, "want": {leaf: norm}, "weights": W,
    "largest": the leaf that holds most of a node's parameters}``.
    """
    start = inputs["params"]
    flat, _ = jax.tree_util.tree_flatten_with_path(state_first)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    states = [l for _, l in flat]
    starts = jax.tree_util.tree_leaves(start)
    n = states[0].shape[0]
    share, size = np.zeros((n, n)), np.zeros(n)
    for a, b in zip(states, starts):
        share += np.asarray(_dots(a, b), np.float64)
        size += np.asarray(jnp.sum(_rows(b) ** 2, axis=1), np.float64)
    # A compromised node's broadcast may be its state times a factor (the
    # attack says which): its start's share in a neighbour's state carries
    # that factor, and so does what the neighbour took from it.
    factor = np.ones(n)
    if job.attack:
        scale = _module("attack_", job.attack).scale(job.attack_params)
        factor = np.where(np.asarray(inputs["compromised"]) > 0, scale, 1.0)
    sent = np.where(np.eye(n, dtype=bool), 1.0, factor[None, :])
    weights = sent * _module("rule_", job.rule).recover(
        share / size[None, :] / sent, np.asarray(inputs["adjacency"]) > 0,
        job.rule_params,
    )
    sizes = [int(np.prod(np.shape(l)[1:])) for l in states]
    out = {"got": {}, "want": {}, "weights": weights,
           "largest": paths[int(np.argmax(sizes))]}
    w = jnp.asarray(weights, jnp.float32)
    for path, a, b, t in zip(paths, states, starts,
                             jax.tree_util.tree_leaves(trained_first)):
        got, want = _update_norms(w, a, b, t)
        out["got"][path], out["want"][path] = float(got), float(want)
    return out


def run(inputs: dict, job: Job, rounds: int, keep_first: bool = False) -> dict:
    """Follow the job for ``rounds`` rounds from ``inputs["params"]``.

    Returns per-round mean eval loss and accuracy, the rule's statistics,
    the per-leaf norm of the parameters' change after the last round, and
    ``trained_first``: every node's state after the first round's local
    training, before the attack and the aggregation (stacked leaves, on
    the device).  ``keep_first`` also keeps ``state_first``, the state after
    the whole first round, for a run that stands in the program's place.
    """
    data = inputs["data"]
    n = int(np.asarray(data["num_samples"]).shape[0])
    start = inputs["params"]  # stays on the host
    params = jax.tree_util.tree_map(
        lambda l: stored(jnp.asarray(l), job.param_dtype), start
    )
    _, unravel = jax.flatten_util.ravel_pytree(_cut(params, 0))
    ravel = jax.jit(jax.vmap(lambda t: jax.flatten_util.ravel_pytree(t)[0]))
    unravel = jax.jit(jax.vmap(unravel))
    compromised = np.asarray(inputs["compromised"], np.float32)
    honest = 1.0 - compromised
    adj = np.asarray(inputs["adjacency"], np.float32)
    rule = _module("rule_", job.rule)
    attack = _module("attack_", job.attack) if job.attack else None
    state = rule.init_state(n, job.rule_params)
    train, evaluate = make_trainer(job), make_evaluator(job)
    x, y = jnp.asarray(data["x"]), jnp.asarray(data["y"])
    ev = [jnp.asarray(data[k]) for k in ("eval_x", "eval_y", "eval_mask")]
    blocks = _blocks(n, job.node_block)
    out = {"loss": [], "accuracy": [], "stats": []}
    for r in range(rounds):
        clock = [time.perf_counter()]
        idx, bmask, live, attack_key = batch_schedule(inputs["seed"], r, data, job)
        idx = idx.reshape((-1,) + idx.shape[2:])
        upd = np.tile(live, (job.local_epochs, 1)) * honest[None, :]
        own = jnp.concatenate([
            ravel(train(_cut(params, sl), x[sl], y[sl], jnp.asarray(idx[:, sl]),
                        jnp.asarray(bmask[sl]), jnp.asarray(upd[:, sl])))
            for sl in blocks
        ])
        del params
        clock.append(_after(own))
        if r == 0:
            out["trained_first"] = unravel(own)
        bcast = own
        if attack is not None:
            rows = np.flatnonzero(compromised > 0)
            if len(rows):
                bcast = own.at[rows].set(stored(
                    attack.apply(own[rows].astype(jnp.float32), attack_key,
                                 job.attack_params, float(r),
                                 context={"own": own, "honest": honest}),
                    job.param_dtype,
                ))
        context = {"data": data, "job": job, "unravel": unravel, "evaluate": evaluate}
        new, state, stats = rule.aggregate(
            own, bcast, adj, float(r), state, job.rule_params, job.total_rounds,
            context,
        )
        if job.fault == "no_exchange":
            new = own
        del own, bcast
        params = unravel(stored(new, job.param_dtype))
        del new
        clock.append(_after(params))
        rows = [evaluate(_cut(params, sl), *(a[sl] for a in ev)) for sl in blocks]
        out["loss"].append(float(jnp.concatenate([a for a, _ in rows]).mean()))
        out["accuracy"].append(float(jnp.concatenate([b for _, b in rows]).mean()))
        out["stats"].append({k: float(jnp.mean(v)) for k, v in stats.items()})
        clock.append(time.perf_counter())
        train_s, rule_s, eval_s = np.diff(clock)
        print(f"[bench] reference round {r}: train {train_s:.2f}s attack and "
              f"rule {rule_s:.2f}s eval {eval_s:.2f}s", flush=True)
        if r == 0 and keep_first:
            out["state_first"] = params
    out["change"] = leaf_norms(params, start)
    return out
