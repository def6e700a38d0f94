"""The routed experts' pairs' side (models/decoder.py ``experts_at``,
ops/row_scatter.py) on the CPU, float32.

(a) The buffer is filled straight from ``x`` and the experts' rows are added
back into ``[T, hidden]``, with cotangents written by hand: the result and
its cotangents with respect to ``x``, the weights and the experts' matrices
are those of autodiff through the pairs' side written out with ``x``
repeated ``top_k`` times (every pair a row of ``[T * top_k, hidden]``), at
every step of the buffer's ladder, at one pair a position and at two, with
no pair of the sequence held, with every pair held, and between.  (b) No
value of the gradient's program is ``[T * top_k, hidden]``,
``[T, top_k, hidden]`` or ``[top_k, T, hidden]``.  (c) The kernel that adds
the rows back, interpreted, is the ``jnp`` path's sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import murmura_tpu.utils  # noqa: F401 - the package's import order (ROADMAP D16)
from murmura_tpu.models import decoder
from murmura_tpu.ops import row_scatter

T, HIDDEN, WIDTH, EXPERTS, HELD = 16, 8, 6, 8, 2


def _repeated(buffer_rows, top_k, dtype, ints, floats):
    """``experts_at`` with the pairs' side over ``[T * top_k, hidden]``:
    ``x`` repeated ``top_k`` times, the held pairs' rows taken into the
    buffer, the buffer's rows taken back to every pair and combined over
    ``[T, top_k, hidden]``; plain autodiff throughout."""
    (row_of, mine, sizes), (p, x, weights) = ints, floats
    t, pairs = x.shape[0], x.shape[0] * top_k
    row_of = jnp.where(mine, row_of, buffer_rows)
    pair_of = jnp.zeros((buffer_rows,), jnp.int32).at[row_of].set(
        jnp.arange(pairs, dtype=jnp.int32), mode="drop")
    filled = jnp.zeros((buffer_rows,), bool).at[row_of].set(True, mode="drop")
    row_of = jnp.minimum(row_of, buffer_rows - 1)
    rows = jnp.where(filled[:, None], jnp.repeat(x, top_k, axis=0)[pair_of], 0.0)
    grouped = lambda a, w: decoder._grouped(a, w, sizes, dtype)
    down = grouped(jax.nn.silu(grouped(rows, p["gate"])) * grouped(rows, p["up"]), p["down"])
    y = jnp.where(mine[:, None], down[row_of], 0.0)
    return (y.reshape(t, top_k, -1) * weights[..., None]).sum(axis=1)


def _inputs(top_k, hidden=HIDDEN, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"gate": 0.3 * jax.random.normal(keys[0], (HELD, hidden, WIDTH)),
         "up": 0.3 * jax.random.normal(keys[1], (HELD, hidden, WIDTH)),
         "down": 0.3 * jax.random.normal(keys[2], (HELD, WIDTH, hidden))}
    x = jax.random.normal(keys[3], (T, hidden))
    weights = jax.random.uniform(keys[4], (T, top_k), minval=0.1, maxval=1.0)
    return p, x, weights, jax.random.normal(keys[5], (T, hidden))


def _chosen(top_k, case):
    """The experts each position chose ([T, top_k], distinct a position;
    experts 0 and 1 are held) and the step of the ladder the buffer takes:
    with tiles of 4 rows and a floor of one even share, 12 / 24 / 40 rows
    at two pairs a position, 8 / 16 / 24 at one."""
    if case == "drawn":
        scores = jax.random.uniform(jax.random.PRNGKey(7), (T, EXPERTS))
        return jax.lax.top_k(scores, top_k)[1], None
    if top_k == 2:
        pair = {"none": [4, 5], "between": [0, 4], "every": [0, 1]}[case]
        return jnp.asarray([pair] * T, jnp.int32), {"none": 0, "between": 1, "every": 2}[case]
    # One pair a position: 12 positions to expert 0 needs 12 rows; 9 and 7
    # to experts 0 and 1 need 12 + 8.
    first = {"none": [4] * T, "between": [0] * 12 + [5] * 4, "every": [0] * 9 + [1] * 7}[case]
    return jnp.asarray(first, jnp.int32)[:, None], {"none": 0, "between": 1, "every": 2}[case]


def _close(got, want, tol=2e-6):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(np.abs(want).max(), 1e-30))


def _against_the_repeated_rows(top_k, case, monkeypatch, hidden=HIDDEN):
    """The layer's result, its step and its cotangents, and the same through
    ``_repeated``; the step each took."""
    p, x, weights, g = _inputs(top_k, hidden)
    chosen, want_step = _chosen(top_k, case)
    dispatch = dict(held=HELD, first_held=0, top_k=top_k, n_routed_experts=EXPERTS, dtype=None)

    def run():
        layer = lambda p, x, w: decoder.experts(p, x, chosen, w, **dispatch)
        y, pull, step = jax.vjp(layer, p, x, weights, has_aux=True)
        return y, step, pull(g)

    y, step, (dp, dx, dw) = jax.jit(run)()
    with monkeypatch.context() as m:
        m.setattr(decoder, "experts_at", _repeated)
        y0, step0, (dp0, dx0, dw0) = jax.jit(run)()
    assert int(step) == int(step0)
    held = np.asarray((chosen >= 0) & (chosen < HELD))
    if case == "none":
        assert not held.any() and not np.asarray(y).any() and not np.asarray(dw).any()
    if case == "every":
        assert held.all()
    _close(y, y0)
    _close(dx, dx0)
    _close(dw, dw0)
    for a, b in zip(jax.tree_util.tree_leaves(dp), jax.tree_util.tree_leaves(dp0)):
        _close(a, b)
    # A pair that is not held takes no weight's gradient.
    assert not np.asarray(dw)[~held].any()
    return int(step), want_step


@pytest.mark.parametrize("case", ["none", "between", "every", "drawn"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_the_pairs_side_gives_the_repeated_sums_and_cotangents(top_k, case, monkeypatch):
    monkeypatch.setattr(decoder, "GROUP_ALIGN", 4)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", 1)
    assert len(decoder.ladder(T, top_k, HELD, EXPERTS)[1]) == 3
    step, want_step = _against_the_repeated_rows(top_k, case, monkeypatch)
    if want_step is not None:
        assert step == want_step


@pytest.mark.parametrize("case", ["none", "every", "drawn"])
def test_the_kernel_adds_the_pairs_back_as_the_repeated_sums(case, monkeypatch):
    """Two pairs a position added back by the kernel, interpreted: tiles of
    8 rows (a buffer of 16, 32 or 48 rows) and 128 wide, where it tiles."""
    monkeypatch.setattr(decoder, "GROUP_ALIGN", 8)
    monkeypatch.setattr(decoder, "GROUP_FLOOR_SHARES", 1)
    assert decoder.ladder(T, 2, HELD, EXPERTS)[1] == [16, 32, 48]
    add = row_scatter.row_scatter_add
    used = []
    monkeypatch.setattr(decoder, "row_scatter_add",
                        lambda *a: used.append(a[0].shape) or add(*a, use_pallas=True))
    step, _ = _against_the_repeated_rows(2, case, monkeypatch, hidden=128)
    assert used and all(row_scatter.kernel_blocks(r, T, w) for r, w in used)
    assert step == {"none": 0, "every": 1}.get(case, step)


def _shapes(jaxpr, seen):
    """Every value's shape in a jaxpr and the jaxprs inside it, and the
    name of every primitive."""
    for eqn in jaxpr.eqns:
        seen.add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                seen.add(tuple(shape))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _shapes(inner, seen)
    return seen


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_no_value_of_the_pairs_rows_in_the_gradient(use_pallas, monkeypatch):
    """At 24 positions, three pairs a position and 256 wide (72 pairs, a
    size nothing else has), the gradient of the experts' layer holds no
    value of 72 rows by 256, of ``[24, 3, 256]`` or ``[3, 24, 256]``, on
    either path of the adding back; written over the repeated rows, it
    does."""
    t, top_k, hidden = 24, 3, 256
    add = row_scatter.row_scatter_add
    monkeypatch.setattr(decoder, "row_scatter_add",
                        lambda *a: add(*a, use_pallas=use_pallas))
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    p = {"gate": jnp.ones((HELD, hidden, WIDTH)), "up": jnp.ones((HELD, hidden, WIDTH)),
         "down": jnp.ones((HELD, WIDTH, hidden))}
    x = jax.random.normal(keys[0], (t, hidden))
    chosen = jax.lax.top_k(jax.random.uniform(keys[1], (t, EXPERTS)), top_k)[1]
    weights = jax.random.uniform(keys[2], (t, top_k))
    dispatch = dict(held=HELD, first_held=0, top_k=top_k, n_routed_experts=EXPERTS, dtype=None)

    def shapes():  # a new function each time: a traced one is cached
        loss = lambda p, x, w: (decoder.experts(p, x, chosen, w, **dispatch)[0] ** 2).sum()
        grad = jax.grad(loss, argnums=(0, 1, 2))
        return _shapes(jax.make_jaxpr(grad)(p, x, weights).jaxpr, set())

    pairs_rows = {(t * top_k, hidden), (t, top_k, hidden), (top_k, t, hidden)}
    seen = shapes()
    assert not seen & pairs_rows
    assert ("pallas_call" in seen) == use_pallas
    monkeypatch.setattr(decoder, "experts_at", _repeated)
    assert (t * top_k, hidden) in shapes()


@pytest.mark.parametrize("rows,size,width,resident", [
    (40, 16, 256, None),        # five blocks of 8 rows, one column block
    (64, 32, 384, 2 * 32 * 128 * 4),  # three column blocks of 128
    (512, 8, 128, None),        # one block of 512 rows, 64 rows a place
])
def test_the_kernel_adds_the_rows_as_the_jnp_path(rows, size, width, resident, monkeypatch):
    """Interpreted, the kernel gives the ``jnp`` path's sums: every place the
    sum of its rows times their scales, a place no row names zeros, and a
    row whose index is negative never read (it holds NaN here)."""
    if resident is not None:
        monkeypatch.setattr(row_scatter, "_RESIDENT_BYTES", resident)
    blocks = row_scatter.kernel_blocks(rows, size, width)
    assert blocks is not None
    keys = jax.random.split(jax.random.PRNGKey(rows), 3)
    values = jax.random.normal(keys[0], (rows, width)).at[-1].set(jnp.nan)
    index = jax.random.randint(keys[1], (rows,), -1, size - 1).at[-1].set(-1)
    scale = jax.random.uniform(keys[2], (rows,))
    got = row_scatter.row_scatter_add(values, index, scale, size, use_pallas=True)
    want = row_scatter.row_scatter_add(values, index, scale, size, use_pallas=False)
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[size - 1].any()  # no row names the last place
    _close(got, want)


def test_the_kernel_takes_only_shapes_it_tiles():
    assert row_scatter.kernel_blocks(8192, 4096, 2048) == (512, 2048)
    assert row_scatter.kernel_blocks(28672, 4096, 2048) == (512, 2048)
    assert row_scatter.kernel_blocks(8192, 8192, 2048) == (512, 1024)
    assert row_scatter.kernel_blocks(40, 16, 64) is None    # 64 columns: under a lane tile
    assert row_scatter.kernel_blocks(12, 16, 128) is None   # 12 rows: not a sublane tile
