"""Isolated local-SGD microbenchmark: what does the training segment cost
without any FL machinery?

Runs the same vmapped 20-node FEMNIST-CNN SGD step the round program
executes (4 masked steps, batch 32/node, bf16 compute) as a standalone
jitted scan, plus a plain 640-image fused-batch training step for
comparison.  The gap between the two bounds what the per-node vmap
formulation costs vs an ideal fused batch; the gap to bench_breakdown's
local_sgd segment bounds what the FL data-indexing adds.

Prints one JSON line.  Chip or fail: exits 2 unless ``jax.devices()`` is a
TPU.  Uses marginal chain timing, which cancels the fixed host latency of
the final fetch.
"""

import json
import time

import jax
import jax.numpy as jnp


def marginal_ms(f, args, k1=5, k2=25):
    def run(k):
        t0 = time.perf_counter()
        o = args[0]
        for _ in range(k):
            o = f(o, *args[1:])
        jax.device_get(jax.tree_util.tree_leaves(o)[0].ravel()[0])
        return time.perf_counter() - t0

    run(2)
    t1, t2 = run(k1), run(k2)
    return 1e3 * (t2 - t1) / (k2 - k1)


def main():
    from bench import require_chip
    from murmura_tpu.models.cnn import make_femnist_cnn

    device = require_chip("bench_sgd_micro")
    n, b, steps = 20, 32, 4
    model = make_femnist_cnn(num_classes=62, compute_dtype="bfloat16")
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    params = jax.vmap(model.init)(keys)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, b * steps, 28, 28, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (n, b * steps), 0, 62)

    def node_loss(p, xb, yb):
        logits = model.apply(p, xb, None, True)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, yb[:, None], -1).mean()

    grad = jax.grad(node_loss)

    @jax.jit
    def vmapped_steps(params, x, y):
        def body(p, t):
            xb = jax.lax.dynamic_slice_in_dim(x, t * b, b, 1)
            yb = jax.lax.dynamic_slice_in_dim(y, t * b, b, 1)
            g = jax.vmap(grad)(p, xb, yb)
            return jax.tree_util.tree_map(lambda a, gg: a - 0.05 * gg, p, g), None

        params, _ = jax.lax.scan(body, params, jnp.arange(steps))
        return params

    t_vmap = marginal_ms(vmapped_steps, (params, x, y))

    # Ideal fused comparison: one model, batch n*b, same total images/step.
    params1 = model.init(jax.random.PRNGKey(0))
    xf = x.reshape(n * b * steps, 28, 28, 1)
    yf = y.reshape(n * b * steps)

    @jax.jit
    def fused_steps(p, x, y):
        def body(p, t):
            xb = jax.lax.dynamic_slice_in_dim(x, t * n * b, n * b, 0)
            yb = jax.lax.dynamic_slice_in_dim(y, t * n * b, n * b, 0)
            g = grad(p, xb, yb)
            return jax.tree_util.tree_map(lambda a, gg: a - 0.05 * gg, p, g), None

        p, _ = jax.lax.scan(body, p, jnp.arange(steps))
        return p

    t_fused = marginal_ms(fused_steps, (params1, xf, yf))

    # Candidate lever 1: bf16 resident params (tpu.param_dtype) — halves
    # the elementwise SGD-update traffic; update math stays f32 like the
    # round program's (rounds.py local_training).
    params_bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), params
    )

    @jax.jit
    def vmapped_steps_bf16(params, x, y):
        def body(p, t):
            xb = jax.lax.dynamic_slice_in_dim(x, t * b, b, 1)
            yb = jax.lax.dynamic_slice_in_dim(y, t * b, b, 1)
            g = jax.vmap(grad)(p, xb, yb)
            return jax.tree_util.tree_map(
                lambda a, gg: (
                    a.astype(jnp.float32) - 0.05 * gg.astype(jnp.float32)
                ).astype(a.dtype),
                p, g,
            ), None

        params, _ = jax.lax.scan(body, params, jnp.arange(steps))
        return params

    t_bf16 = marginal_ms(vmapped_steps_bf16, (params_bf16, x, y))

    # Candidate lever 2: im2col formulation — per-node convs expressed as
    # patch-extraction + batched GEMM ([N, B*HW, K*K*C] @ [N, K*K*C, F]),
    # so the whole conv stack runs as MXU-native batched matmuls instead of
    # whatever XLA lowers a vmapped (grouped) convolution to.  Same math,
    # same shapes as the FEMNIST CNN's two conv layers + FC head.
    from jax import lax

    def patches(x, k):
        # [B, H, W, C] -> [B, H, W, k*k*C] (SAME padding, stride 1)
        p = lax.conv_general_dilated_patches(
            x, (k, k), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return p

    def init_im2col(key):
        ks = jax.random.split(key, 8)
        he = jax.nn.initializers.he_normal()
        return {
            "w1": he(ks[0], (25 * 1, 32)),     "b1": jnp.zeros((32,)),
            "w2": he(ks[1], (25 * 32, 64)),    "b2": jnp.zeros((64,)),
            "w3": he(ks[2], (7 * 7 * 64, 2048)), "b3": jnp.zeros((2048,)),
            "w4": he(ks[3], (2048, 62)),       "b4": jnp.zeros((62,)),
        }

    def im2col_apply(p, xb):
        bsz = xb.shape[0]
        cd = jnp.bfloat16
        h = patches(xb, 5).reshape(bsz * 28 * 28, 25)
        h = (h.astype(cd) @ p["w1"].astype(cd)).astype(jnp.float32) + p["b1"]
        h = jax.nn.relu(h).reshape(bsz, 28, 28, 32)
        h = lax.reduce_window(
            h, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
        h = patches(h, 5).reshape(bsz * 14 * 14, 25 * 32)
        h = (h.astype(cd) @ p["w2"].astype(cd)).astype(jnp.float32) + p["b2"]
        h = jax.nn.relu(h).reshape(bsz, 14, 14, 64)
        h = lax.reduce_window(
            h, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
        h = h.reshape(bsz, 7 * 7 * 64)
        h = jax.nn.relu(
            (h.astype(cd) @ p["w3"].astype(cd)).astype(jnp.float32) + p["b3"]
        )
        return (h.astype(cd) @ p["w4"].astype(cd)).astype(jnp.float32) + p["b4"]

    def im2col_loss(p, xb, yb):
        logp = jax.nn.log_softmax(im2col_apply(p, xb), -1)
        return -jnp.take_along_axis(logp, yb[:, None], -1).mean()

    im2col_grad = jax.grad(im2col_loss)
    params_i2c = jax.vmap(init_im2col)(keys)

    @jax.jit
    def vmapped_steps_im2col(params, x, y):
        def body(p, t):
            xb = jax.lax.dynamic_slice_in_dim(x, t * b, b, 1)
            yb = jax.lax.dynamic_slice_in_dim(y, t * b, b, 1)
            g = jax.vmap(im2col_grad)(p, xb, yb)
            return jax.tree_util.tree_map(
                lambda a, gg: a - 0.05 * gg, p, g
            ), None

        params, _ = jax.lax.scan(body, params, jnp.arange(steps))
        return params

    t_i2c = marginal_ms(vmapped_steps_im2col, (params_i2c, x, y))

    print(json.dumps({
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "shapes": {"nodes": n, "batch": b, "steps": steps},
        "vmapped_20node_4step_ms": round(t_vmap, 2),
        "fused_single_model_4step_ms": round(t_fused, 2),
        "vmapped_bf16_params_ms": round(t_bf16, 2),
        "vmapped_im2col_ms": round(t_i2c, 2),
        "note": "vmapped = the round program's formulation (20 models, "
                "batch 32 each); fused = one model at batch 640 (upper "
                "bound on achievable MXU utilization for the same images); "
                "bf16/im2col = candidate levers for the local_sgd segment "
                "(resident-param dtype; conv-as-batched-GEMM)",
    }))


if __name__ == "__main__":
    main()
