"""Precision of the plain reference's multiplications.

A product ``f(a, b)`` (a matmul or a convolution) is computed in float32
at ``highest`` on operands rounded to the stated dtype, forward and
backward: the cotangent is rounded too before it meets an operand, as a
chip does that multiplies in that dtype.  "bfloat16" is what the
configurations state (bf16 operands, f32 accumulation).  "float8_e4m3fn"
is the control, the nearest precision below bf16, as an fp8 training step
would run it: e4m3 operands and e5m2 cotangents, each scaled per tensor to
its format's range.

Every rounding is ``lax.reduce_precision`` and never a pair of casts:
inside a jitted program the v5e's compiler drops
``astype(bfloat16).astype(float32)`` and the value stays float32
(PERF.md).  ``rounding_probe`` shows on the device a run is made on that
each format rounds as it does on the host.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# (exponent bits, mantissa bits, largest finite value) of a format as
# ``reduce_precision`` rounds to it: IEEE-like, so e4m3 tops out at 240
# where the "fn" variant reaches 448; values under the least normal
# number go to nought.
FORMATS = {
    "bfloat16": (8, 7, None),
    "float8_e4m3fn": (4, 3, 240.0),
    "float8_e5m2": (5, 2, 57344.0),
}


def rounded(x, fmt: str):
    """``x`` (float32) rounded to ``fmt``; the 8-bit formats after a
    per-tensor scale that puts the largest magnitude at the format's top."""
    exponent, mantissa, top = FORMATS[fmt]
    x = x.astype(jnp.float32)
    if top is None:
        return jax.lax.reduce_precision(x, exponent, mantissa)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * scale, exponent, mantissa) / scale


def round_operand(x, dtype: str):
    if dtype == "float32":
        return x.astype(jnp.float32)
    if dtype in ("bfloat16", "float8_e4m3fn"):
        return rounded(x, dtype)
    raise ValueError(f"unknown compute dtype {dtype!r}")


def round_cotangent(g, dtype: str):
    if dtype == "float8_e4m3fn":
        return rounded(g, "float8_e5m2")
    return round_operand(g, dtype)


def rounding_probe(size: int = 1 << 16) -> dict:
    """On the device in use, inside a jitted program: per format, the share
    of ``size`` normal draws that rounding changed and the widest relative
    step it made (half a unit in the last place: 2^-(mantissa + 1)).  A
    format whose rounding the compiler dropped reads 0 and 0."""
    x = jax.random.normal(jax.random.PRNGKey(0), (size,), jnp.float32)
    out = {}
    for fmt, (_, mantissa, _) in FORMATS.items():
        y = jax.jit(lambda v, fmt=fmt: rounded(v, fmt))(x)
        # Leave out what an 8-bit format flushes to nought.
        kept = (y != 0) & (x != 0)
        step = jnp.where(kept, jnp.abs(y - x) / jnp.abs(x), 0.0)
        out[fmt] = {
            "changed": float(jnp.mean((y != x).astype(jnp.float32))),
            "widest_step": float(jnp.max(step)),
            "half_ulp": 2.0 ** -(mantissa + 1),
        }
    return out


def product(f, a, b, dtype: str):
    """``f(a, b)``, bilinear, with operands and cotangents in ``dtype``."""

    @jax.custom_vjp
    def op(a, b):
        return f(round_operand(a, dtype), round_operand(b, dtype))

    def forward(a, b):
        qa, qb = round_operand(a, dtype), round_operand(b, dtype)
        return f(qa, qb), (qa, qb)

    def backward(rounded, g):
        return jax.vjp(f, *rounded)[1](round_cotangent(g, dtype))

    op.defvjp(forward, backward)
    return op(a, b)


def matmul(a, b, dtype: str):
    return product(lambda x, y: jnp.dot(x, y, precision=HIGHEST), a, b, dtype)


def stored(x, dtype: str):
    """A parameter in its resident dtype; consumers compute in float32."""
    return x.astype(jnp.dtype(dtype))
