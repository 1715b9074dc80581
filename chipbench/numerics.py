"""The contractions of the plain references, at a stated precision.

``"highest"`` is float32 contraction (``Precision.HIGHEST``), the
precision the configurations state.  ``"high"`` is the three-pass
bfloat16 product, the nearest precision below it and the one a kernel
would be tempted to drop to; it serves as the correctness control.  It
is written out, the same on every backend: each operand is split into a
bfloat16 high part and a bfloat16 remainder, and the product keeps
hi*hi + hi*lo + lo*hi, each pass exact in float32.  The split rounds with
``reduce_precision`` and keeps float32 tensors: a split by casts to
bfloat16 and back is rewritten by the TPU compiler, which then keeps
the tensors in bfloat16, and reads about one bfloat16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")
_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    """``a`` rounded to bfloat16's 8 significant bits, kept in float32."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _passes(f, a, b, precision: str):
    if precision == "highest":
        return f(a, b, _HIGHEST)
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ah, al = _split(a)
    bh, bl = _split(b)
    return (f(ah, bh, _HIGHEST)
            + (f(ah, bl, _HIGHEST) + f(al, bh, _HIGHEST)))


def conv(x, w, stride: int, pad: int, precision: str, groups: int = 1):
    """NHWC x HWIO convolution with symmetric zero padding."""
    def f(a, b, p):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=p)
    return _passes(f, x, w, precision)


def dot(x, w, precision: str):
    return _passes(lambda a, b, p: jnp.dot(a, b, precision=p), x, w,
                   precision)


def maxpool(x, k: int, s: int, p: int):
    """Max pool over NHWC with symmetric ``-inf`` padding."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1),
        [(0, 0), (p, p), (p, p), (0, 0)])
