"""Public jit'd wrappers around the Pallas kernels.

``flash_attention`` takes the model-layout [B, H, S, hd] (+ GQA kv heads),
pads the sequence to block multiples and dispatches to the kernel;
``conv2d`` / ``dwconv2d`` route through the shard kernel for any supported
geometry (stride >= 1, square kernel, non-degenerate output) with an
automatic XLA fallback otherwise; ``matmul`` is the row-tiled MXU kernel
behind the engine's FC layers.  ``interpret=None`` (the default) picks
interpret mode everywhere except a TPU backend
(:func:`repro.kernels.mode.interpret_mode`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .conv2d import UnsupportedGeometry, conv2d_shard
from .flash_attention import flash_attention_bh
from .mode import interpret_mode


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: [B, H, S, hd]; k/v: [B, KV, S, hd] with H % KV == 0."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    blk = max(block_q, block_k)
    pad = (-S) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    out = flash_attention_bh(
        q.reshape(B * H, Sp, hd), k.reshape(B * H, Sp, hd),
        v.reshape(B * H, Sp, hd), causal=causal, window=window,
        scale=1.0 / math.sqrt(hd), block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out.reshape(B, H, Sp, hd)[:, :, :S, :]


# ---------------------------------------------------------------------------
# Row-tiled matmul — the FC / pointwise-as-matmul shard kernel.
# ---------------------------------------------------------------------------

def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def matmul_tiled(x: jnp.ndarray, w: jnp.ndarray, *, tile_m: int = 128,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: [M, Cin] @ w: [Cin, Cout], output rows tiled by ``tile_m`` (each
    tile is one MXU matmul; rows pad to the tile multiple and are dropped
    on return).  Engine FC shards are [seq, Cin] with Cin/Cout possibly
    channel-sliced by the plan — any shape goes."""
    M, cin = x.shape
    cout = w.shape[1]
    if M == 0 or cin == 0 or cout == 0:
        raise UnsupportedGeometry(f"degenerate matmul {x.shape} @ {w.shape}")
    tile_m = max(1, min(tile_m, M))
    nt = -(-M // tile_m)
    pad = nt * tile_m - M
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((tile_m, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt * tile_m, cout), x.dtype),
        interpret=interpret_mode(interpret),
        name="matmul_tiled",
    )(xp, w)
    return out[:M]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def matmul(x: jnp.ndarray, w: jnp.ndarray, *, tile_m: int = 128,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Jit'd :func:`matmul_tiled` with XLA fallback on degenerate shapes."""
    try:
        return matmul_tiled(x, w, tile_m=tile_m, interpret=interpret)
    except UnsupportedGeometry:
        return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Conv wrappers: Pallas when supported, XLA fallback otherwise.
# ---------------------------------------------------------------------------

def _conv_xla(x: jnp.ndarray, w: jnp.ndarray, *, padding: int, stride: int,
              groups: int = 1) -> jnp.ndarray:
    out = jax.lax.conv_general_dilated(
        x[None].astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding=[(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return out[0].astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("padding", "stride", "tile_h",
                                             "interpret"))
def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, padding: int = 0,
           stride: int = 1, tile_h: int = 8,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: [H, W, Cin]; w: [K, K, Cin, Cout]; any stride.  Pallas path for
    every non-degenerate square-kernel geometry; degenerate outputs
    (``out_h/out_w <= 0``) fall back to XLA cleanly."""
    try:
        return conv2d_shard(x, w, pads=(padding,) * 4, stride=stride,
                            tile_h=tile_h, interpret=interpret)
    except UnsupportedGeometry:
        return _conv_xla(x, w, padding=padding, stride=stride)


@functools.partial(jax.jit, static_argnames=("padding", "stride", "tile_h",
                                             "interpret"))
def dwconv2d(x: jnp.ndarray, w: jnp.ndarray, *, padding: int = 0,
             stride: int = 1, tile_h: int = 8,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """Depthwise conv: x [H, W, C]; w [K, K, 1, C] (engine layout)."""
    try:
        return conv2d_shard(x, w, pads=(padding,) * 4, stride=stride,
                            depthwise=True, tile_h=tile_h,
                            interpret=interpret)
    except UnsupportedGeometry:
        return _conv_xla(x, w, padding=padding, stride=stride,
                         groups=x.shape[-1])
