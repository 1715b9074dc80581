"""Pallas TPU conv2d shard kernels — the FlexPie compute hot spot.

The edge engine's partitioned inference runs conv shards with halo rows
(§2.3 of the paper).  :func:`conv2d_shard` is the TPU-native version of one
shard's compute and consumes the NT-mode shard layout: the local input
slice — its own rows plus the halo rows backward-chained from the segment
tail.  The shard's per-side zero padding at the graph boundary is applied
once in HBM, where a stride-``s`` shard is also split into its ``s*s``
row/column phases (:func:`_phases`).  The shard stays in HBM: each output
tile's halo-extended row window is an overlapping ``pl.Element`` block, so
the Pallas pipeline streams one window per tile (double-buffered) and peak
VMEM depends on the tile height and the row width, never on the shard
height.

The compute is im2col without materializing the im2col matrix: the output
is tiled by rows and each (kh, kw) kernel tap is an MXU matmul
``[tile_h*W, Cin] @ [Cin, Cout]`` accumulated in f32 at f32 contraction
precision (``Precision.HIGHEST``).  Thanks to the phase split every tap of
a strided conv is a contiguous stride-1 window of one phase — the TPU
compiler lowers neither a strided value slice nor a strided ref load of
these layouts.  Depthwise convs replace the tap matmul with a VPU
broadcast-multiply.  A tile deliberately reads ``K-1`` rows past its own
range — exactly the redundant-compute region the planner accounts for.

Degenerate geometries (``out_h <= 0`` or ``out_w <= 0`` after padding)
raise :class:`UnsupportedGeometry`; callers (``ops.conv2d``, the engine's
pallas backend) route those to the XLA path.  Off the TPU the kernel runs
in interpret mode (:func:`repro.kernels.mode.interpret_mode`).

The kernel is executor-agnostic: the single-process engine hands it the
host-sliced local input, and the mesh executor
(``runtime.mesh_exec``) traces the *same* kernel inside per-device
``shard_map`` programs where the halo-extended slice is assembled by
collectives (``ppermute`` neighbor exchange / ``all_gather``) instead of
host indexing — the shard layout contract above is what makes that
drop-in.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import interpret_mode

Pads = Tuple[int, int, int, int]   # (top, bottom, left, right)


class UnsupportedGeometry(ValueError):
    """Raised when a conv geometry cannot be lowered to the Pallas kernel
    (callers fall back to XLA)."""


def shard_out_shape(in_h: int, in_w: int, k: int, stride: int,
                    pads: Pads) -> Tuple[int, int]:
    """Output (H, W) of a conv over a [in_h, in_w] shard with explicit
    per-side zero padding ``pads`` and square kernel ``k``."""
    pt, pb, pl_, pr = pads
    out_h = (in_h + pt + pb - k) // stride + 1
    out_w = (in_w + pl_ + pr - k) // stride + 1
    return out_h, out_w


def _phases(xp: jnp.ndarray, s: int) -> jnp.ndarray:
    """[R, C, cin] (R, C multiples of ``s``) -> [s*s, R/s, C/s, cin]:
    phase ``a*s + b`` holds rows ``a::s`` and columns ``b::s``, so every
    tap of a stride-``s`` conv is a contiguous stride-1 window of one
    phase."""
    R, C, cin = xp.shape
    if s == 1:
        return xp[None]
    return xp.reshape(R // s, s, C // s, s, cin).transpose(
        1, 3, 0, 2, 4).reshape(s * s, R // s, C // s, cin)


def _shard_kernel(x_ref, w_ref, o_ref, *, k: int, stride: int,
                  tile_h: int, out_w: int, cin: int, cout: int,
                  depthwise: bool):
    s = stride
    if depthwise:
        acc = jnp.zeros((tile_h, out_w, cout), jnp.float32)
    else:
        acc = jnp.zeros((tile_h * out_w, cout), jnp.float32)
    for kh in range(k):
        for kw in range(k):
            # tap (kh, kw): phase (kh % s, kw % s) at offset
            # (kh // s, kw // s) of this tile's window
            xs = x_ref[(kh % s) * s + kw % s, pl.ds(kh // s, tile_h),
                       pl.ds(kw // s, out_w), :].astype(jnp.float32)
            if depthwise:
                acc = acc + xs * w_ref[kh, kw].astype(jnp.float32)
            else:
                acc = acc + jax.lax.dot_general(
                    xs.reshape(tile_h * out_w, cin),
                    w_ref[kh, kw].astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(tile_h, out_w, cout).astype(o_ref.dtype)


def conv2d_shard(x: jnp.ndarray, w: jnp.ndarray, *, pads: Pads = (0, 0, 0, 0),
                 stride: int = 1, depthwise: bool = False, tile_h: int = 8,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """One conv shard over the NT-mode local layout.

    ``x``: [Hl, Wl, Cin] — the node's raw input slice, halo rows included,
    NOT zero-padded.  ``w``: [K, K, Cin, Cout] (depthwise: [K, K, 1, C]).
    ``pads`` is the logical zero padding of this shard's position in the
    full feature map (interior shards: all zero — their "padding" is real
    halo data already inside ``x``).
    """
    K = w.shape[0]
    if w.shape[1] != K:
        raise UnsupportedGeometry(f"non-square kernel {w.shape[:2]}")
    if stride < 1:
        raise UnsupportedGeometry(f"stride {stride}")
    Hl, Wl, cin = x.shape
    cout = cin if depthwise else w.shape[3]
    out_h, out_w = shard_out_shape(Hl, Wl, K, stride, pads)
    if out_h <= 0 or out_w <= 0 or cin <= 0 or cout <= 0:
        raise UnsupportedGeometry(
            f"degenerate output {out_h}x{out_w}x{cout} for input "
            f"{Hl}x{Wl}x{cin}, k={K}, s={stride}, pads={pads}")
    s = stride
    pt, _, pl_, _ = pads
    tile_h = max(1, min(tile_h, out_h))
    nt = -(-out_h // tile_h)
    q = (K - 1) // s                      # tap reach, in phase rows/cols
    # padded extent: every tap of every tile (rows past out_h are computed
    # then dropped); input rows/cols no output reads are cropped
    R, C = s * (nt * tile_h + q), s * (out_w + q)
    xp = jnp.pad(x, ((pt, max(0, R - Hl - pt)), (pl_, max(0, C - Wl - pl_)),
                     (0, 0)))[:R, :C]
    xph = _phases(xp, s)                  # [s*s, R/s, C/s, cin]
    kernel = functools.partial(
        _shard_kernel, k=K, stride=s, tile_h=tile_h, out_w=out_w,
        cin=cin, cout=cout, depthwise=depthwise)
    out = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            # tile i's halo-extended window: phase rows
            # [i*tile_h, i*tile_h + tile_h + q), overlapping its neighbours
            pl.BlockSpec((pl.Element(s * s), pl.Element(tile_h + q),
                          pl.Element(C // s), pl.Element(cin)),
                         lambda i: (0, i * tile_h, 0, 0)),
            pl.BlockSpec(w.shape, lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_h, out_w, cout), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt * tile_h, out_w, cout), x.dtype),
        interpret=interpret_mode(interpret),
    )(xph, w)
    return out[:out_h]


def conv2d_tiled(x: jnp.ndarray, w: jnp.ndarray, *, padding: int = 0,
                 stride: int = 1, tile_h: int = 8,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Full-tensor convenience form: x [H, W, Cin] unpadded, symmetric
    ``padding``.  Thin wrapper over :func:`conv2d_shard` (a one-shard
    "plan"); kept as the historical public name."""
    return conv2d_shard(x, w, pads=(padding,) * 4, stride=stride,
                        tile_h=tile_h, interpret=interpret)
