"""Pallas TPU flash attention (causal / sliding-window), MXU-aligned tiles.

TPU-native adaptation of the streaming-softmax algorithm: the score matrix
never leaves VMEM; q blocks of ``block_q`` rows stream over k/v blocks of
``block_k`` with the online max/sum rescaling.  Block shapes default to 128
— the MXU systolic dimension — and the kv stream is an in-kernel
``fori_loop`` so a q tile's working set is
``block_q*hd + 2*block_k*hd + block_q*block_k`` floats, comfortably inside
the ~16 MiB VMEM for hd <= 256.

Off the TPU both kernels run in interpret mode
(:func:`repro.kernels.mode.interpret_mode`); their dots contract at f32
precision (``Precision.HIGHEST``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import interpret_mode

NEG_INF = -1e30
_F32 = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                  window: Optional[int], block_q: int, block_k: int,
                  seq_len: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # [bq, hd]
    nk = seq_len // block_k

    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        k_idx = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = jnp.ones((block_q, block_k), bool)
        if causal:
            valid &= k_idx <= q_idx
        if window is not None:
            valid &= k_idx > q_idx - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    hd = q_ref.shape[-1]
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, hd), jnp.float32)
    # causal upper bound: kv blocks beyond the diagonal contribute nothing
    hi = nk if not causal else jnp.minimum(
        nk, ((qi + 1) * block_q + block_k - 1) // block_k)
    # sliding-window lower bound: block j is fully masked when its last key
    # (j+1)*block_k - 1 <= min_q - window, so start at the first block that
    # can reach the tile's earliest query
    lo = 0 if window is None else jnp.maximum(
        0, (qi * block_q - window) // block_k)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _decode_kernel(seq_ref, pt_ref, q_ref, kp_ref, vp_ref, o_ref, *,
                   scale: float, window: Optional[int], page_size: int):
    """Single-query (decode) attention over a paged KV cache.

    One grid step per batch*head.  The kv stream walks *logical* pages
    ``lo .. hi`` and maps each through the page table to its physical slot,
    so block skipping happens in logical page space: the sliding-window
    lower bound is floored to the page boundary containing the earliest
    live key (a mid-page start would read the wrong physical page — the
    table is per whole page), and the in-page positions outside the window
    or beyond ``kv_len`` are masked instead.
    """
    kv_len = seq_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale              # [1, hd]
    hd = q_ref.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)

    def body(j, carry):
        m, l, acc = carry
        phys = pt_ref[j]
        kb = kp_ref[0, phys].astype(jnp.float32)          # [ps, hd]
        vb = vp_ref[0, phys].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        k_idx = j * page_size + col                        # [1, ps]
        valid = k_idx < kv_len
        if window is not None:
            valid &= k_idx > kv_len - 1 - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((1,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1,), jnp.float32)
    a0 = jnp.zeros((1, hd), jnp.float32)
    hi = -(-kv_len // page_size)                           # occupied pages
    # window lower bound, floored to the containing page: the first logical
    # page holding key index kv_len - window (never past a page boundary)
    lo = 0 if window is None else jnp.maximum(
        0, (kv_len - window) // page_size)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, page_table: jnp.ndarray,
                       kv_len, *, window: Optional[int] = None,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode-step (``q_len == 1``) flash attention over a paged KV cache.

    ``q``: [BH, hd]; ``k_pages``/``v_pages``: [BH, n_phys_pages, page_size,
    hd] physical page pool; ``page_table``: [n_logical_pages] int32 mapping
    logical page ``i`` (keys ``i*ps .. (i+1)*ps - 1``) to its physical
    slot; ``kv_len``: number of live keys (traced — the compiled program is
    reused as the sequence grows).  Pages beyond ``ceil(kv_len/ps)`` are
    never touched, so the table may contain garbage there.
    """
    BH, n_pages, page_size, hd = k_pages.shape
    assert q.shape == (BH, hd), (q.shape, k_pages.shape)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               page_size=page_size)
    # q and the output travel as [BH, 1, hd] so each step's (1, hd) block
    # spans the array's last two dims, as the TPU's (8, 128) tiling needs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, seq, pt: (b, 0, 0)),
            pl.BlockSpec((1, n_pages, page_size, hd),
                         lambda b, seq, pt: (b, 0, 0, 0)),
            pl.BlockSpec((1, n_pages, page_size, hd),
                         lambda b, seq, pt: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, seq, pt: (b, 0, 0)),
    )
    seq = jnp.asarray([kv_len], jnp.int32)
    pt = jnp.asarray(page_table, jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(seq, pt, q.reshape(BH, 1, hd), k_pages, v_pages)
    return out.reshape(BH, hd)


def flash_attention_bh(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                       causal: bool = True, window: Optional[int] = None,
                       scale: Optional[float] = None, block_q: int = 128,
                       block_k: int = 128,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """q/k/v: [BH, S, hd]; S must be a multiple of the block sizes (the
    public wrapper in ops.py pads)."""
    BH, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    grid = (BH, S // block_q)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_len=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(q, k, v)
