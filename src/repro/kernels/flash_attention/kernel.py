"""Flash attention Pallas TPU kernel — the CELLO "explicit buffer" for the
attention fusion group.

The schedule's fusion group {scores, softmax, pv} lowers to this kernel: the
(q_block × kv_block) score tile, the running softmax statistics and the
output accumulator live in VMEM scratch (the explicit region); K/V stream
through VMEM tile-by-tile.  The score matrix never materialises in HBM —
exactly the traffic the hybrid-buffer simulator credits to this fusion group.

Grid: (batch, heads, q_blocks, kv_blocks); kv is innermost and sequential
("arbitrary") so VMEM scratch accumulates across kv tiles; the outer three
axes are parallel.  GQA is handled in the K/V BlockSpec index maps
(h → h * KVH // H), so repeated K/V never moves through HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_block: int, kv_block: int, kv_blocks: int,
                  q_offset: int, t_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level early-out for fully-masked tiles (saves MXU work)
    needed = ik * kv_block < t_valid
    if causal:
        needed = jnp.logical_and(
            needed, ik * kv_block <= iq * q_block + q_offset + q_block - 1)
    if window is not None:
        needed = jnp.logical_and(
            needed, (ik + 1) * kv_block > iq * q_block + q_offset - window + 1)

    @pl.when(needed)
    def _compute():
        # absolute positions (queries offset when T != S: decode/extension)
        q_pos = iq * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 0) + q_offset
        k_pos = ik * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 1)

        q = q_ref[0, 0].astype(jnp.float32) * scale       # (qb, E)
        k = k_ref[0, 0].astype(jnp.float32)               # (kb, E)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = k_pos < t_valid                            # kv padding
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (qb, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)               # (kb, E)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    q_block: int = 512, kv_block: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused attention. q: (B,H,S,E); k,v: (B,KVH,T,E). Returns (B,H,S,E)."""
    B, H, S, E = q.shape
    KVH, T = k.shape[1], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    scale = scale if scale is not None else E ** -0.5
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    Sp = -(-S // q_block) * q_block
    Tp = -(-T // kv_block) * kv_block
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    grid = (B, H, Sp // q_block, Tp // kv_block)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, kv_blocks=grid[3],
        q_offset=T - S, t_valid=T)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, E),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, kv_block, E),
                         lambda b, h, iq, ik: (b, h * KVH // H, ik, 0)),
            pl.BlockSpec((1, 1, kv_block, E),
                         lambda b, h, iq, ik: (b, h * KVH // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, E),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, E), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),       # running max
            pltpu.VMEM((q_block, 1), jnp.float32),       # running denom
            pltpu.VMEM((q_block, E), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S, :]
