"""Fused gated-MLP Pallas TPU kernel (up-proj → activation → down-proj).

CELLO's MLP fusion group {up, act, down}: the (m_block × f_block) hidden tile
and the (m_block × D) output accumulator live in VMEM (explicit region); the
hidden activation tensor (tokens × d_ff — the largest activation in a
transformer block) never reaches HBM.  Weights stream through VMEM in
f_block-wide tiles (double-buffered by the Pallas pipeline), matching the
streamed-weight-tile feasibility rule in ``core.schedule``.

Grid: (m_blocks, f_blocks); f innermost & sequential — the accumulator in
VMEM scratch integrates partial down-projections across hidden tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(h: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return h * jax.nn.sigmoid(h)
    if kind == "gelu":
        return jax.nn.gelu(h, approximate=True)
    if kind == "relu2":
        r = jnp.maximum(h, 0.0)
        return r * r
    raise ValueError(kind)


def _mlp_kernel_gated(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_scr, *,
                      activation: str, f_blocks: int):
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)                    # (mb, D)
    g = jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)   # (mb, fb)
    u = jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    h = _act(g, activation) * u
    acc_scr[...] += jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(jf == f_blocks - 1)
    def _fin():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _mlp_kernel_plain(x_ref, wu_ref, wd_ref, o_ref, acc_scr, *,
                      activation: str, f_blocks: int):
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    h = _act(jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32), activation)
    acc_scr[...] += jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(jf == f_blocks - 1)
    def _fin():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def fused_mlp(x: jnp.ndarray, w_gate: Optional[jnp.ndarray],
              w_up: jnp.ndarray, w_down: jnp.ndarray, *,
              activation: str = "silu", m_block: int = 256,
              f_block: int = 512, interpret: bool = False) -> jnp.ndarray:
    """x: (M, D); w_gate/w_up: (D, F); w_down: (F, D). Returns (M, D)."""
    M, D = x.shape
    F = w_up.shape[1]
    m_block = min(m_block, M)
    f_block = min(f_block, F)
    Mp = -(-M // m_block) * m_block
    Fp = -(-F // f_block) * f_block
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Fp != F:
        pad_w = ((0, 0), (0, Fp - F))
        w_up = jnp.pad(w_up, pad_w)
        w_down = jnp.pad(w_down, ((0, Fp - F), (0, 0)))
        if w_gate is not None:
            w_gate = jnp.pad(w_gate, pad_w)
            # relu2/silu/gelu(0) = 0 ⇒ padded hidden cols contribute zero
    grid = (Mp // m_block, Fp // f_block)

    x_spec = pl.BlockSpec((m_block, D), lambda im, jf: (im, 0))
    wcol_spec = pl.BlockSpec((D, f_block), lambda im, jf: (0, jf))
    wrow_spec = pl.BlockSpec((f_block, D), lambda im, jf: (jf, 0))
    o_spec = pl.BlockSpec((m_block, D), lambda im, jf: (im, 0))
    scratch = [pltpu.VMEM((m_block, D), jnp.float32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    if w_gate is not None:
        kern = functools.partial(_mlp_kernel_gated, activation=activation,
                                 f_blocks=grid[1])
        out = pl.pallas_call(
            kern, grid=grid,
            in_specs=[x_spec, wcol_spec, wcol_spec, wrow_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, D), x.dtype),
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret,
        )(x, w_gate, w_up, w_down)
    else:
        kern = functools.partial(_mlp_kernel_plain, activation=activation,
                                 f_blocks=grid[1])
        out = pl.pallas_call(
            kern, grid=grid,
            in_specs=[x_spec, wcol_spec, wrow_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, D), x.dtype),
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret,
        )(x, w_up, w_down)
    return out[:M]
