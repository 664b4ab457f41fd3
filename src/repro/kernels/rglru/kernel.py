"""RG-LRU recurrence Pallas TPU kernel.

The recurrence is sequential in time — CELLO marks it ``scan`` (unfusable
with neighbouring matmuls) and gives it a dedicated kernel whose *state* is
the explicit-buffer resident: h (B-tile × D-tile, f32) lives in VMEM scratch
across the whole time loop and is written to HBM exactly once at the end.

Grid: (batch, d_blocks) — both parallel (channels are independent; the
sequential dependency is the in-kernel fori_loop over time).  Inputs stream
as (1, S, d_block) VMEM tiles.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import RGLRU_C


def _rglru_kernel(x_ref, gr_ref, gi_ref, ap_ref, h0_ref, y_ref, hT_ref,
                  h_scr, *, seq_len: int):
    h_scr[...] = h0_ref[...].astype(jnp.float32)          # (1, db)
    a_param = ap_ref[...].astype(jnp.float32)             # (1, db)
    log_a_coef = -RGLRU_C * jax.nn.softplus(a_param)

    def step(t, _):
        x = x_ref[0, t, :].astype(jnp.float32)[None, :]
        r = jax.nn.sigmoid(gr_ref[0, t, :].astype(jnp.float32))[None, :]
        i = jax.nn.sigmoid(gi_ref[0, t, :].astype(jnp.float32))[None, :]
        a = jnp.exp(log_a_coef * r)
        beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
        h = a * h_scr[...] + beta * (i * x)
        h_scr[...] = h
        y_ref[0, t, :] = h[0].astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, seq_len, step, ())
    hT_ref[...] = h_scr[...].astype(hT_ref.dtype)


def rglru(x: jnp.ndarray, gate_r: jnp.ndarray, gate_i: jnp.ndarray,
          a_param: jnp.ndarray, h0: Optional[jnp.ndarray] = None, *,
          d_block: int = 512, interpret: bool = False
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, gate_r, gate_i: (B,S,D); a_param: (D,); h0: (B,D). -> (y, hT)."""
    B, S, D = x.shape
    d_block = min(d_block, D)
    Dp = -(-D // d_block) * d_block
    if Dp != D:
        pad3 = ((0, 0), (0, 0), (0, Dp - D))
        x, gate_r, gate_i = (jnp.pad(t, pad3) for t in (x, gate_r, gate_i))
        a_param = jnp.pad(a_param, (0, Dp - D))
    if h0 is None:
        h0 = jnp.zeros((B, Dp), jnp.float32)
    elif Dp != D:
        h0 = jnp.pad(h0, ((0, 0), (0, Dp - D)))
    ap2 = a_param[None, :]                                 # (1, Dp)

    grid = (B, Dp // d_block)
    seq_spec = pl.BlockSpec((1, S, d_block), lambda b, j: (b, 0, j))
    vec_spec = pl.BlockSpec((1, d_block), lambda b, j: (0, j))
    state_spec = pl.BlockSpec((1, d_block), lambda b, j: (b, j))

    y, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, seq_len=S),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, vec_spec, state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, Dp), x.dtype),
                   jax.ShapeDtypeStruct((B, Dp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, d_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, gate_r, gate_i, ap2, h0)
    return y[:, :, :D], hT[:, :D]
