"""Fused RMSNorm Pallas TPU kernel.

Single pass over a (row_block, D) VMEM tile: mean-of-squares reduction and
the normalise+scale stay fused — x is read from HBM once and y written once
(the unfused HLO does two passes).  Uses the Gemma convention
``y = x * rsqrt(mean x² + eps) * (1 + w)``.

Grid: (row_blocks,), parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # (rb, D)
    var = (x * x).mean(axis=-1, keepdims=True)
    w = w_ref[...].astype(jnp.float32)                    # (1, D)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * (1.0 + w)).astype(o_ref.dtype)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            row_block: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x: (M, D); w: (D,)."""
    M, D = x.shape
    row_block = min(row_block, M)
    Mp = -(-M // row_block) * row_block
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(Mp // row_block,),
        in_specs=[pl.BlockSpec((row_block, D), lambda i: (i, 0)),
                  pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((row_block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w[None, :])
    return out[:M]
