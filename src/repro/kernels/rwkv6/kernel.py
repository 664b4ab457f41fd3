"""WKV6 recurrence Pallas TPU kernel (RWKV-6 / Finch time-mix core).

CELLO treatment: the per-head (E × E) f32 state matrix is the explicit-
buffer resident — it lives in VMEM scratch for the whole sequence and hits
HBM exactly twice (initial load, final store).  r/k/v/decay stream through
VMEM in (S, E) tiles.  E = 64 for all RWKV-6 sizes, so the state tile is
16 KiB — VREG/VMEM friendly; the sequential fori_loop over time is the
TPU-native replacement for the CUDA per-warp scan in the reference
implementations (documented hardware adaptation).

Grid: (batch, heads), both parallel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                 s_scr, *, seq_len: int):
    s_scr[...] = s0_ref[0, 0].astype(jnp.float32)          # (E, E)
    u = u_ref[0].astype(jnp.float32)                       # (E,)

    def step(t, _):
        rt = r_ref[0, 0, t, :].astype(jnp.float32)         # (E,)
        kt = k_ref[0, 0, t, :].astype(jnp.float32)
        vt = v_ref[0, 0, t, :].astype(jnp.float32)
        dt = jnp.exp(-jnp.exp(w_ref[0, 0, t, :].astype(jnp.float32)))
        s = s_scr[...]
        kv = kt[:, None] * vt[None, :]                     # (E, E)
        y = ((s + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        s_scr[...] = dt[:, None] * s + kv
        y_ref[0, 0, t, :] = y.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, seq_len, step, ())
    sT_ref[0, 0] = s_scr[...].astype(sT_ref.dtype)


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
         u: jnp.ndarray, s0: Optional[jnp.ndarray] = None, *,
         interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,w: (B,H,S,E); u: (H,E); s0: (B,H,E,E). -> (y, sT)."""
    B, H, S, E = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, E, E), jnp.float32)
    grid = (B, H)
    seq_spec = pl.BlockSpec((1, 1, S, E), lambda b, h: (b, h, 0, 0))
    u_spec = pl.BlockSpec((1, E), lambda b, h: (h, 0))
    s_spec = pl.BlockSpec((1, 1, E, E), lambda b, h: (b, h, 0, 0))

    y, sT = pl.pallas_call(
        functools.partial(_wkv6_kernel, seq_len=S),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec, s_spec],
        out_specs=[seq_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, E), r.dtype),
                   jax.ShapeDtypeStruct((B, H, E, E), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((E, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, sT
