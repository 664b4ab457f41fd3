"""Keys and right-hand sides of a cell, made from the seed.

The operator of a cell is made by its kind's file, ``operators/<kind>.py``
(found by :func:`cells.operator_kind`); this module holds what every kind
and driver shares: keys drawn from the whole seed, and the right-hand
sides, on the device for a cell of solves and on the host for clients of
the solver service.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, Mapping

import numpy as np


def seed_words(seed: int, stream: str) -> np.ndarray:
    """Two 32-bit words from the whole seed (any size or sign) and a
    stream name, so that distinct seeds and streams give distinct keys."""
    tag = int.from_bytes(hashlib.sha256(stream.encode()).digest()[:4],
                         "little")
    ss = np.random.SeedSequence([seed % (1 << 64), tag])
    return ss.generate_state(2, np.uint32)


def device_key(seed: int, stream: str):
    import jax
    import jax.numpy as jnp
    return jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, stream), jnp.uint32),
        impl="threefry2x32")


def host_rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream))


@functools.lru_cache(maxsize=None)
def _rhs_builder(count: int, n: int, dtype: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        return jax.random.normal(key, (count, n), jnp.float32).astype(dtype)
    return build


def device_rhs(config: Mapping[str, Any], seed: int, count: int):
    """``count`` standard normal right-hand sides on the device, as a list
    of ``(n,)`` arrays."""
    n = int(config["params"]["n"])
    stacked = _rhs_builder(count, n, config["dtype"])(
        device_key(seed, "rhs"))
    return list(stacked)


def host_rhs(config: Mapping[str, Any], seed: int, count: int) -> np.ndarray:
    """``count`` standard normal right-hand sides on the host, ``(count,
    n)``: what clients of the solver service send."""
    n = int(config["params"]["n"])
    return host_rng(seed, "rhs").standard_normal(
        (count, n), dtype=np.float32).astype(config["dtype"])
