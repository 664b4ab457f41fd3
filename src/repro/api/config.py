"""Typed configuration objects for the public API (0.10).

Three frozen dataclasses consolidate the keyword sprawl that grew on
``Session.codesign``, ``Session.lower`` / ``CompiledPlan.run`` /
``CompiledPlan.batched``, and ``Server``:

* :class:`CodesignConfig` — the schedule × buffer search knobs.
* :class:`ExecConfig` — lowering/execution: backend, device mesh,
  buffer donation.
* :class:`ServeConfig` — batching, admission control, and resilience
  (retry / fallback / circuit breaker) for :class:`repro.serve.Server`.

Every legacy keyword keeps working for one release through a single
normalization shim (:func:`resolve_config`): passing the old kwargs
emits a :class:`DeprecationWarning` and builds the equivalent config;
passing *both* a config and legacy kwargs is a :class:`TypeError`
(there is no sensible merge order).  ``docs/api_migration.md`` maps
every old name to its new field.

``ExecConfig.donate`` deserves a note: the pallas executor reads the
process-level toggle ``CELLO_PALLAS_DONATE`` when it builds a program, so
the field *pins the process-level toggle* when set (a programmatic
spelling of the env var, applied at ``lower()`` / ``run()`` time) rather
than acting per-plan; it additionally flows per-plan into
``CompiledPlan.batched``, which already threads an explicit donation
flag.  Whether kernels compile through Mosaic or run interpreted is not
a knob: it follows the platform (``repro.exec.pallas.use_interpret``).
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple, Union

from ..core.search import DEFAULT_SPLITS

__all__ = [
    "CodesignConfig", "ExecConfig", "ServeConfig",
    "UNSET", "resolve_config",
]


class _Unset:
    """Sentinel for 'keyword not passed' (``None`` is a meaningful value
    for several legacy keywords, e.g. ``Server(breaker_failures=None)``
    disables the breaker while omitting it keeps the default)."""

    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self) -> str:
        return "<unset>"

    def __bool__(self) -> bool:
        return False


UNSET = _Unset()


@dataclasses.dataclass(frozen=True)
class CodesignConfig:
    """Knobs of the joint schedule × buffer search
    (``Session.codesign``).

    Field-for-field the old keyword surface: ``strategy`` (registered
    name or strategy instance), ``capacity_bytes`` (None → session
    capacity), ``max_orders``, ``splits`` (explicit/implicit boundary
    candidates), ``overbook`` (fractional pin spill for sparse
    operands), ``use_cache`` (None → session default).
    """
    strategy: Any = "default"
    capacity_bytes: Optional[int] = None
    max_orders: int = 16
    splits: Sequence[float] = DEFAULT_SPLITS
    overbook: float = 0.0
    use_cache: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Lowering/execution knobs (``Session.lower``,
    ``CompiledPlan.run`` / ``batched``).

    ``backend`` — any name registered in ``repro.exec`` (None keeps
    the surface's default).  ``mesh`` — shard count ``K`` or
    ``(axis_name, K)``; partitions the co-designed DAG across the
    first ``K`` devices (see ``docs/distributed.md``).  ``donate`` —
    pins the ``CELLO_PALLAS_DONATE`` process toggle when not None (see
    the module docstring; donation is additionally honoured per-plan by
    ``batched``).
    """
    backend: Optional[str] = None
    mesh: Optional[Union[int, Tuple[str, int]]] = None
    donate: Optional[bool] = None

    def apply_toggles(self) -> None:
        """Pin the process-level pallas toggle this config sets."""
        if self.donate is not None:
            os.environ["CELLO_PALLAS_DONATE"] = \
                "1" if self.donate else "0"


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Batching + admission + resilience knobs of
    :class:`repro.serve.Server`.

    ``retry`` takes a :class:`repro.serve.RetryPolicy`;
    ``fallback`` names a backend that answers when the primary keeps
    failing (``"reference"``: the op-by-op oracle; ``None``, the
    default, serves no answer the caller did not ask for);
    ``breaker_failures=None`` disables the circuit breaker.
    """
    max_batch_size: int = 16
    max_wait_us: float = 2000.0
    max_plans: int = 8
    autostart: bool = True
    policy: str = "oldest"
    max_queue: Optional[int] = None
    overload: str = "block"
    retry: Optional[Any] = None
    fallback: Optional[str] = None
    breaker_failures: Optional[int] = 3
    breaker_reset_s: float = 30.0
    max_worker_restarts: int = 2


def resolve_config(cls, config, legacy: Dict[str, Any], where: str):
    """Normalize ``(config=, **legacy kwargs)`` to one config instance.

    The single deprecation shim behind every config-accepting surface:
    legacy kwargs still passed (values ``is not UNSET``) build the
    equivalent config with a :class:`DeprecationWarning`; mixing them
    with an explicit ``config=`` raises (no merge order is obvious);
    neither given returns ``cls()`` defaults.
    """
    given = {k: v for k, v in legacy.items() if v is not UNSET}
    if config is not None:
        if given:
            raise TypeError(
                f"{where}: pass either config= or the legacy keyword(s) "
                f"{sorted(given)}, not both")
        if not isinstance(config, cls):
            raise TypeError(f"{where}: config= takes a {cls.__name__}, "
                            f"got {type(config).__name__}")
        return config
    if given:
        warnings.warn(
            f"{where}: keyword argument(s) {sorted(given)} are deprecated "
            f"since 0.10 and will be removed in 0.11; pass "
            f"config={cls.__name__}(...) instead "
            f"(see docs/api_migration.md)",
            DeprecationWarning, stacklevel=3)
        return dataclasses.replace(cls(), **given)
    return cls()
