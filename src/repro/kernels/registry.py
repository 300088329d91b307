"""The name of the batched engine's one tick implementation.

The batched engine (``repro.sim.batched``) runs one tick program, in
``jax.numpy``. This module remains only because the chip benchmark
imports :func:`resolve_tick_impl` to name that program.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class TickImpl:
    """The resolved tick implementation: ``name`` is ``"jnp"``."""

    name: str


def resolve_tick_impl(name: str = "auto") -> TickImpl:
    """``"auto"`` or ``"jnp"`` names the one implementation, ``"jnp"``;
    any other name raises ``ValueError``."""
    if name not in ("auto", "jnp"):
        raise ValueError(f"unknown tick implementation {name!r} "
                         "(expected 'auto' or 'jnp')")
    return TickImpl("jnp")
