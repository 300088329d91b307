"""Kernel-selection registry: the ``tick_impl`` axis.

One name — ``"jnp" | "pallas" | "pallas_interpret" | "auto"`` — selects
how the batched tick engine (``repro.sim.batched``) and the carousel
tick wrapper (``repro.kernels.carousel_update.ops``) execute their hot
loop, replacing the scattered ``use_pallas``/``interpret`` booleans that
previously leaked through ``simulate_packed``/``run_sweep_jax``/
``carousel_tick``:

- ``"jnp"``: the pure-``jax.numpy`` program — the numerical oracle and
  the CPU fast path (scatter-free one-hot formulation; see
  ``repro.sim.batched``). Bitwise identical to the pre-registry default,
  so its cache fingerprint stays the legacy ``jax:<tick>`` key.
- ``"pallas"``: the fused lane-tick Pallas kernels
  (``repro.kernels.lane_tick``) compiled for the local accelerator
  (Mosaic on TPU, Triton on GPU). Requires an accelerator backend.
- ``"pallas_interpret"``: the same kernels in Pallas interpret mode —
  traced to regular XLA ops, so they run (slowly) on any backend. This
  is the CI-runnable parity path, not a performance mode.
- ``"auto"``: the jnp program, on every platform. Compiled Pallas is
  refused by Mosaic for the paper's two-site shapes (its ``(1, F)`` row
  blocks over ``[S, F]`` break the (8, 128) tiling rule), and no chip
  measurement shows it winning, so ``auto`` never selects it; an
  explicit ``"pallas"`` still compiles the kernels and fails loudly
  with Mosaic's error. ``auto`` never selects interpret mode either.

Naming note: ``tick_impl`` selects the *kernel implementation*; the
neighbouring ``tick=`` float on ``run_sweep``/``SweepDriver``/the CLIs
is the *clock step duration in seconds*. The two axes are independent
(``--tick 60 --tick-impl pallas_interpret`` is a coarse-clock interpret
run).

``jax`` is imported lazily — resolving a name never touches jax, so
jax-free flows (the process backend, cache keying) stay jax-free.
``on_accelerator`` probes the backend for callers that must know the
platform (the interpret default of bare kernel calls, and the sweep's
refusal to send accelerator work to CPU-pinned fleet workers).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from repro.obs.metrics import get_registry


@dataclass(frozen=True)
class TickImpl:
    """One resolved tick-engine implementation.

    ``use_kernel`` — run the fused Pallas kernels instead of the jnp
    program; ``interpret`` — trace those kernels in Pallas interpret
    mode rather than compiling them for the accelerator.
    """

    name: str
    use_kernel: bool
    interpret: bool


TICK_IMPLS = {
    "jnp": TickImpl("jnp", use_kernel=False, interpret=False),
    "pallas": TickImpl("pallas", use_kernel=True, interpret=False),
    "pallas_interpret": TickImpl("pallas_interpret", use_kernel=True,
                                 interpret=True),
}

#: Valid ``tick_impl=`` / ``--tick-impl`` values, resolution aliases
#: included (CLI ``choices=`` uses this tuple).
TICK_IMPL_CHOICES: Tuple[str, ...] = ("auto",) + tuple(TICK_IMPLS)


def _platform() -> str:
    """The active JAX backend platform (monkeypatch point for tests)."""
    import jax

    return jax.default_backend()


def on_accelerator() -> bool:
    """True when the default JAX backend is an accelerator (tpu/gpu)."""
    return _platform() in ("tpu", "gpu")


def default_tick_impl() -> str:
    """Resolve ``"auto"``: the jnp program on every platform, until a
    chip measurement shows a kernel implementation winning."""
    return "jnp"


def default_interpret() -> bool:
    """Backend-aware interpret default for bare kernel calls: compile on
    an accelerator, interpret elsewhere (the only way the kernel runs on
    CPU). Kernel entry points (``carousel_tick_pallas``, the
    ``lane_tick`` wrappers) use this when ``interpret`` is not given."""
    return not on_accelerator()


def resolve_tick_impl(name: Optional[str] = "auto") -> TickImpl:
    """Resolve a ``tick_impl`` name to its :class:`TickImpl` record.

    ``"auto"`` (or ``None``) resolves via :func:`default_tick_impl`; no
    name imports jax. Unknown names raise ``ValueError``.
    """
    if name is None:
        name = "auto"
    if isinstance(name, TickImpl):
        return name
    if isinstance(name, bool):
        raise ValueError(
            f"tick_impl={name!r} is a boolean — this looks like the "
            "removed use_pallas= flag landing in the tick_impl slot; "
            "use tick_impl="
            f"{'pallas_interpret' if name else 'jnp'!r} "
            "(or 'pallas' to compile the kernels on an accelerator)")
    requested = name
    if name == "auto":
        name = default_tick_impl()
    try:
        impl = TICK_IMPLS[name]
    except KeyError:
        raise ValueError(
            f"unknown tick_impl {name!r} "
            f"(expected one of {', '.join(TICK_IMPL_CHOICES)})") from None
    get_registry().inc("tick_impl.resolved",
                       help="tick_impl resolutions by resolved name",
                       impl=impl.name, requested=requested)
    return impl
