"""Fused lane-blocked Pallas kernels for the batched sweep tick.

See ``lane_tick.py`` for the kernel design notes. Public wrappers:

- :func:`transfer_tick` — carousel transfer advance + completion
  classification + month-bucketed billing, fused per site block;
- :func:`gcs_admit` — the shared-GCS first-fit admission gate: the
  jnp program's loop (``repro.sim.batched._gcs_first_fit``) with one
  sequential site-grid call as each pass, fused with the GB-second
  storage integration;
- :func:`window_admit` — the [S, K]/[S, W] candidate-window prefix
  recurrences (non-blocking job window, strict-FIFO wait queue).
"""

from repro.kernels.lane_tick.lane_tick import (  # noqa: F401
    gcs_admit,
    transfer_tick,
    window_admit,
)
