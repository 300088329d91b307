"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel directory contains the ``pl.pallas_call`` implementation with
explicit BlockSpec VMEM tiling, an ``ops.py`` jitted wrapper, and a
``ref.py`` pure-jnp oracle. On this CPU container kernels run in
interpret mode (correctness); on TPU the same calls compile to Mosaic.

Kernel selection is the ``tick_impl`` axis (``registry.py``): one name —
``"jnp" | "pallas" | "pallas_interpret" | "auto"`` — threaded from
``run_sweep``/``SweepDriver``/the CLIs down to the kernels, replacing
the former per-function ``use_pallas``/``interpret`` booleans (removed
after their one-release deprecation window).

- ``carousel_update``: the paper's transfer-manager tick (its stated
  linear-scaling hot loop) vectorized for the MXU: per-link counts and
  table lookups become one-hot matmuls; transfers tile across VMEM
  blocks with sequential-grid accumulation.
- ``lane_tick``: the batched sweep engine's fused tick — the carousel
  transfer math + completion billing per site block, the shared-GCS
  first-fit admission gate (one sequential site-grid call per pass),
  and the K/W candidate-window prefix recurrences; lane-blocked
  via ``vmap`` (the batch axis becomes a leading grid dimension).
- ``flash_attention``: blocked online-softmax attention (128x128 MXU
  tiles, GQA-aware, causal + sliding-window masks).
- ``mamba_scan``: chunked selective-scan; the carry persists in a VMEM
  scratch across sequential time-chunk grid steps, emitting y (not h) to
  keep HBM traffic O(T x d_inner).

The model's jnp reference paths (``models.attention.attention_core``,
``models.ssm.ssm_scan_y``) mirror these kernels' chunked structures, so
the dry-run HLO is representative; on TPU the kernels additionally keep
chunk intermediates in VMEM (the EXPERIMENTS §Perf notes quantify where
the jnp chunked paths over-count HBM bytes relative to the kernels).
"""
