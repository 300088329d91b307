"""Pallas TPU kernels of the seed model stack (``repro.models``).

Each kernel directory contains the ``pl.pallas_call`` implementation with
explicit BlockSpec VMEM tiling, an ``ops.py`` jitted wrapper, and a
``ref.py`` pure-jnp oracle. On this CPU container kernels run in
interpret mode (correctness); on TPU the same calls compile to Mosaic.

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

The batched sweep engine uses none of them: its tick is one
``jax.numpy`` program (``repro.sim.batched``). ``registry.py`` only names
that program for the chip benchmark.
"""
