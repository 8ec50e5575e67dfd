"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel subpackage ships three modules:
  <name>.py -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- thin public wrapper over the tunable-op registry (api.py)
  ref.py    -- pure-jnp oracle used by the allclose/bit-match test sweeps

Shared surface (see kernels/README.md):
  api.py   -- tunable-op registry: axes + defaults + clamp + ref per op,
              one dispatch (`api.call`) replacing the four copy-pasted
              interpret/use_ref entry points
  tuned.py -- persisted tuned-point cache (experiments/tuned/, JSON,
              keyed op|shape_key with a device-kind guard)
  tune.py  -- block/grid sweep harness driving core.autotune.tune_design
              over any registered op

Kernels:
  compact_pack -- chunk-aligned token-run compaction (the AutoComp rewrite
                  inner loop adapted to TPU: scalar-prefetched DMA gather)
                  + fused filter+pack (rewrite-deletes-as-compaction)
  flash_attn   -- causal GQA flash attention (training/prefill)
  decode_attn  -- flash-decode over a KV cache (single-token serving)
  rmsnorm      -- fused RMSNorm
"""
