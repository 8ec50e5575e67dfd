"""Token-run compaction kernels — the AutoComp rewrite inner loop on TPU.

Hardware adaptation (DESIGN.md §2): the Spark executor's file-rewrite loop
(read many small fragments, emit few target-size files) becomes a
scalar-prefetched DMA gather. Token shards are written 128x8-aligned
(CHUNK = 1024 tokens = an (8, 128) int32 VMEM tile), so compacting F
fragments into dense output blocks is a *permutation of aligned chunks*:
no compute, pure data movement — exactly what the TPU DMA engine does well.

Two kernels:

``compact_chunks_kernel`` — the plain gather. The chunk index map rides in
scalar-prefetch SMEM (PrefetchScalarGridSpec); the BlockSpec index_map
dereferences it, so the Pallas pipeline issues the HBM->VMEM->HBM copies
with double buffering. The kernel body is a single VMEM tile copy. The
DMA granularity is tunable: when the plan is runs of consecutive chunks
(fragments usually are), the wrapper coarsens ``block_chunks`` chunks into
one block — fewer, larger copies, the data-movement knob the LSM
compaction design-space work (arXiv:2202.04522) identifies as dominant.

``compact_filter_kernel`` — the fused filter+pack variant (rewrite-deletes
as compaction: a rewrite that drops rows IS a compaction with a filter).
Filtering happens at 128-token row granularity in ONE pass: the grid walks
the *touched* source chunks in plan order (fully-dropped chunks are never
DMA'd), each kept row is scattered into a 16-row staging window at a
host-precomputed destination slot (scalar-prefetched, derived from the
per-chunk keep counts' prefix sums), and a carry tile in VMEM holds the
<8 rows that straddle an output-chunk boundary. Dropped rows never
round-trip through VMEM twice — the unfused path writes every row then
re-reads all of them to filter.

Bounded tables: scalar-prefetch tables live whole in SMEM, so one
``pallas_call`` over a deployment-size plan (a 512 MiB Iceberg target file
is 131 072 chunks) would not fit. Both kernels therefore run their grid
in consecutive *segments* whose tables fit ``TABLE_BYTES``; every segment
writes into the same output buffer (input/output aliasing, so no segment
output is ever concatenated or copied), and the fused kernel hands its
carry tile from one segment to the next.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_ROWS = 8
CHUNK_COLS = 128
CHUNK_TOKENS = CHUNK_ROWS * CHUNK_COLS  # 1024

# destination-slot sentinel for dropped rows: never matches the 16-slot
# staging window iota, so the scatter contributes exact zeros
DROP_SLOT = 127

# SMEM per TensorCore on v4, v5e, v5p and v6e
# (pltpu.get_tpu_info().smem_capacity_bytes). A kernel call's scalar-
# prefetch tables get half of it; the rest stays with Mosaic's own scalars.
SMEM_CAPACITY_BYTES = 1 << 20
TABLE_BYTES = SMEM_CAPACITY_BYTES // 2
# grid steps per segment: the gather keeps one int32 per output block, the
# fused filter chunk_sel + completed + out_idx + CHUNK_ROWS dest slots
GATHER_SEGMENT = TABLE_BYTES // 4
FILTER_SEGMENT = TABLE_BYTES // (4 * (3 + CHUNK_ROWS))


def _segments(n: int, segment: int):
    return [(a, min(a + segment, n)) for a in range(0, n, segment)]


def _copy_kernel(idx_ref, src_ref, *refs):
    del idx_ref  # consumed by the BlockSpec index maps
    refs[-1][...] = src_ref[...]   # refs = ([aliased prev output,] out)


def compact_chunks_kernel(src: jnp.ndarray, chunk_map: jnp.ndarray,
                          interpret: bool = False,
                          segment: int = GATHER_SEGMENT) -> jnp.ndarray:
    """Gather blocks of ``src`` according to ``chunk_map``.

    src: (n_src_blocks, rows, CHUNK_COLS) any dtype — ``rows`` is
        CHUNK_ROWS for the plain per-chunk gather, or a multiple of it
        when the wrapper coarsened the plan (block_chunks > 1)
    chunk_map: (n_out_blocks,) int32 -- source block id per output block
    segment: output blocks per ``pallas_call`` (SMEM-bounded)
    returns (n_out_blocks, rows, CHUNK_COLS)
    """
    n_out = chunk_map.shape[0]
    rows = src.shape[1]
    block = (1, rows, CHUNK_COLS)
    out_shape = jax.ShapeDtypeStruct((n_out, rows, CHUNK_COLS), src.dtype)
    out = jnp.zeros(out_shape.shape, out_shape.dtype) if n_out == 0 else None
    for a, b in _segments(n_out, segment):
        in_specs = [pl.BlockSpec(block, lambda i, idx_ref: (idx_ref[i], 0, 0))]
        operands = [chunk_map[a:b], src]
        if out is not None:          # earlier segments' blocks stay put
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(out)
        out = pl.pallas_call(
            _copy_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b - a,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    block, lambda i, idx_ref, a=a: (a + i, 0, 0))),
            out_shape=out_shape,
            input_output_aliases={2: 0} if len(operands) == 3 else {},
            interpret=interpret,
        )(*operands)
    return out


def _filter_kernel(chunk_sel_ref, dest_ref, completed_ref, out_idx_ref,
                   carry_in_ref, src_ref, *refs):
    """One touched source chunk per step, sequential grid.

    The staging window W is 16 rows: slots 0..7 are the output chunk
    currently being assembled, 8..15 spill into the carry. Row j of the
    loaded tile goes to slot dest[8*i + j] (host-precomputed from the
    keep-count prefix sums; DROP_SLOT for dropped rows, which therefore
    contribute exact zeros and never reach the output). When this step
    completes an output chunk (completed[i]), W[:8] is final for out block
    out_idx[i] and W[8:] shifts down into the carry; otherwise everything
    still lives in W[:8] and carries forward. o_ref is written every step
    — Pallas flushes the block when out_idx advances, so the last write
    at each index wins, and the final partial chunk flushes at grid end
    zero-padded (the carry invariant keeps slots >= the fill level zero).

    The carry is an output block with a constant index, so it stays
    resident in VMEM across the grid and is written back once at the end
    — the next segment's ``carry_in``.
    """
    del chunk_sel_ref, out_idx_ref      # consumed by the BlockSpec maps
    out_ref, carry_ref = refs[-2], refs[-1]   # after [aliased prev output]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = carry_in_ref[...]

    tile = src_ref[0]                                   # (8, 128)
    window = jnp.concatenate(
        [carry_ref[...], jnp.zeros_like(carry_ref)], axis=0)   # (16, 128)
    slot_iota = jax.lax.broadcasted_iota(
        jnp.int32, (2 * CHUNK_ROWS, 1), 0)
    for j in range(CHUNK_ROWS):
        dest = dest_ref[i * CHUNK_ROWS + j]
        row = tile[j:j + 1, :]                          # (1, 128)
        window = window + jnp.where(slot_iota == dest,
                                    jnp.broadcast_to(row, window.shape),
                                    jnp.zeros_like(window))
    out_ref[0] = window[:CHUNK_ROWS].astype(out_ref.dtype)
    carry_ref[...] = jnp.where(completed_ref[i] > 0,
                               window[CHUNK_ROWS:], window[:CHUNK_ROWS])


def compact_filter_kernel(src: jnp.ndarray, chunk_sel: jnp.ndarray,
                          dest: jnp.ndarray, completed: jnp.ndarray,
                          out_idx: jnp.ndarray, n_out: int,
                          interpret: bool = False,
                          segment: int = FILTER_SEGMENT) -> jnp.ndarray:
    """Fused filter+pack over touched chunks (see ``_filter_kernel``).

    src: (n_src_chunks, CHUNK_ROWS, CHUNK_COLS)
    chunk_sel: (n_touched,) int32 -- source chunk per grid step, plan order
    dest: (n_touched * CHUNK_ROWS,) int32 -- staging slot per source row
        (0..15, or DROP_SLOT for dropped rows)
    completed: (n_touched,) int32 -- 1 iff this step completes an output
        chunk (the step's kept rows cross an 8-row boundary)
    out_idx: (n_touched,) int32 -- output chunk being assembled at step i
    segment: grid steps per ``pallas_call`` (SMEM-bounded)
    returns (n_out, CHUNK_ROWS, CHUNK_COLS), final chunk zero-padded
    """
    tile = (CHUNK_ROWS, CHUNK_COLS)
    block = (1,) + tile
    out_shape = (jax.ShapeDtypeStruct((n_out,) + tile, src.dtype),
                 jax.ShapeDtypeStruct(tile, src.dtype))
    n_steps = chunk_sel.shape[0]
    out = jnp.zeros(out_shape[0].shape, src.dtype) if n_steps == 0 else None
    carry = jnp.zeros(tile, src.dtype)
    for a, b in _segments(n_steps, segment):
        in_specs = [pl.BlockSpec(tile, lambda i, *_: (0, 0)),
                    pl.BlockSpec(block, lambda i, cs, *_: (cs[i], 0, 0))]
        operands = [chunk_sel[a:b], dest[a * CHUNK_ROWS:b * CHUNK_ROWS],
                    completed[a:b], out_idx[a:b], carry, src]
        if out is not None:          # earlier segments' blocks stay put
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(out)
        out, carry = pl.pallas_call(
            _filter_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(b - a,),
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec(block, lambda i, cs, d, cf, oi:
                                 (oi[i], 0, 0)),
                    pl.BlockSpec(tile, lambda i, *_: (0, 0))]),
            out_shape=out_shape,
            input_output_aliases={6: 0} if len(operands) == 7 else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),   # carry crosses steps
            interpret=interpret,
        )(*operands)
    return out
