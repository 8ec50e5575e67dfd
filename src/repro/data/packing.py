"""Sequence packing + the compaction merge_fn for token shards.

``merge_shards_fn`` is what AutoComp's Act phase calls when the candidate is
a token-shard table: it concatenates the chunk-aligned payloads of the input
shards and runs the compact_pack Pallas kernel to produce the merged shard —
the measured RewriteBytesPerHour of this path calibrates the GBHr cost trait.

With ``filter_fn`` it becomes a rewrite-delete: deletes applied AT
compaction time, in the same pass, via the fused filter+pack kernel
(``compact_chunks(..., keep_mask=)``) — dropped rows never round-trip
through a second read. ``fused_filter=False`` routes the identical mask
through the two-pass filter-then-pack reference instead; the outputs are
bit-identical, only the HBM traffic differs.

On the host each byte moves as few times as the device path allows: the
inputs are read as views over the store's bytes and copied once, into
the upload's concatenate; the merged shard is framed from views of the
read-back in one join, so each output byte is written once.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.data import shards as sh
from repro.kernels.compact_pack import compact_chunks, plan_compaction
from repro.kernels.compact_pack.compact_pack import (
    CHUNK_COLS, CHUNK_ROWS, CHUNK_TOKENS)
from repro.lst.compaction import CompactionTask
from repro.lst.files import DataFile
from repro.lst.table import LogStructuredTable
from repro.spans import (MERGE_CONCAT, MERGE_DEVICE, MERGE_ENCODE,
                         MERGE_FILTER, MERGE_READ, MERGE_RESLICE,
                         MERGE_SHARDS, MERGE_STORE, span)


def pack_tokens(stream: np.ndarray, batch: int, seq_len: int) -> np.ndarray:
    """Pack a flat token stream into (n_batches, batch, seq_len+1) slabs
    (the +1 provides next-token labels)."""
    per = batch * (seq_len + 1)
    n = stream.shape[0] // per
    return stream[: n * per].reshape(n, batch, seq_len + 1)


def valid_row_mask(counts: Sequence[int], lengths: Sequence[int]
                   ) -> np.ndarray:
    """Which 128-token rows of the padded, fragment-concatenated stream
    hold real tokens: fragment i occupies counts[i] chunks; its first
    ceil(lengths[i] / 128) rows are content, the rest padding."""
    total = sum(counts) * CHUNK_ROWS
    valid = np.zeros(total, bool)
    row0 = 0
    for c, ln in zip(counts, lengths):
        valid[row0: row0 + -(-ln // CHUNK_COLS)] = True
        row0 += c * CHUNK_ROWS
    return valid


def merge_shards_fn(table: LogStructuredTable, task: CompactionTask,
                    out_path: str,
                    filter_fn: Optional[Callable] = None,
                    fused_filter: bool = True
                    ) -> Union[DataFile, Tuple[DataFile, int]]:
    """Compaction merge for token shards (kernel-backed).

    ``filter_fn(rows, task) -> keep`` makes the merge a rewrite-delete at
    128-token-row granularity: ``rows`` is the (n_rows, 128) view of the
    packed stream, ``keep`` a bool mask over it. Padding rows (beyond each
    fragment's true length) are dropped regardless of the mask, so a
    filtered merge also squeezes out inter-fragment padding; a partially
    valid boundary row that the mask keeps is kept verbatim, trailing pad
    included. Returns (DataFile, rows_dropped) — dropped counts only
    content rows the FILTER removed, not padding.

    The inputs are views over the stored ``bytes`` (``decode_shard_padded``,
    lengths from the headers); the output is ``shards.frame_shard`` of the
    read-back's live slices (each fragment's true length, or the kept
    prefix when filtered), one join that writes each output byte once.
    """
    with span(MERGE_SHARDS, inputs=len(task.inputs),
              input_bytes=int(task.input_bytes)):
        with span(MERGE_READ):
            payloads = []
            lengths = []
            for f in task.inputs:
                raw = table.store.get(f.path)
                payloads.append(sh.decode_shard_padded(raw))
                lengths.append(sh.shard_length(raw))
        with span(MERGE_CONCAT):
            flat = np.concatenate(payloads) if payloads \
                else np.zeros(0, np.int32)
            counts = [p.shape[0] // CHUNK_TOKENS for p in payloads]
            chunk_map = plan_compaction(counts)

        if filter_fn is not None:
            # merge_shards_fn plans fragments in input order, so the packed
            # stream IS the concatenated stream and the row views coincide.
            with span(MERGE_FILTER):
                rows = flat.reshape(-1, CHUNK_COLS) if flat.size else \
                    np.zeros((0, CHUNK_COLS), np.int32)
                valid = valid_row_mask(counts, lengths)
                keep = np.asarray(filter_fn(rows, task), bool).reshape(-1) \
                    & valid
            with span(MERGE_DEVICE):
                merged = np.asarray(compact_chunks(
                    jnp.asarray(flat), chunk_map, use_ref=not fused_filter,
                    keep_mask=keep))
            n_kept = int(keep.sum()) * CHUNK_COLS
            with span(MERGE_ENCODE):
                raw = sh.frame_shard([merged[:n_kept]])
            with span(MERGE_STORE):
                table.store.put(out_path, raw)
            out = DataFile(path=out_path, size_bytes=len(raw),
                           num_rows=n_kept, partition=task.scope,
                           created_at=table.now_fn())
            return out, int(valid.sum() - keep.sum())

        with span(MERGE_DEVICE):
            merged = np.asarray(compact_chunks(jnp.asarray(flat), chunk_map))
        # each fragment's live tokens, as views of the read-back: the
        # inter-fragment padding is dropped by framing only these
        with span(MERGE_RESLICE):
            starts = np.cumsum([0] + counts[:-1]) * CHUNK_TOKENS
            live = [merged[s: s + n] for s, n in zip(starts, lengths)]
        with span(MERGE_ENCODE):
            raw = sh.frame_shard(live)
        with span(MERGE_STORE):
            table.store.put(out_path, raw)
        return DataFile(path=out_path, size_bytes=len(raw),
                        num_rows=sum(lengths), partition=task.scope,
                        created_at=table.now_fn())
