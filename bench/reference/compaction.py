"""Plain reference of bin-pack compaction and rewrite-delete on a table of
token shards. It imports nothing of the program.

A token shard is the table's data file format: ``b"TOKS"``, the true
token count as a little-endian int64, then the int32 tokens padded with
zeros to a multiple of 1024. Compaction is first-fit-decreasing bin
packing of the files under the target size (ties keep the table's
order; a bin is rewritten only if it holds two files or more), repeated
until a pass merges nothing; a merged file holds its inputs' tokens
concatenated in bin order. A rewrite-delete bins every file the same way
with single-file bins allowed and keeps, of each input's 128-token rows
that hold content, those the predicate does not drop, boundary padding
included.
"""

from __future__ import annotations

import hashlib
import struct
from typing import List, Sequence, Tuple

import numpy as np

MAGIC = b"TOKS"
HEADER = 12
CHUNK_TOKENS = 1024
ROW_TOKENS = 128


def encode(tokens: np.ndarray) -> bytes:
    tokens = np.asarray(tokens, np.int32)
    pad = (-tokens.size) % CHUNK_TOKENS
    return (MAGIC + struct.pack("<q", tokens.size) + tokens.tobytes()
            + bytes(4 * pad))


def decode(raw: bytes) -> np.ndarray:
    if raw[:4] != MAGIC:
        raise ValueError("not a token shard")
    (n,) = struct.unpack("<q", raw[4:HEADER])
    return np.frombuffer(raw, np.int32, count=n, offset=HEADER)


def file_bytes(n_tokens: int) -> int:
    return HEADER + 4 * (-(-n_tokens // CHUNK_TOKENS) * CHUNK_TOKENS)


def digest(tokens: np.ndarray) -> str:
    return hashlib.blake2b(np.ascontiguousarray(tokens, np.int32).data,
                           digest_size=16).hexdigest()


def first_fit_decreasing(sizes: Sequence[int], target: int
                         ) -> List[List[int]]:
    """Bins of indices; larger files first, ties in input order."""
    bins: List[List[int]] = []
    fill: List[int] = []
    for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
        for b in range(len(bins)):
            if fill[b] + sizes[i] <= target:
                bins[b].append(i)
                fill[b] += sizes[i]
                break
        else:
            bins.append([i])
            fill.append(sizes[i])
    return bins


def compact(files: Sequence[np.ndarray], target: int
            ) -> Tuple[List[np.ndarray], List[int]]:
    """(final files' tokens in table order, indices of the input files
    that were rewritten), from the input files' tokens in table order."""
    live = [(i, np.asarray(t, np.int32)) for i, t in enumerate(files)]
    removed: List[int] = []
    while True:
        small = [k for k, (_, t) in enumerate(live)
                 if file_bytes(t.size) < target]
        bins = [b for b in first_fit_decreasing(
            [file_bytes(live[k][1].size) for k in small], target)
            if len(b) >= 2]
        if not bins:
            return [t for _, t in live], sorted(removed)
        merged = [[small[j] for j in b] for b in bins]
        gone = {k for b in merged for k in b}
        removed += [live[k][0] for k in gone if live[k][0] >= 0]
        out = [np.concatenate([live[k][1] for k in b]) for b in merged]
        live = [x for k, x in enumerate(live) if k not in gone] + \
            [(-1, t) for t in out]


def kept_rows(tokens: np.ndarray, drop) -> np.ndarray:
    """The tokens a rewrite-delete keeps of one file: its content rows
    (padded to 128 tokens) that ``drop(rows)`` does not mark."""
    n_rows = -(-tokens.size // ROW_TOKENS)
    rows = np.zeros(n_rows * ROW_TOKENS, np.int32)
    rows[:tokens.size] = tokens
    rows = rows.reshape(n_rows, ROW_TOKENS)
    return rows[~np.asarray(drop(rows), bool)].reshape(-1)


def rewrite_delete(files: Sequence[np.ndarray], target: int, drop
                   ) -> Tuple[List[np.ndarray], int]:
    """(files after the delete, in table order; content rows dropped)."""
    sizes = [file_bytes(t.size) for t in files]
    out, dropped = [], 0
    for b in first_fit_decreasing(sizes, target):
        parts = []
        for k in b:
            kept = kept_rows(files[k], drop)
            dropped += -(-files[k].size // ROW_TOKENS) - kept.size // ROW_TOKENS
            parts.append(kept)
        out.append(np.concatenate(parts))
    return out, dropped


def drop_by_row_hash(fraction: float, mix_seed: int):
    """A delete predicate over 128-token rows, by a hash of each row's
    content, so the drops scatter over every fragment: about
    ``fraction`` of distinct rows are dropped."""
    mix = np.arange(1, ROW_TOKENS + 1, dtype=np.uint32) * np.uint32(mix_seed)
    cut = np.uint32(round(fraction * 1000))

    def drop(rows: np.ndarray) -> np.ndarray:
        h = (np.asarray(rows).astype(np.uint32) * mix).sum(
            axis=1, dtype=np.uint32)
        return (h >> np.uint32(11)) % np.uint32(1000) < cut
    return drop
