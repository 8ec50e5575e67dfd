"""Token shards: the data files of the training corpus LST.

A shard is an int32 token array padded to CHUNK_TOKENS (1024) alignment —
the alignment contract that turns compaction into the chunk-permutation DMA
kernel (repro.kernels.compact_pack). The 12-byte header (magic, int64)
records the true (pre-padding) length.

The codec copies nothing it need not: a decode is a read-only view over
the stored ``bytes``, and ``frame_shard`` writes a shard's bytes once,
in one join of the header, the token arrays' buffers and the zero pad.

Writers model the paper's §2 causes of small files:
  * TrickleWriter — CDC/streaming ingestion: many small appends;
  * BulkWriter   — well-tuned batch ingestion: near-target files.
"""

from __future__ import annotations

import dataclasses
import io
import struct
from typing import Iterable, List, Optional, Sequence

import numpy as np

from repro.kernels.compact_pack.compact_pack import CHUNK_TOKENS
from repro.lst.files import DataFile
from repro.lst.table import LogStructuredTable

_MAGIC = b"TOKS"
_HEADER = struct.Struct("<4sq")     # magic, true length


def zipf_tokens(rng: np.random.RandomState, vocab: int, n: int) -> np.ndarray:
    """Zipf-distributed synthetic tokens (learnable unigram structure; a
    uniform stream would already sit at the entropy floor ln(V))."""
    vals = rng.zipf(1.5, size=n)
    return ((vals - 1) % vocab).astype(np.int32)


def frame_shard(parts: Sequence[np.ndarray]) -> bytes:
    """The shard of the int32 ``parts`` end to end: the header, each
    part's buffer and the zero pad to the next chunk, in one join."""
    n = sum(int(p.shape[0]) for p in parts)
    pad = (-n) % CHUNK_TOKENS
    return b"".join([_HEADER.pack(_MAGIC, n),
                     *(memoryview(np.ascontiguousarray(p, np.int32))
                       for p in parts),
                     bytes(4 * pad)])


def encode_shard(tokens: np.ndarray) -> bytes:
    """One token array as a shard."""
    return frame_shard([tokens])


def shard_length(raw: bytes) -> int:
    """True (pre-padding) token count, from the header."""
    magic, n = _HEADER.unpack_from(raw)
    assert magic == _MAGIC, "not a token shard"
    return n


def decode_shard(raw: bytes) -> np.ndarray:
    """The tokens: a read-only view over ``raw``."""
    return np.frombuffer(raw, np.int32, count=shard_length(raw),
                         offset=_HEADER.size)


def decode_shard_padded(raw: bytes) -> np.ndarray:
    """Full chunk-aligned payload including padding (kernel input): a
    read-only view over ``raw``."""
    shard_length(raw)                   # checks the magic
    return np.frombuffer(raw, np.int32, offset=_HEADER.size)


@dataclasses.dataclass
class TokenShardWriter:
    table: LogStructuredTable
    vocab: int = 32000
    seed: int = 0
    _counter: int = 0

    def _write(self, tokens: np.ndarray, partition: Optional[str]) -> DataFile:
        self._counter += 1
        path = f"{self.table.table_id}/data/shard-{self._counter:08d}.toks"
        raw = encode_shard(tokens)
        self.table.store.put(path, raw)
        return DataFile(path=path, size_bytes=len(raw),
                        num_rows=int(tokens.shape[0]), partition=partition,
                        created_at=self.table.now_fn())

    def trickle_append(self, n_files: int, tokens_per_file: int,
                       partition: Optional[str] = None,
                       rng: Optional[np.random.RandomState] = None
                       ) -> List[DataFile]:
        """CDC-style: many small shards in one commit."""
        rng = rng or np.random.RandomState(self.seed + self._counter)
        files = [self._write(zipf_tokens(rng, self.vocab, tokens_per_file),
                             partition) for _ in range(n_files)]
        self.table.append(files)
        return files

    def bulk_append(self, total_tokens: int, target_file_tokens: int,
                    partition: Optional[str] = None,
                    rng: Optional[np.random.RandomState] = None
                    ) -> List[DataFile]:
        rng = rng or np.random.RandomState(self.seed + self._counter)
        files = []
        left = total_tokens
        while left > 0:
            n = min(target_file_tokens, left)
            files.append(self._write(zipf_tokens(rng, self.vocab, n),
                                     partition))
            left -= n
        self.table.append(files)
        return files
