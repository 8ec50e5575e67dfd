"""Data layer: shard round-trips (hypothesis), packing, pipeline
determinism, and the central invariant — compaction NEVER changes the token
multiset the training job reads."""

import struct

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import (DataPipeline, TokenShardWriter, decode_shard,
                        encode_shard, merge_shards_fn, pack_tokens)
from repro.data.shards import decode_shard_padded
from repro.kernels.compact_pack.compact_pack import CHUNK_TOKENS
from repro.lst import Catalog, InMemoryStore
from repro.lst import compaction as comp
from repro.lst.compaction import CompactionTask
from repro.lst.files import DataFile
from repro.lst.workload import SimClock


def shard_layout(tokens):
    """A shard's bytes as the format defines them, built independently of
    the codec: magic, int64 true length, the tokens, zeros to the chunk."""
    pad = (-tokens.size) % CHUNK_TOKENS
    return (b"TOKS" + struct.pack("<q", tokens.size)
            + np.concatenate([tokens, np.zeros(pad, np.int32)]).tobytes())


def make_table(seed=0):
    clock = SimClock()
    store = InMemoryStore()
    cat = Catalog(store, now_fn=clock.now)
    t = cat.create_table("train", "corpus",
                         properties={"conflict_granularity": "table"})
    t.now_fn = clock.now
    return cat, t, store


class TestShardFormat:
    @given(st.integers(min_value=0, max_value=5000))
    @settings(max_examples=30, deadline=None)
    def test_encode_decode_roundtrip(self, n):
        rng = np.random.RandomState(1)
        toks = rng.randint(0, 1 << 20, size=n).astype(np.int32)
        raw = encode_shard(toks)
        out = decode_shard(raw)
        assert np.array_equal(out, toks)
        padded = decode_shard_padded(raw)
        assert padded.shape[0] % CHUNK_TOKENS == 0
        assert padded.shape[0] >= n

    @pytest.mark.parametrize("n", [0, 1, 1023, 1024, 1025])
    def test_encode_is_the_shard_layout(self, n):
        toks = np.random.RandomState(n).randint(
            -(1 << 31), 1 << 31, size=n).astype(np.int32)
        raw = encode_shard(toks)
        assert type(raw) is bytes
        assert raw == shard_layout(toks)

    @pytest.mark.parametrize("n", [0, 1, 1024, 1025])
    def test_decode_is_a_view_of_the_stored_bytes(self, n):
        store = InMemoryStore()
        toks = np.arange(n, dtype=np.int32)
        store.put("s.toks", encode_shard(toks))
        raw = store.get("s.toks")
        out, padded = decode_shard(raw), decode_shard_padded(raw)
        assert out.base is raw and padded.base is raw
        assert not out.flags.writeable and not padded.flags.writeable
        assert out.shape == (n,)
        assert padded.shape == (-(-n // CHUNK_TOKENS) * CHUNK_TOKENS,)
        assert np.array_equal(out, toks)
        assert not padded[n:].any()

    def test_pack_tokens_shapes_and_labels(self):
        stream = np.arange(4 * 3 * 9 + 5, dtype=np.int32)
        slabs = pack_tokens(stream, batch=3, seq_len=8)
        assert slabs.shape == (4, 3, 9)
        # labels are next-token shifted views of the same stream
        assert np.array_equal(slabs[0, 0, 1:], stream[1:9])


class TestMergeOutputBytes:
    """The merged shard equals, byte for byte, the shard layout of the
    tokens the merge should keep, computed here from the inputs alone."""

    @staticmethod
    def _keep_odd(rows, task):
        return (rows[:, 0] % 2).astype(bool)

    @pytest.mark.parametrize("filtered", [False, True],
                             ids=["plain", "filtered"])
    def test_merge_output_is_the_layout_of_the_kept_tokens(self, filtered):
        _, table, store = make_table()
        rng = np.random.RandomState(11)
        files, inputs = [], []
        for j, n in enumerate([1, 1023, 1024, 1025, 3000, 0, 129]):
            toks = rng.randint(0, 997, size=n).astype(np.int32)
            path = f"{table.table_id}/data/s{j}.toks"
            store.put(path, shard_layout(toks))
            files.append(DataFile(path=path, size_bytes=CHUNK_TOKENS,
                                  num_rows=n, created_at=0.0))
            inputs.append(toks)
        table.append(files)
        task = CompactionTask(task_id=0, table_id=table.table_id,
                              scope=None, inputs=tuple(files),
                              est_output_bytes=0)
        if filtered:
            out, dropped = merge_shards_fn(table, task, "out.toks",
                                           filter_fn=self._keep_odd)
            rows = [np.concatenate([t, np.zeros((-t.size) % CHUNK_TOKENS,
                                                np.int32)]).reshape(-1, 128)
                    [:-(-t.size // 128)] for t in inputs]
            rows = np.concatenate(rows)
            want = rows[rows[:, 0] % 2 == 1].reshape(-1)
            assert dropped == int((rows[:, 0] % 2 == 0).sum()) > 0
        else:
            out = merge_shards_fn(table, task, "out.toks")
            want = np.concatenate(inputs)
        raw = store.get("out.toks")
        assert raw == shard_layout(want)
        assert out.size_bytes == len(raw) and out.num_rows == want.size


class TestCompactionPreservesData:
    @pytest.mark.parametrize("tokens_per_file", [100, 1024, 3000])
    def test_token_multiset_preserved(self, tokens_per_file):
        _, table, _ = make_table()
        w = TokenShardWriter(table, vocab=997, seed=3)
        for _ in range(5):
            w.trickle_append(n_files=8, tokens_per_file=tokens_per_file)
        pipe = DataPipeline(table, batch=2, seq_len=64)
        before = np.sort(np.concatenate(
            [b["tokens"].ravel() for b in pipe.batches()]))
        for t in comp.plan_table(table, target_bytes=1 << 20):
            r = comp.execute_task(table, t, merge_fn=merge_shards_fn)
            assert r.success, r.error
        assert table.file_count() < 40
        pipe2 = DataPipeline(table, batch=2, seq_len=64)
        after = np.sort(np.concatenate(
            [b["tokens"].ravel() for b in pipe2.batches()]))
        assert np.array_equal(before, after)

    def test_num_rows_preserved_exactly(self):
        _, table, _ = make_table()
        w = TokenShardWriter(table, vocab=100, seed=4)
        w.trickle_append(n_files=6, tokens_per_file=777)
        rows_before = sum(f.num_rows for f in table.current_files())
        for t in comp.plan_table(table, target_bytes=1 << 22):
            assert comp.execute_task(table, t, merge_fn=merge_shards_fn).success
        rows_after = sum(f.num_rows for f in table.current_files())
        assert rows_before == rows_after


class TestRewriteDeletes:
    """Rewrite-deletes-as-compaction through the real execute path: a
    filter_fn on execute_task routes the merge through the fused
    filter+pack kernel; fused and reference paths must commit identical
    tables and identical rows_dropped accounting."""

    @staticmethod
    def _drop_even(rows, task):
        return (rows[:, 0] % 2).astype(bool)    # keep odd-leading rows

    def _run(self, fused):
        _, table, store = make_table()
        w = TokenShardWriter(table, vocab=997, seed=3)
        for _ in range(3):
            w.trickle_append(n_files=6, tokens_per_file=3000)
        results = [comp.execute_task(table, t, merge_fn=merge_shards_fn,
                                     filter_fn=self._drop_even,
                                     fused_filter=fused)
                   for t in comp.plan_table(table, target_bytes=1 << 20)]
        assert results and all(r.success for r in results)
        toks = sorted((decode_shard(store.get(f.path))
                       for f in table.current_files()),
                      key=lambda a: (a.shape[0], tuple(a[:8])))
        return sum(r.rows_dropped for r in results), toks

    def test_fused_and_reference_commit_identical_tables(self):
        dropped_fused, toks_fused = self._run(fused=True)
        dropped_ref, toks_ref = self._run(fused=False)
        assert dropped_fused == dropped_ref > 0
        assert len(toks_fused) == len(toks_ref)
        assert all(np.array_equal(a, b)
                   for a, b in zip(toks_fused, toks_ref))
        # the filter held: every surviving 128-token row leads odd
        for t in toks_fused:
            assert (t.reshape(-1, 128)[:, 0] % 2 == 1).all()

    def test_unfiltered_rewrite_reports_zero_dropped(self):
        _, table, _ = make_table()
        w = TokenShardWriter(table, vocab=100, seed=4)
        w.trickle_append(n_files=6, tokens_per_file=777)
        for t in comp.plan_table(table, target_bytes=1 << 22):
            r = comp.execute_task(table, t, merge_fn=merge_shards_fn)
            assert r.success and r.rows_dropped == 0

    def test_drop_everything_yields_empty_shard(self):
        _, table, store = make_table()
        w = TokenShardWriter(table, vocab=100, seed=5)
        w.trickle_append(n_files=4, tokens_per_file=900)
        tasks = comp.plan_table(table, target_bytes=1 << 22)
        res = [comp.execute_task(
            table, t, merge_fn=merge_shards_fn,
            filter_fn=lambda rows, task: np.zeros(rows.shape[0], bool))
            for t in tasks]
        assert all(r.success for r in res)
        assert sum(r.rows_dropped for r in res) > 0
        for f in table.current_files():
            assert decode_shard(store.get(f.path)).shape[0] == 0


class TestPipeline:
    def test_batches_deterministic_by_seed(self):
        _, table, _ = make_table()
        w = TokenShardWriter(table, vocab=500, seed=5)
        w.trickle_append(n_files=10, tokens_per_file=2000)
        a = [b["tokens"] for b in DataPipeline(table, 2, 64, seed=1).batches()]
        b = [b["tokens"] for b in DataPipeline(table, 2, 64, seed=1).batches()]
        assert all(np.array_equal(x, y) for x, y in zip(a, b))

    def test_prefetch_yields_same_batches(self):
        _, table, _ = make_table()
        w = TokenShardWriter(table, vocab=500, seed=6)
        w.trickle_append(n_files=6, tokens_per_file=2000)
        plain = [b["tokens"] for b in DataPipeline(table, 2, 64, seed=2).batches()]
        pre = [b["tokens"] for b in
               DataPipeline(table, 2, 64, seed=2).prefetching_batches()]
        assert len(plain) == len(pre)
        assert all(np.array_equal(x, y) for x, y in zip(plain, pre))

    def test_plan_cost_scales_with_file_count(self):
        _, table, store = make_table()
        w = TokenShardWriter(table, vocab=100, seed=7)
        w.trickle_append(n_files=50, tokens_per_file=200)
        pipe = DataPipeline(table, 2, 16)
        open_before = store.metrics.open_calls
        list(pipe.batches())
        opens_fragmented = store.metrics.open_calls - open_before
        for t in comp.plan_table(table, target_bytes=1 << 22):
            comp.execute_task(table, t, merge_fn=merge_shards_fn)
        open_before = store.metrics.open_calls
        list(DataPipeline(table, 2, 16).batches())
        opens_compacted = store.metrics.open_calls - open_before
        assert opens_compacted < opens_fragmented
