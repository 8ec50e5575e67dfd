"""Driver: AutoComp compacting a token-shard table (``core/``, ``lst/``,
``data/packing.py::merge_shards_fn``, the ``compact_pack`` kernels).

Set-up makes the configuration's pool of small files once from the seed:
a fixed set of file sizes (the same for every seed, so every seed does
the same work and compiles the same kernel shapes) in an order and with
tokens drawn from the seed, encoded as token shards in host memory.

One iteration registers the pool in a fresh table under fresh paths, in
commits of ``files_per_commit`` (metadata only: the bytes are shared),
then runs ``AutoCompPipeline.run_cycle`` until a cycle removes no file.
With the traffic's ``delete_fraction`` it then deletes that share of
rows by a row-hash predicate through the rewrite-delete path
(``route_delete`` -> ``plan_rewrite_delete`` ->
``execute_tasks_atomic(filter_fn=...)``). The table is dropped after the
iteration, except for a few iterations drawn from the seed, kept until
the window has closed for the check.

Host spans: ``ingest`` around registering the pool, ``commit`` around
each of its commits, ``cycle`` around each ``run_cycle``, ``merge``
around each plain rewrite (wrapping ``scheduler.merge_fn``), ``delete``
around the rewrite-delete and ``merge_filter`` around each of its
filtered rewrites.
"""

from __future__ import annotations

import numpy as np

from bench.reference import compaction as ref


def pool_sizes(config: dict) -> list:
    """Token counts of the pool's ``files`` files: uniform in the
    configuration's range of file bytes, drawn from its fixed sizes
    seed."""
    rng = np.random.default_rng(config["file_sizes_seed"])
    lo, hi = (int(b) // 4 for b in config["file_bytes"])
    return [int(n) for n in rng.integers(lo, hi + 1, int(config["files"]))]


def make_pool(config: dict, seed: int):
    """(tokens per file, encoded shard per file), in the seed's order."""
    rng = np.random.default_rng(seed)
    sizes = pool_sizes(config)
    sizes = [sizes[i] for i in rng.permutation(len(sizes))]
    tokens = [rng.integers(0, config["vocab"], n, dtype=np.int32)
              for n in sizes]
    return tokens, [ref.encode(t) for t in tokens]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config, self.traffic, self.seed, self.log = \
            config, traffic, seed, log
        self.target = int(config["write.target-file-size-bytes"])
        self.iterations = []          # per window iteration: a summary
        self.kept = []                # (store, live files) to check
        self._keep_rng = np.random.default_rng([seed, 1])
        self._drop = ref.drop_by_row_hash(
            traffic.get("delete_fraction", 0.0), traffic["delete_hash_seed"])

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import time

        import jax

        from repro.kernels import api
        self.api = api
        self.platform = jax.devices()[0].platform
        t0 = time.perf_counter()
        self.tokens, self.pool = make_pool(self.config, self.seed)
        t1 = time.perf_counter()
        self._iteration(record=False)       # warms every kernel shape
        self.log(f"[compaction] pool: {len(self.pool)} files, "
                 f"{sum(len(r) for r in self.pool)} bytes, made in "
                 f"{t1 - t0:.3f} s; warm-up iteration "
                 f"{time.perf_counter() - t1:.3f} s")

    # -- one iteration ---------------------------------------------------------
    def _table(self):
        from repro.lst import Catalog, InMemoryStore
        from repro.lst.workload import SimClock

        clock = SimClock()
        store = InMemoryStore()
        catalog = Catalog(store, now_fn=clock.now)
        table = catalog.create_table(
            "train", "corpus", properties={"conflict_granularity": "table"})
        table.now_fn = clock.now
        return clock, store, catalog, table

    def _merge_fn(self, span, merges):
        from jax.profiler import TraceAnnotation

        from repro.data import merge_shards_fn

        def merge(tbl, task, out_path, **kw):
            with TraceAnnotation(span):
                out = merge_shards_fn(tbl, task, out_path, **kw)
            f = out[0] if isinstance(out, tuple) else out
            merges.append({"kind": span, "inputs": len(task.inputs),
                           "input_bytes": int(task.input_bytes),
                           "output_bytes": int(f.size_bytes)})
            return out
        return merge

    def _iteration(self, record: bool):
        import time

        from jax.profiler import TraceAnnotation

        from repro.lst.compaction import execute_tasks_atomic
        from repro.lst.files import DataFile
        from repro.lst.retention import (PredicateDelete, plan_rewrite_delete,
                                         route_delete)
        from repro.launch.train import build_autocomp

        clock, store, catalog, table = self._table()
        merges, results = [], []
        per = int(self.config["files_per_commit"])
        t_ingest = time.perf_counter()
        with TraceAnnotation("ingest"):
            files = []
            for j, (tok, raw) in enumerate(zip(self.tokens, self.pool)):
                path = f"{table.table_id}/data/shard-{j:08d}.toks"
                store.put(path, raw)
                files.append(DataFile(path=path, size_bytes=len(raw),
                                      num_rows=int(tok.size),
                                      created_at=clock.now()))
            for k in range(0, len(files), per):
                with TraceAnnotation("commit"):
                    table.append(files[k:k + per])
                clock.advance(0.02)
        self.ingest_s = time.perf_counter() - t_ingest
        pool_paths = [f.path for f in files]
        autocomp = build_autocomp(catalog, clock, target_bytes=self.target,
                                  top_k=int(self.config["planner_top_k"]))
        autocomp.scheduler.merge_fn = self._merge_fn("merge", merges)
        with self.api.record_dispatches() as dispatches:
            while True:
                with TraceAnnotation("cycle"):
                    rep = autocomp.run_cycle(catalog)
                results += rep.act.results if rep.act else []
                if rep.files_removed == 0:
                    break
            live_compacted = [f.path for f in table.current_files()]
            rows_dropped = None
            if self.traffic.get("delete_fraction", 0.0) > 0:
                drop = self._drop
                op = PredicateDelete("bench-delete",
                                     row_predicate=lambda rows, task:
                                     drop(rows))
                with TraceAnnotation("delete"):
                    route = route_delete(table, op)
                    res = execute_tasks_atomic(
                        table, plan_rewrite_delete(
                            table, route.rewrite_files, self.target),
                        merge_fn=self._merge_fn("merge_filter", merges),
                        filter_fn=op.filter_fn())
                results.append(res)
                rows_dropped = res.rows_dropped
        if not record:
            return
        i = len(self.iterations)
        live = set(f.path for f in table.current_files())
        self.iterations.append({
            "merges": merges,
            "bytes_rewritten": sum(r.bytes_rewritten for r in results),
            "failed_results": [r.error for r in results if not r.success],
            "removed": sorted(j for j, p in enumerate(pool_paths)
                              if p not in live),
            "live_after_compaction": len(live_compacted),
            "live": len(live),
            "rows_dropped": rows_dropped,
            "dispatches": [d.op for d in dispatches],
            "interpreted": self.api.use_interpret(),
        })
        # reservoir sample of the iterations whose contents are checked
        k = int(self.traffic["check_iterations"])
        files_now = [(f.path, f.num_rows) for f in table.current_files()]
        if len(self.kept) < k:
            self.kept.append((store, files_now))
        else:
            j = int(self._keep_rng.integers(0, i + 1))
            if j < k:
                self.kept[j] = (store, files_now)

    def step(self):
        import resource
        import time

        faults = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        t0 = time.perf_counter()
        self._iteration(record=True)
        faults = resource.getrusage(resource.RUSAGE_SELF).ru_minflt - faults
        self.log(f"[compaction] iteration {len(self.iterations) - 1}: "
                 f"{time.perf_counter() - t0:.3f} s (ingest "
                 f"{self.ingest_s:.3f} s), {faults} minor page faults")

    # -- results ---------------------------------------------------------------
    def end_to_end(self, window_s: float) -> dict:
        b = sum(it["bytes_rewritten"] for it in self.iterations)
        return {"rewrite_gbps": b / window_s / 1e9}

    def records(self) -> list:
        return [{"merges": it["merges"]} for it in self.iterations]

    def counts(self):
        """Rewrite tasks attempted (merges run) and failed (in a commit
        that did not land)."""
        attempted = sum(len(it["merges"]) for it in self.iterations)
        failed = sum(len(it["failed_results"]) for it in self.iterations)
        return attempted, failed

    def release(self):
        self.pool = None

    def check(self) -> dict:
        """Every iteration: the files the control plane removed, the file
        counts, the bytes rewritten, the rows dropped and the kernel
        dispatches against the reference. The sampled iterations: the
        committed contents, read back from the store."""
        target = self.target
        final, removed = ref.compact(self.tokens, target)
        want_bytes = sum(ref.file_bytes(self.tokens[j].size) for j in removed)
        n_compacted = len(final)
        dropped = None
        if self.traffic.get("delete_fraction", 0.0) > 0:
            want_bytes += sum(ref.file_bytes(t.size) for t in final)
            final, dropped = ref.rewrite_delete(final, target, self._drop)
        want = sorted(ref.digest(t) for t in final)
        bad = {"removed_files_wrong": 0, "file_count_wrong": 0,
               "bytes_rewritten_wrong": 0, "rows_dropped_wrong": 0,
               "rewrite_failed": 0, "kernel_not_dispatched": 0,
               "kernel_interpreted_on_tpu": 0, "contents_wrong": 0}
        for it in self.iterations:
            bad["removed_files_wrong"] += it["removed"] != removed
            bad["file_count_wrong"] += (
                it["live_after_compaction"] != n_compacted
                or it["live"] != len(final))
            bad["bytes_rewritten_wrong"] += it["bytes_rewritten"] != want_bytes
            bad["rows_dropped_wrong"] += it["rows_dropped"] != dropped
            bad["rewrite_failed"] += len(it["failed_results"])
            ops = it["dispatches"]
            bad["kernel_not_dispatched"] += (
                len(ops) != len(it["merges"])
                or any(op != "compact_pack" for op in ops))
            bad["kernel_interpreted_on_tpu"] += (
                self.platform == "tpu" and it["interpreted"])
        for store, files in self.kept:
            got = []
            for path, n in files:
                t = ref.decode(store.get(path))
                got.append(ref.digest(t) if t.size == n else "row count")
            bad["contents_wrong"] += sorted(got) != want
        if not self.iterations:
            bad["removed_files_wrong"] = 1      # nothing was measured
        self.kept = []
        return {name: float(v) for name, v in bad.items()}
