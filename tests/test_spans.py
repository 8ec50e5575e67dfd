"""The program's own host spans (``repro.spans``), captured on the CPU by
the JAX profiler inside a ``window`` annotation and read back with the
benchmark's trace reader: the shard merge, the table commit, the
AutoComp cycle and the slot engine each emit their spans, nested as the
code nests them. A process that never imports JAX commits and compacts
without loading it."""

import contextlib
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from bench.harness import trace as T
from repro import spans
from repro.core import (AutoCompPipeline, ComputeCostTrait,
                        FileCountReductionTrait, MoopRanker, Scope,
                        StatsCollector, TraitContext)
from repro.core.act import Scheduler
from repro.data import merge_shards_fn
from repro.data.shards import encode_shard
from repro.lst import Catalog, InMemoryStore
from repro.lst.compaction import CompactionTask
from repro.lst.files import DataFile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def traced(tmp_path):
    """Profile the block inside a ``window`` span; the trace is in
    ``box["trace"]`` after the block."""
    box = {}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            yield box
    finally:
        jax.profiler.stop_trace()
    box["trace"] = T.load(T.find_xplane(str(tmp_path)))


def named(tr, name):
    return [e for e in tr.host if e.name == name]


def inside(e, outer):
    return outer.start <= e.start and e.end <= outer.end


def token_table(n_files=5):
    store = InMemoryStore()
    table = Catalog(store).create_table("ns", "t")
    files = []
    for j in range(n_files):
        tok = (np.arange(700 + 450 * j) % 997).astype(np.int32)
        raw = encode_shard(tok)
        path = f"{table.table_id}/data/s{j}.toks"
        store.put(path, raw)
        files.append(DataFile(path=path, size_bytes=len(raw),
                              num_rows=int(tok.size), created_at=0.0))
    table.append(files)
    return table, files


MERGE_CHILDREN = {
    "plain": [spans.MERGE_READ, spans.MERGE_CONCAT, spans.MERGE_DEVICE,
              spans.MERGE_RESLICE, spans.MERGE_ENCODE, spans.MERGE_STORE],
    "filtered": [spans.MERGE_READ, spans.MERGE_CONCAT, spans.MERGE_FILTER,
                 spans.MERGE_DEVICE, spans.MERGE_ENCODE, spans.MERGE_STORE],
}


@pytest.mark.parametrize("path", sorted(MERGE_CHILDREN))
def test_merge_emits_one_span_tiled_by_its_steps(tmp_path, path):
    table, files = token_table()
    task = CompactionTask(task_id=0, table_id=table.table_id, scope=None,
                          inputs=tuple(files), est_output_bytes=0)
    kw = {} if path == "plain" else {
        "filter_fn": lambda rows, task: (rows[:, 0] % 2).astype(bool)}
    with traced(tmp_path) as box:
        merge_shards_fn(table, task, "out/merged.toks", **kw)
    tr = box["trace"]
    (merge,) = named(tr, spans.MERGE_SHARDS)
    children = sorted((e for e in tr.host if e.name.startswith("merge.")
                       and e.name != spans.MERGE_SHARDS),
                      key=lambda e: e.start)
    # one span a step, not one a file, in the order the merge runs them
    assert [e.name for e in children] == MERGE_CHILDREN[path]
    assert all(inside(e, merge) for e in children)
    assert all(a.end <= b.start for a, b in zip(children, children[1:]))
    # the steps leave only the bookkeeping between them uncovered
    assert sum(e.dur for e in children) <= merge.dur


def test_each_commit_spans_its_metadata_write(tmp_path):
    store = InMemoryStore()
    table = Catalog(store).create_table("ns", "t")
    n = 7
    with traced(tmp_path) as box:
        for j in range(n):
            path = f"{table.table_id}/data/f{j}.bin"
            store.put(path, b"x")
            table.append([DataFile(path, 1, 1)])
    tr = box["trace"]
    commits = named(tr, spans.TABLE_COMMIT)
    assert len(commits) == n
    for name in (spans.TABLE_METADATA, spans.TABLE_REBASE,
                 spans.TABLE_MANIFEST):
        events = named(tr, name)
        assert [sum(inside(e, c) for e in events) for c in commits] == \
            [1] * n, name


def core_pipeline(target=1 << 20):
    return AutoCompPipeline(
        stats=StatsCollector(target),
        traits=(FileCountReductionTrait(), ComputeCostTrait()),
        trait_ctx=TraitContext(target_file_bytes=target),
        ranker=MoopRanker({"file_count_reduction": 0.7,
                           "compute_cost": 0.3}),
        scheduler=Scheduler(target), scope=Scope.TABLE, top_k=4)


def test_cycle_emits_its_three_phases(tmp_path):
    store = InMemoryStore()
    catalog = Catalog(store)
    table = catalog.create_table("ns", "t")
    files = []
    for j in range(12):
        path = f"{table.table_id}/data/f{j}.bin"
        store.put(path, b"x" * 64)
        files.append(DataFile(path, 1 << 14, 10))
    table.append(files)
    with traced(tmp_path) as box:
        rep = core_pipeline().run_cycle(catalog)
    assert rep.files_removed > 0
    tr = box["trace"]
    (cycle,) = named(tr, spans.AUTOCOMP_CYCLE)
    phases = [named(tr, p) for p in (spans.AUTOCOMP_PROPOSE,
                                     spans.AUTOCOMP_DECIDE,
                                     spans.AUTOCOMP_ACT)]
    assert [len(p) for p in phases] == [1, 1, 1]
    (propose,), (decide,), (act,) = phases
    assert inside(propose, cycle) and inside(decide, cycle) and \
        inside(act, cycle)
    assert propose.end <= decide.start and decide.end <= act.start
    # the rewrite's commit happens inside the act phase
    assert any(inside(c, act) for c in named(tr, spans.TABLE_COMMIT))


# the plain slot stream, and the slot engine's fan-in configuration on one
# device: two prefill workers into a paged table whose one page holds any
# prompt of the tiny traffic
SLOT_CONFIGS = {"plain": {},
                "fan_in": {"workers": 2, "paged": True, "page_size": 16}}


@pytest.mark.parametrize("config", sorted(SLOT_CONFIGS))
def test_slot_engine_emits_a_decode_span_a_step(tmp_path, config):
    from bench.drivers import serving
    from bench.tests.serving_tiny import tiny
    cfg, tr_ = tiny("decode", requests=5, slots=2, max_new=4,
                    check_requests=2)
    d = serving.Driver(cfg, tr_, 11, lambda s: None)
    d.setup()
    d.kwargs.update(SLOT_CONFIGS[config])
    with traced(tmp_path) as box:
        d.step()
    stats = d.serve._generate_slots.last_stats
    tr = box["trace"]
    (gen,) = named(tr, spans.SERVE_GENERATE)
    (setup,) = named(tr, spans.SERVE_SETUP)
    assert inside(setup, gen)
    decodes = named(tr, spans.SERVE_DECODE)
    assert len(decodes) == stats["decode_steps"] > 0
    assert len(named(tr, spans.SERVE_SAMPLE)) == stats["decode_steps"]
    assert len(named(tr, spans.SERVE_EMIT)) == stats["decode_steps"]
    # an evicted request is prefilled and admitted again; the plain slot
    # stream never evicts
    if config == "plain":
        assert stats["evictions"] == 0
    admits = named(tr, spans.SERVE_ADMIT)
    assert len(admits) == stats["admissions"] == \
        tr_["requests"] + stats["evictions"]
    waits = named(tr, spans.SERVE_TRANSFER_WAIT)
    assert [sum(inside(w, a) for w in waits) for a in admits] == \
        [1] * len(admits)
    assert len(named(tr, spans.SERVE_PREFILL)) == stats["admissions"]
    every = decodes + admits + named(tr, spans.SERVE_PREFILL)
    assert all(inside(e, gen) for e in every)


def test_compaction_programs_have_stable_names():
    from repro.kernels.compact_pack import ops
    from repro.kernels.compact_pack.compact_pack import (CHUNK_COLS,
                                                         CHUNK_ROWS)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32)
    gather = ops.compact_gather.lower(sds(2, CHUNK_ROWS, CHUNK_COLS),
                                      sds(2), interpret=True)
    filt = ops.compact_filter.lower(
        sds(2, CHUNK_ROWS, CHUNK_COLS), sds(2), sds(2 * CHUNK_ROWS),
        sds(2), sds(2), n_out=1, interpret=True)
    assert "module @jit_compact_gather " in gather.as_text()
    assert "module @jit_compact_filter " in filt.as_text()


# what each configuration jits, once a call: the paged table's decode step
# wraps the unchanged dense one in its page-table gather and scatter
SLOT_PROGRAMS = {
    "plain": ["admit", "decode_step", "grow_cache", "init_cache",
              "prefill_step"],
    "fan_in": ["admit_pages", "grow_cache", "init_cache",
               "paged_decode_step", "prefill_step"]}


@pytest.mark.parametrize("config", sorted(SLOT_CONFIGS))
def test_slot_engine_programs_have_stable_names(monkeypatch, config):
    """Every program the slot engine jits is a named function, so a
    device trace names it ``jit_<name>`` (a lambda or a partial would
    read ``jit__lambda`` or ``jit__unknown``)."""
    from bench.drivers import serving
    from bench.tests.serving_tiny import tiny
    cfg, tr_ = tiny("decode", requests=3, slots=2, max_new=3,
                    check_requests=2)
    d = serving.Driver(cfg, tr_, 5, lambda s: None)
    d.setup()
    d.kwargs.update(SLOT_CONFIGS[config])
    names = []
    real = jax.jit

    def jit(fun, *args, **kw):
        names.append(getattr(fun, "__name__", None))
        return real(fun, *args, **kw)
    monkeypatch.setattr(jax, "jit", jit)
    d.step()
    assert sorted(names) == SLOT_PROGRAMS[config]


def test_span_names_are_spelled_in_one_place():
    names = [v for k, v in vars(spans).items()
             if k.isupper() and isinstance(v, str)
             and not k.startswith("KERNEL_")]
    assert len(names) == len(set(names)) == 24
    assert all("." in n and n == n.strip() for n in names)
    # kernel names (the device trace's op names) are spelled there too
    names += [v for k, v in vars(spans).items() if k.startswith("KERNEL_")]
    assert len(names) == len(set(names)) == 25
    src = os.path.join(ROOT, "src", "repro")
    for d, _, files in os.walk(src):
        for f in files:
            path = os.path.join(d, f)
            if not f.endswith(".py") or path == spans.__file__:
                continue
            with open(path) as fh:
                text = fh.read()
            assert not [n for n in names
                        if f'"{n}"' in text or f"'{n}'" in text], path


def test_lst_and_core_run_without_jax():
    code = textwrap.dedent("""
        import sys
        import repro.lst, repro.core
        from repro.core import (AutoCompPipeline, ComputeCostTrait,
                                FileCountReductionTrait, MoopRanker, Scope,
                                StatsCollector, TraitContext)
        from repro.core.act import Scheduler
        from repro.lst.files import DataFile

        store = repro.lst.InMemoryStore()
        catalog = repro.lst.Catalog(store)
        table = catalog.create_table("ns", "t")
        for j in range(8):
            path = f"{table.table_id}/data/f{j}.bin"
            store.put(path, b"x")
            table.append([DataFile(path, 1 << 14, 1)])
        pipe = AutoCompPipeline(
            stats=StatsCollector(1 << 20),
            traits=(FileCountReductionTrait(), ComputeCostTrait()),
            trait_ctx=TraitContext(target_file_bytes=1 << 20),
            ranker=MoopRanker({"file_count_reduction": 0.7,
                               "compute_cost": 0.3}),
            scheduler=Scheduler(1 << 20), scope=Scope.TABLE, top_k=4)
        rep = pipe.run_cycle(catalog)
        assert rep.files_removed > 0, rep
        print("jax" in sys.modules)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
