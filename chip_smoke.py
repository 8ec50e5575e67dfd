#!/usr/bin/env python3
"""Chip smoke: the system's main paths once, at deployment size, on a TPU.

    python chip_smoke.py             # one chip: compaction, then serving
    python chip_smoke.py --chips 4   # four chips: disaggregated serving

One chip, two phases in this one process:

1. Compaction. A token-shard table of at least 1 GiB of int32 tokens in
   seeded 1-8 MiB files (``TokenShardWriter.trickle_append``, as
   ``launch/train.py::build_data`` builds its corpus) is compacted by
   AutoComp cycles (``launch/train.py::build_autocomp``) with
   ``merge_shards_fn`` and a 512 MiB target, Iceberg's default
   ``write.target-file-size-bytes``. A rewrite-delete then drops about 30%
   of the 128-token rows through the fused filter+pack kernel
   (``execute_tasks_atomic(..., filter_fn=)``). Passes when the file count
   fell, the live tokens equal a numpy reference (the concatenation of
   the files before compaction, and a boolean-mask filter for the
   delete), ``rows_dropped`` equals the reference count, every kernel call
   ran compiled (``tpu_custom_call`` in its HLO, never the interpreter or
   the jnp reference) and no block point came from the tuned-point cache.

2. Serving. minicpm3-4b at its published widths (the largest config that
   fits one chip whole) answers 4 requests of 128 prompt tokens with 16
   new greedy tokens through ``launch/serve.py`` (``setup`` + ``generate``,
   the code of ``serve.main``), once with ``--stream batch`` and once with
   ``--stream slots``. ``generate`` keeps the logit row each token was
   chosen from (``keep_logits``): every one must be finite, every token
   in the vocabulary and the argmax of its row, and every row within
   ``ROW_TOL`` of the row one full-sequence forward of prompt + output
   gives for the same position. The check must be able to fail: the
   forward after another request's prompt (a cache from the wrong slot)
   has to break the same limit on most decode rows.

``--chips 4`` runs only the path that exists across chips: granite-3-8b
(15.2 GiB, more than one chip holds) disaggregated over 2 prefill and 2
decode chips at tp=2 with ``--stream slots``, whose bf16 cache-transfer
tokens must equal the colocated 4-chip tokens and pass the same
full-forward check, then an int8 cache-transfer run that must complete
within a looser limit. It runs with the persistent compile cache off, as
every disaggregated server does (``launch/compile_cache.py``).

Figures printed here are smoke figures from one cold run, compilation
included, not benchmark metrics. Any failed check exits non-zero. The last
line of standard output is the JSON verdict with the device JAX reports.
There is no CPU fallback: without a TPU the script exits before any work.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MiB = 1 << 20
GiB = 1 << 30
TARGET_BYTES = 512 * MiB        # Iceberg write.target-file-size-bytes
TABLE_BYTES = GiB               # token payload the table starts with
FILE_MIB = (1, 8)               # small-file sizes the writers trickle in
FILES_PER_COMMIT = 8
DROP_SEED = 0x9E3779B9
# Serving-numerics check: each logit row a generated token was chosen
# from, against the full forward's row for the same position, as
# max |difference| over the vocabulary in units of the row's standard
# deviation. On v5e chips granite-3-8b read 0.055 at most with a bf16
# cache transfer and 0.058 with int8, minicpm3-4b 0.077, while the same
# tokens after another request's prompt read 5.5 at least. The limits
# leave room for a deeper or wider stack (3-4x the bf16 readings) and
# stay 20x below a cache from the wrong slot; int8 blocks round the
# shipped cache, so its limit is twice as loose.
ROW_TOL = 0.25                  # bf16 cache, bf16 transfer
ROW_TOL_INT8 = 0.5              # int8 cache transfer

SERVE_ARGS = ["--arch", "minicpm3-4b", "--full", "--batch", "4",
              "--prompt-len", "128", "--max-new", "16"]
DISAGG_ARGS = ["--arch", "granite-3-8b", "--full", "--batch", "4",
               "--prompt-len", "128", "--max-new", "16", "--tp", "2",
               "--stream", "slots"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[check] {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# phase 1: compaction at deployment size
# ---------------------------------------------------------------------------

def drop_rows(rows):
    """The delete predicate: about 30% of 128-token rows, chosen by a hash
    of each row's content, so the drops scatter across every fragment."""
    import numpy as np
    mix = (np.arange(1, rows.shape[1] + 1, dtype=np.uint32)
           * np.uint32(DROP_SEED))
    h = (rows.astype(np.uint32) * mix).sum(axis=1, dtype=np.uint32)
    return (h >> np.uint32(11)) % np.uint32(10) < np.uint32(3)


def build_table(total_bytes, file_mib, seed):
    import numpy as np

    from repro.data import TokenShardWriter
    from repro.data.shards import decode_shard
    from repro.lst import Catalog, InMemoryStore
    from repro.lst.workload import SimClock

    clock = SimClock()
    store = InMemoryStore()
    catalog = Catalog(store, now_fn=clock.now)
    table = catalog.create_table("train", "corpus",
                                 properties={"conflict_granularity": "table"})
    table.now_fn = clock.now
    writer = TokenShardWriter(table, seed=seed)
    rng = np.random.RandomState(seed)
    lo, hi = (int(m * MiB) // 4 for m in file_mib)
    written = 0
    while written < total_bytes:
        n_tok = int(rng.randint(lo, hi + 1))       # ragged: shards pad
        for f in writer.trickle_append(FILES_PER_COMMIT, n_tok):
            written += f.num_rows * 4
        clock.advance(0.02)
    # the reference: every live file's tokens as written, before compaction
    ref = {f.path: decode_shard(store.get(f.path))
           for f in table.current_files()}
    return catalog, table, store, clock, ref


def _tokens(store, f):
    from repro.data.shards import decode_shard
    return decode_shard(store.get(f.path))


def compact_cycles(autocomp, catalog, table, store, ref, max_cycles):
    """AutoComp cycles until one removes no file. Each cycle's outputs
    must hold the reference concatenation of its inputs; ``ref`` then
    maps the outputs to their share of it."""
    import numpy as np

    for cycle in range(max_cycles):
        before = {f.path for f in table.current_files()}
        rep = autocomp.run_cycle(catalog)
        if rep.files_removed == 0:
            break
        check(all(r.success for r in rep.act.results),
              f"cycle {cycle}: every rewrite committed")
        inputs = [f.path for r in rep.act.results for f in r.task.inputs]
        live = table.current_files()
        outputs = [f for f in live if f.path not in before]
        check(set(inputs) == before - {f.path for f in live},
              f"cycle {cycle}: exactly the planned inputs left the table")
        want = np.concatenate([ref.pop(p) for p in inputs])
        got = np.concatenate([_tokens(store, f) for f in outputs])
        check(np.array_equal(got, want),
              f"cycle {cycle}: {len(outputs)} compacted files hold the "
              f"reference concatenation of their {len(inputs)} inputs "
              f"({want.size} tokens)")
        for f, part in zip(outputs, np.split(
                want, np.cumsum([f.num_rows for f in outputs])[:-1])):
            ref[f.path] = part
        print(f"[compaction] cycle {cycle}: files {len(before)} -> "
              f"{len(live)}, bytes rewritten {rep.act.bytes_rewritten}, "
              f"cycle wall {rep.wall_s:.2f}s", flush=True)


def rewrite_delete(table, store, ref, merge_fn):
    """Drop about 30% of the rows of every live file in one atomic
    rewrite, through the fused filter, and check it against a boolean
    mask over the reference. Returns the (n_src_chunks, n_steps, n_out)
    of the largest fused filter call."""
    import numpy as np

    from repro.kernels.compact_pack import plan_compaction
    from repro.kernels.compact_pack.compact_pack import (CHUNK_COLS,
                                                         CHUNK_TOKENS)
    from repro.kernels.compact_pack.ops import plan_filter
    from repro.lst.compaction import CompactionTask, execute_tasks_atomic

    files = table.current_files()
    tasks = [CompactionTask(i + 1, table.table_id, None, (f,), f.size_bytes)
             for i, f in enumerate(files)]
    res = execute_tasks_atomic(table, tasks, merge_fn=merge_fn,
                               filter_fn=lambda rows, task: ~drop_rows(rows))
    check(res.success, "rewrite-delete committed")
    want, n_drop, filters = [], 0, []
    for f in files:
        toks = ref.pop(f.path)
        rows = np.zeros(-(-toks.size // CHUNK_COLS) * CHUNK_COLS, np.int32)
        rows[:toks.size] = toks
        rows = rows.reshape(-1, CHUNK_COLS)
        drop = drop_rows(rows)
        n_drop += int(drop.sum())
        want.append(rows[~drop].reshape(-1))
        n_chunks = -(-toks.size // CHUNK_TOKENS)
        keep = np.zeros(n_chunks * CHUNK_TOKENS // CHUNK_COLS, bool)
        keep[:rows.shape[0]] = ~drop
        sel, _, _, _, n_out = plan_filter(plan_compaction([n_chunks]), keep)
        filters.append((n_chunks, sel.size, n_out))
    live = table.current_files()
    got = np.concatenate([_tokens(store, f) for f in live])
    want = np.concatenate(want)
    check(res.rows_dropped == n_drop,
          f"rows_dropped {res.rows_dropped} == reference {n_drop} "
          f"({n_drop / (n_drop + want.size // CHUNK_COLS):.3f} of the rows)")
    check(np.array_equal(got, want),
          f"live tokens after the delete equal the masked reference "
          f"({want.size} tokens in {len(live)} files)")
    return max(filters)


def phase_compaction(total_bytes=TABLE_BYTES, target_bytes=TARGET_BYTES,
                     file_mib=FILE_MIB, seed=0, max_cycles=3):
    """Returns the chunk count of the largest plain gather and the
    (n_src_chunks, n_steps, n_out) of the largest fused filter, for the
    HLO check."""
    from repro.data import merge_shards_fn
    from repro.kernels import api
    from repro.kernels.compact_pack.compact_pack import CHUNK_TOKENS
    from repro.launch.train import build_autocomp

    t0 = time.perf_counter()
    catalog, table, store, clock, ref = build_table(total_bytes, file_mib,
                                                    seed)
    n_files0 = table.file_count()
    tok0 = sum(t.size for t in ref.values())
    print(f"[compaction] table: {n_files0} files, {tok0} tokens "
          f"({tok0 * 4 / GiB:.3f} GiB), built in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    rewrites = []                      # (inputs, bytes, chunks, seconds)

    def timed_merge(tbl, task, out_path, **kw):
        t = time.perf_counter()
        out = merge_shards_fn(tbl, task, out_path, **kw)
        chunks = sum(-(-f.num_rows // CHUNK_TOKENS) for f in task.inputs)
        rewrites.append((len(task.inputs), task.input_bytes, chunks,
                         time.perf_counter() - t))
        return out

    autocomp = build_autocomp(catalog, clock, target_bytes=target_bytes)
    autocomp.scheduler.merge_fn = timed_merge
    with api.record_dispatches() as dispatches:
        compact_cycles(autocomp, catalog, table, store, ref, max_cycles)
        n_files1 = table.file_count()
        check(n_files1 < n_files0,
              f"file count fell: {n_files0} -> {n_files1}")
        live_tokens = sum(_tokens(store, f).size
                          for f in table.current_files())
        check(live_tokens == tok0,
              f"live tokens preserved: {live_tokens} == {tok0}")
        n_gathers = len(rewrites)
        filt = rewrite_delete(table, store, ref, timed_merge)

    for i, (n_in, nbytes, chunks, dt) in enumerate(rewrites):
        kind = "gather" if i < n_gathers else "filter"
        print(f"[compaction] rewrite {i} ({kind}): {n_in} input files, "
              f"{nbytes} bytes, {chunks} chunks, {dt:.3f}s wall "
              f"({nbytes / dt / 1e9:.2f} GB/s smoke figure, host "
              f"copies included)", flush=True)
    for d in dispatches:
        print(f"[compaction] kernel call {d.op} {d.shape_key}: point "
              f"{d.point} from {d.source}", flush=True)
    check(len(dispatches) == len(rewrites)
          and all(d.op == "compact_pack" for d in dispatches),
          f"all {len(rewrites)} rewrites dispatched the compact_pack "
          f"kernel (none took use_ref)")
    check(all(d.source == "default" for d in dispatches),
          "every block point is the registry default, none read from "
          "experiments/tuned/")
    print(f"[compaction] smoke figures: files {n_files0} -> {n_files1} -> "
          f"{table.file_count()}, phase wall "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return max(r[2] for r in rewrites[:n_gathers]), filt


def check_kernel_hlo(gather, filt) -> None:
    """Compile the two kernel programs the compaction phase ran, at the
    largest shapes it ran them, and look for the Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import api
    from repro.kernels.compact_pack import ops
    from repro.kernels.compact_pack.compact_pack import (CHUNK_COLS,
                                                         CHUNK_ROWS)

    interpret = api.use_interpret()
    check(not interpret, "Pallas kernels compile (interpret mode is off)")

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    hlo = ops.compact_gather.lower(
        sds(gather, CHUNK_ROWS, CHUNK_COLS), sds(gather),
        interpret=interpret).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"gather kernel HLO at {gather} chunks has tpu_custom_call "
          f"({hlo.count('tpu_custom_call')} segment calls)")
    n_src, n_steps, n_out = filt
    hlo = ops.compact_filter.lower(
        sds(n_src, CHUNK_ROWS, CHUNK_COLS), sds(n_steps),
        sds(n_steps * CHUNK_ROWS), sds(n_steps), sds(n_steps),
        n_out=n_out, interpret=interpret).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"fused filter HLO at {n_steps} touched chunks has "
          f"tpu_custom_call ({hlo.count('tpu_custom_call')} segment calls)")


# ---------------------------------------------------------------------------
# phase 2: serving at full width
# ---------------------------------------------------------------------------

def forward_rows(cfg, params, prompts, out):
    """The full-forward logit rows that predict each generated token,
    float32 (B, n, vocab), from one forward of prompt + output."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer

    s0, n = prompts.shape[1], out.shape[1]

    @jax.jit
    def fwd(p, tokens):
        logits, _ = transformer.forward(cfg, p, {"tokens": tokens}, "encode")
        return logits[:, s0 - 1:s0 - 1 + n].astype(jnp.float32)

    return np.asarray(fwd(params, jnp.asarray(
        np.concatenate([prompts, out], axis=1))))


def row_dev(rows, ref):
    """Per row, max |rows - ref| over the vocabulary in units of the
    reference row's standard deviation."""
    return abs(rows - ref).max(-1) / ref.std(-1)


def check_against_forward(cfg, params, prompts, out, rows, label, tol):
    """Every serving logit row within ``tol`` of the full forward's row,
    and the check able to see a broken cache: the same output tokens
    after another request's prompt (a cache from the wrong slot) must
    break the limit on most decode rows."""
    import numpy as np

    ref = forward_rows(cfg, params, prompts, out)
    check(bool(np.isfinite(ref).all()), f"{label}: full-forward logits finite")
    dev = row_dev(rows, ref)
    wrong = row_dev(forward_rows(cfg, params, np.roll(prompts, 1, axis=0),
                                 out), ref)[:, 1:]
    agree = int((ref.argmax(-1) == out).sum())
    print(f"[serve] {label}: serving vs full-forward logit rows, max |d| / "
          f"row std: max {float(dev.max()):.5f}, median "
          f"{float(np.median(dev)):.5f}; argmax agrees on {agree}/{out.size}; "
          f"{len(set(out.flat))} distinct tokens; wrong-slot context: "
          f"median {float(np.median(wrong)):.5f}, min "
          f"{float(wrong.min()):.5f}", flush=True)
    check(bool((dev <= tol).all()),
          f"{label}: every serving logit row within {tol} row std of the "
          f"full forward's")
    blind = float((wrong <= tol).mean())
    check(blind <= 0.5,
          f"{label}: a wrong-slot cache breaks that limit on "
          f"{1 - blind:.3f} of the decode rows")


def serve_once(serve, cfg, params, prompts, kwargs, label):
    """One ``generate`` call with the logit rows kept; returns the tokens
    and the rows each token was chosen from."""
    import jax
    import numpy as np

    t = time.perf_counter()
    out = serve.generate(cfg, params, prompts, **kwargs, keep_logits=True)
    dt = time.perf_counter() - t
    rows = (serve._generate_slots if kwargs["stream"] == "slots"
            else serve.generate).last_stats["logits"]
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) / GiB
            for d in jax.devices()]
    print(f"[serve] {label}: {out.size} tokens in {dt:.2f}s wall (smoke "
          f"figure, compile included); peak GiB in use per chip so far "
          f"{[round(p, 2) for p in peak]}; first row {out[0].tolist()}",
          flush=True)
    check(bool(np.isfinite(rows).all()),
          f"{label}: every serving logit finite")
    check(bool(((out >= 0) & (out < cfg.vocab)).all()),
          f"{label}: token ids within the {cfg.vocab}-token vocabulary")
    check(bool((rows.argmax(-1) == out).all()),
          f"{label}: every greedy token is the argmax of its kept row")
    return out, rows


def phase_serving(argv=SERVE_ARGS):
    from repro.launch import serve

    t0 = time.perf_counter()
    args = serve.build_parser().parse_args(argv)
    cfg, params, prompts, kwargs = serve.setup(args)
    print(f"[serve] {cfg.name}: {cfg.param_count() / 1e9:.2f}B params "
          f"placed in {time.perf_counter() - t0:.1f}s", flush=True)
    for stream in ("batch", "slots"):
        label = f"stream={stream}"
        out, rows = serve_once(serve, cfg, params, prompts,
                               {**kwargs, "stream": stream}, label)
        check_against_forward(cfg, params, prompts, out, rows, label,
                              ROW_TOL)


def phase_disagg(argv=DISAGG_ARGS):
    import gc

    from repro.launch import serve

    parser = serve.build_parser()
    args = parser.parse_args(argv + ["--disagg", "--cache-transfer", "bf16"])
    cfg, params, prompts, kwargs = serve.setup(args)
    pre, dec = kwargs["mesh"], kwargs["decode_mesh"]
    print(f"[disagg] {cfg.name}: prefill chips "
          f"{[d.id for d in pre.devices.flat]}, decode chips "
          f"{[d.id for d in dec.devices.flat]}", flush=True)
    runs = {"bf16": serve_once(serve, cfg, params, prompts, kwargs,
                               "disagg cache-transfer=bf16")}
    runs["int8"] = serve_once(serve, cfg, params, prompts,
                              {**kwargs, "cache_transfer": "int8"},
                              "disagg cache-transfer=int8")
    del params
    gc.collect()
    cfg, params, prompts, kwargs = serve.setup(parser.parse_args(argv))
    colo, _ = serve_once(serve, cfg, params, prompts, kwargs,
                         f"colocated mesh {dict(kwargs['mesh'].shape)}")
    dis = runs["bf16"][0]
    check(bool((dis == colo).all()),
          f"bf16 disaggregated tokens equal the colocated tokens "
          f"({int((dis == colo).sum())}/{dis.size} equal)")
    for mode, tol in (("bf16", ROW_TOL), ("int8", ROW_TOL_INT8)):
        check_against_forward(cfg, params, prompts, *runs[mode],
                              f"disagg cache-transfer={mode}", tol)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: compaction + serving on one chip; 4: only "
                         "the disaggregated serving path across 4 chips")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache(disaggregated=args.chips == 4)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs only on a chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    print(f"[device] {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {cache or 'off (disaggregated serving)'}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_disagg()
        else:
            gather, filt = phase_compaction()
            check_kernel_hlo(gather, filt)
            phase_serving()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n_cached = len(list(cache.glob("*"))) if cache and cache.is_dir() else 0
    print(f"[done] {time.perf_counter() - t0:.1f}s; {n_cached} entries in "
          f"the compile cache", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
