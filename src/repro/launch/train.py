"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Wires together: config -> model -> sharded train_step (jit with logical-rule
shardings on the local mesh) -> AutoComp-managed data pipeline -> fault-
tolerant Trainer. On this CPU container it runs reduced configs end-to-end;
on a TPU fleet the same entry point runs the full configs (mesh comes from
``jax.devices()``).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core import (AutoCompPipeline, MoopRanker, StatsCollector,
                        TraitContext)
from repro.core.act import Scheduler
from repro.core.model import Scope
from repro.core.orient import (ComputeCostTrait, FileCountReductionTrait,
                               FileEntropyTrait)
from repro.data import DataPipeline, TokenShardWriter, merge_shards_fn
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.lst import Catalog, InMemoryStore
from repro.lst.workload import SimClock
from repro.models import transformer
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib
from repro.train.checkpoints import CheckpointManager
from repro.train.runner import RunnerConfig, Trainer


def build_data(cfg, *, batch, seq_len, n_trickle=30, files_per=15,
               tokens_per_file=4096, seed=0):
    clock = SimClock()
    store = InMemoryStore()
    catalog = Catalog(store, now_fn=clock.now)
    table = catalog.create_table("train", "corpus",
                                 properties={"conflict_granularity": "table"})
    table.now_fn = clock.now
    writer = TokenShardWriter(table, vocab=cfg.vocab, seed=seed)
    for _ in range(n_trickle):
        writer.trickle_append(files_per, tokens_per_file)
        clock.advance(0.02)
    pipe = DataPipeline(table, batch=batch, seq_len=seq_len, seed=seed)
    return catalog, table, pipe, clock, store


def build_autocomp(catalog, clock, target_bytes=1 << 22, top_k=4):
    pipeline = AutoCompPipeline(
        stats=StatsCollector(target_bytes),
        traits=(FileCountReductionTrait(), FileEntropyTrait(),
                ComputeCostTrait()),
        trait_ctx=TraitContext(target_file_bytes=target_bytes),
        ranker=MoopRanker({"file_count_reduction": 0.7, "compute_cost": 0.3}),
        scheduler=Scheduler(target_bytes, merge_fn=merge_shards_fn),
        scope=Scope.TABLE, top_k=top_k)
    return pipeline


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config of the arch family")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--grad-transport", default="bf16",
                    choices=step_lib.GRAD_TRANSPORTS,
                    help="int8_ef = blockwise int8 + error feedback on the "
                         "gradient reduction (residual in optimizer state)")
    ap.add_argument("--compact-every", type=int, default=25)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh()
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    catalog, table, pipe, clock, store = build_data(
        cfg, batch=args.batch, seq_len=args.seq_len)
    print(f"[data] shard files: {table.file_count()} "
          f"(plan {pipe.plan()[0].path.split('/')[-1]}...)")

    params = transformer.init_params(
        cfg, jax.random.PRNGKey(0),
        shd.tree_shardings(transformer.abstract_params(cfg),
                           transformer.param_axes(cfg), mesh))
    opt_state = opt_lib.init_state(
        params, error_feedback=args.grad_transport == "int8_ef")
    adamw = opt_lib.AdamWConfig(lr=1e-3, warmup_steps=10,
                                total_steps=args.steps)
    with shd.axis_rules(mesh):
        step_fn = jax.jit(step_lib.make_train_step(
            cfg, adamw, microbatches=args.microbatches,
            grad_transport=args.grad_transport))

    ckpt = CheckpointManager(store, keep_last=2)
    autocomp = build_autocomp(catalog, clock)
    state = {"i": 0}

    def tick():
        state["i"] += 1
        clock.advance(0.01)
        if state["i"] % args.compact_every == 0:
            rep = autocomp.run_cycle(catalog)
            if rep.files_removed:
                print(f"[autocomp] cycle: removed {rep.files_removed} files "
                      f"-> table now {table.file_count()} files "
                      f"(gbhr {rep.gbhr:.4f})")

    trainer = Trainer(
        RunnerConfig(total_steps=args.steps, ckpt_every=20),
        step_fn, params, opt_state, pipe.prefetching_batches,
        ckpt=ckpt, autocomp_tick=tick)
    t0 = time.time()
    out = trainer.run_with_recovery()
    dt = time.time() - t0
    losses = [h["loss"] for h in out["history"]]
    print(f"[train] {out['final_step']} steps in {dt:.1f}s "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training did not reduce loss"
    print(f"[store] objects={store.object_count} "
          f"rpc={store.metrics.rpc_total}")


if __name__ == "__main__":
    main()
