#!/usr/bin/env python3
"""The readings each ``correct`` limit is set from: the program's own, on
many seeds, and the controls', which have to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 4,5] [--controls fp8,int8] [--seconds 1]

Every reading is one whole run of the cell through ``core.run_cell`` in
this one process (set-up, whole iterations for ``--seconds``, the
program's state freed, the check against the cell's limits), so a
control's ``correct`` is decided by the same Check as a benchmark run's.
First the program on each of ``--seeds``, then each control on each of
``--control-seeds``:

- serving cells: the float32 reference put in the program's place and
  computed with every linear layer in a lower precision (``fp8`` e4m3 or
  ``int8``, weights per output channel, inputs per token); the check
  reads the gaps, below the float32 reference's best, of the tokens that
  the lower precision puts first over the same prompts and served
  tokens;
- compaction cells: the program run with one guarantee broken
  (``row_swap``: the first two 128-token rows of every merged file
  swapped, a reordering within a fragment).

Prints one JSON line per run: the seed, the side (``program`` or the
control's name), ``correct`` and each number beside its limit. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench.harness import core  # noqa: E402

SERVING_CONTROLS = ("fp8", "int8")


@contextlib.contextmanager
def row_swap():
    """Every merged file's first two 128-token rows swapped where the
    kernel's output comes back."""
    from repro.data import packing

    orig = packing.compact_chunks

    def swapped(*args, **kw):
        import jax.numpy as jnp
        out = orig(*args, **kw)
        if out.shape[0] >= 256:
            out = jnp.concatenate([out[128:256], out[:128], out[256:]])
        return out
    packing.compact_chunks = swapped
    try:
        yield
    finally:
        packing.compact_chunks = orig


def lower_precision(driver_cls, quant: str):
    """The serving driver whose check reads the reference computed in
    ``quant`` in the program's place."""
    class Control(driver_cls):
        def check(self):
            return super().check(quant=quant)
    return Control


def run(parts, seed: int, seconds: float, control=None, log=None) -> dict:
    """One run of the cell; ``control`` is ``None`` for the program, a
    serving precision, or ``"row_swap"``."""
    cell, config, traffic, e2e, per_layer, limits = parts
    driver_cls = core.load_module("drivers", traffic["driver"]).Driver
    guard = row_swap() if control == "row_swap" else contextlib.nullcontext()
    if control in SERVING_CONTROLS:
        driver_cls = lower_precision(driver_cls, control)
    with guard:
        return core.run_cell(cell, config, traffic, e2e, per_layer, limits,
                             seed, seconds, False, time.perf_counter(),
                             log=log, driver_cls=driver_cls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers: program runs")
    ap.add_argument("--control-seeds", default="",
                    help="comma-separated whole numbers: control runs")
    ap.add_argument("--controls", default=",".join(SERVING_CONTROLS),
                    help="serving cells: the lower precisions to read")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    parts = core.cell_parts(core.benchmark(), args.workload)
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    core.devices_or_fail(int(parts[0]["chips"]))

    def seeds(text):
        return [int(s) for s in text.split(",") if s]
    controls = (args.controls.split(",") if parts[2]["driver"] == "serving"
                else ["row_swap"])
    runs = [(s, None) for s in seeds(args.seeds)] + \
        [(s, c) for s in seeds(args.control_seeds) for c in controls]
    for seed, control in runs:
        res = run(parts, seed, args.seconds, control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": control or "program",
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
