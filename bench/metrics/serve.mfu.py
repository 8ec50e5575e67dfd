"""serve.mfu (%): the model operations of every real prompt token and
every generated token in the traced window (published configuration,
nothing recomputed counts), over the window, over the chip's peak bf16
FLOP/s."""

from bench.harness import trace as T
from bench.harness import work


def reduce(run):
    if run.trace is None or run.peak is None or not run.records:
        return None
    flops = sum(work.generate_call(run.config, r["lens"], r["max_new"],
                                   r["slots"])["model_flops"]
                for r in run.records)
    return 100.0 * flops / T.window_s(run.trace) / \
        run.peak["bf16_flops_per_s"]
