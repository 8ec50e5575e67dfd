"""moe_serve.mfu (%): the model operations of every real prompt token
and every generated token in the traced window (published configuration;
the held experts at this chip's share, top_k x 8/64 experts a token;
nothing recomputed counts), over the window, over the chip's peak bf16
FLOP/s. For the MLA + held-share expert model (``work_moe.py``)."""

from bench.harness import trace as T
from bench.harness import work_moe


def reduce(run):
    if run.trace is None or run.peak is None or not run.records:
        return None
    if any("moe_held_pairs" not in r for r in run.records):
        return None
    flops = sum(work_moe.generate_call(run.config, r["lens"], r["max_new"],
                                       r["slots"], r["moe_held_pairs"]
                                       )["model_flops"]
                for r in run.records)
    return 100.0 * flops / T.window_s(run.trace) / \
        run.peak["bf16_flops_per_s"]
