"""decode_step.roofline (%): the decode steps' least time (the larger of
least operations at peak FLOP/s and least bytes at peak bandwidth, each
step from the published configuration and its live positions) over the
device time of the ``decode_step`` program's events. Counted from the
configuration, so it reads the same whatever computes the attention."""

from bench.harness import trace as T
from bench.harness import work


def reduce(run):
    if run.trace is None or run.peak is None or not run.records:
        return None
    steps = [s for r in run.records for s in work.generate_call(
        run.config, r["lens"], r["max_new"], r["slots"])["decode"]]
    device_s, n = T.module_s(run.trace, "decode_step")
    if not device_s or n != len(steps):
        return None
    least = sum(work.roofline_s(f, b, run.peak) for f, b in steps)
    return 100.0 * least / device_s
