"""prefill_step.roofline (%): the prefills' least time (the larger of
least operations, on real prompt tokens with causal attention, at peak
FLOP/s and least bytes at peak bandwidth) over the device time of the
``prefill_step`` program's events."""

from bench.harness import trace as T
from bench.harness import work


def reduce(run):
    if run.trace is None or run.peak is None or not run.records:
        return None
    pre = [p for r in run.records for p in work.generate_call(
        run.config, r["lens"], r["max_new"], r["slots"])["prefill"]]
    device_s, n = T.module_s(run.trace, "prefill_step")
    if not device_s or n != len(pre):
        return None
    least = sum(work.roofline_s(f, b, run.peak) for f, b in pre)
    return 100.0 * least / device_s
