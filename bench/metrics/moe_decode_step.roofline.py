"""moe_decode_step.roofline (%): the decode steps' least time (each step
the larger of least operations at peak FLOP/s and least bytes at peak
bandwidth, from the published configuration, the step's live positions
and the held pairs the program counted, spread evenly over the call's
steps) over the device time of the ``decode_step`` program's events.
For the MLA + held-share expert model (``work_moe.py``)."""

from bench.harness import trace as T
from bench.harness import work
from bench.harness import work_moe


def reduce(run):
    if run.trace is None or run.peak is None or not run.records:
        return None
    if any("moe_held_pairs" not in r for r in run.records):
        return None
    steps = [s for r in run.records for s in work_moe.generate_call(
        run.config, r["lens"], r["max_new"], r["slots"],
        r["moe_held_pairs"])["decode"]]
    device_s, n = T.module_s(run.trace, "decode_step")
    if not device_s or n != len(steps):
        return None
    least = sum(work.roofline_s(f, b, run.peak) for f, b in steps)
    return 100.0 * least / device_s
