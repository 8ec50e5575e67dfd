"""moe_experts.roofline (%): the held experts' least time in the decode
steps (each held expert's weights read once a step, the routed tokens in
and out, and the operations of the ``moe_held_pairs`` the program
counted; at peak FLOP/s or bandwidth, whichever is slower) over the
device time of the grouped-matmul kernel's operations inside
``decode_step`` programs. ``None`` where the trace holds no such
operation, or the program counted nothing."""

import bisect
import re

from bench.harness import trace as T
from bench.harness import work
from bench.harness import work_moe


def kernel_s_in_module(tr, kernel: str, module: str):
    """Chip 0's device seconds of the operations named ``kernel`` or
    ``kernel.<n>`` that run inside programs ``module``, in the window."""
    if not tr.ops:
        return 0.0
    lo, hi = tr.window
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    mods = [m for m in tr.modules[0] if T.module_name(m.name) == module]
    starts = [m.start for m in mods]
    total = 0.0
    for e in tr.ops[0]:
        if not pat.match(T.op_name(e.name)):
            continue
        mid = (e.start + e.end) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mods[i].end > mid:
            total += max(0.0, min(e.end, hi) - max(e.start, lo))
    return total * T.NS


def reduce(run):
    try:
        from repro.spans import KERNEL_EXPERT_GMM
    except ImportError:            # a program without the kernel
        return None
    if run.trace is None or run.peak is None or not run.records:
        return None
    if any("moe_held_pairs" not in r for r in run.records):
        return None
    device_s = kernel_s_in_module(run.trace, KERNEL_EXPERT_GMM,
                                  "decode_step")
    if not device_s:
        return None
    least = sum(work.roofline_s(*work_moe.experts_decode(
        run.config, r["moe_held_pairs"], r["decode_steps"]), run.peak)
        for r in run.records)
    return 100.0 * least / device_s
