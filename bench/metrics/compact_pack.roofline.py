"""compact_pack.roofline (%): the plain rewrites' least HBM time (every
input byte read once, every output byte written once, at peak
bandwidth) over the device time of every operation inside ``merge``
spans. Counted over the rewrite's work, not the kernel's, so a change of
kernel keeps the metric."""

from bench.harness import trace as T
from bench.harness import work


def reduce(run):
    tr = run.trace
    merges = [m for r in run.records for m in r["merges"]
              if m["kind"] == "merge"]
    if tr is None or run.peak is None or not merges:
        return None
    device_s = T.device_s_in(tr, "merge")
    if not device_s:
        return None
    least = sum(work.gather_bytes(m["input_bytes"], m["output_bytes"])
                for m in merges) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / device_s
