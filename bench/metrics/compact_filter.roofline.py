"""compact_filter.roofline (%): the filtering rewrites' least HBM time
(each kept byte read once and written once, at peak bandwidth) over the
device time of every operation inside ``merge_filter`` spans."""

from bench.harness import trace as T
from bench.harness import work


def reduce(run):
    tr = run.trace
    merges = [m for r in run.records for m in r["merges"]
              if m["kind"] == "merge_filter"]
    if tr is None or run.peak is None or not merges:
        return None
    device_s = T.device_s_in(tr, "merge_filter")
    if not device_s:
        return None
    least = sum(work.filter_bytes(m["output_bytes"])
                for m in merges) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / device_s
