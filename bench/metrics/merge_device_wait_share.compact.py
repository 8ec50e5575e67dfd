"""merge_device_wait_share.compact (%): what the shard merge's device
step costs the host beyond the device's own work
(``data/packing.py::merge_shards_fn``): time inside the program's
``merge.device`` spans (upload, kernel dispatch, read-back) in which the
device ran nothing, over the traced window."""

from bench.harness import trace as T

DEVICE = "merge.device"


def reduce(run):
    tr = run.trace
    if tr is None or not tr.ops or not T.spans(tr, DEVICE):
        return None
    return 100.0 * T.idle_s_in(tr, DEVICE) / T.window_s(tr)
