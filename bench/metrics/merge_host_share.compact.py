"""merge_host_share.compact (%): the host side of the shard merge
(``data/packing.py::merge_shards_fn``): time inside ``merge`` spans in
which the device ran nothing, over the traced window."""

from bench.harness import trace as T


def reduce(run):
    tr = run.trace
    if tr is None or not tr.ops or not T.spans(tr, "merge"):
        return None
    return 100.0 * T.idle_s_in(tr, "merge") / T.window_s(tr)
