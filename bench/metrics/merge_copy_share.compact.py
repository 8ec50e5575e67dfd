"""merge_copy_share.compact (%): the shard merge's host copies
(``data/packing.py::merge_shards_fn``): time in the program's
``merge.concat`` spans (the concatenate of the payloads and the plan) or
``merge.reslice`` spans (the per-input re-slice and its concatenate),
over the traced window."""

from bench.harness import trace as T

COPIES = ("merge.concat", "merge.reslice")


def reduce(run):
    tr = run.trace
    if tr is None:
        return None
    copies = T.union(iv for name in COPIES for iv in T.spans(tr, name))
    if not copies:
        return None
    return 100.0 * T.total(copies) * T.NS / T.window_s(tr)
