"""merge_encode_share.compact (%): the shard merge's output
(``data/packing.py::merge_shards_fn``): time in the program's
``merge.encode`` spans (``encode_shard``) or ``merge.store`` spans (the
output's ``store.put``), over the traced window."""

from bench.harness import trace as T

OUTPUT = ("merge.encode", "merge.store")


def reduce(run):
    tr = run.trace
    if tr is None:
        return None
    out = T.union(iv for name in OUTPUT for iv in T.spans(tr, name))
    if not out:
        return None
    return 100.0 * T.total(out) * T.NS / T.window_s(tr)
