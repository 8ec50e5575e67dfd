"""merge_read_share.compact (%): the shard merge's reads
(``data/packing.py::merge_shards_fn``): time in the program's
``merge.read`` spans, the store reads and both decodes of every input,
over the traced window."""

from bench.harness import trace as T

READ = "merge.read"


def reduce(run):
    tr = run.trace
    if tr is None or not T.spans(tr, READ):
        return None
    return 100.0 * T.total(T.spans(tr, READ)) * T.NS / T.window_s(tr)
