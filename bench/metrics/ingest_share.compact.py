"""ingest_share.compact (%): the table's metadata commits
(``lst/table.py``): time in ``ingest`` spans, where the pool of small
files is committed to a fresh table one flush at a time, over the traced
window."""

from bench.harness import trace as T


def reduce(run):
    tr = run.trace
    if tr is None or not T.spans(tr, "ingest"):
        return None
    return 100.0 * T.total(T.spans(tr, "ingest")) * T.NS / T.window_s(tr)
