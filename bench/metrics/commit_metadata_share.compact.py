"""commit_metadata_share.compact (%): the table's metadata writes
(``lst/table.py::_persist_metadata``, on every commit): time in the
program's ``table.metadata`` spans, over the traced window."""

from bench.harness import trace as T

METADATA = "table.metadata"


def reduce(run):
    tr = run.trace
    if tr is None or not T.spans(tr, METADATA):
        return None
    return 100.0 * T.total(T.spans(tr, METADATA)) * T.NS / T.window_s(tr)
