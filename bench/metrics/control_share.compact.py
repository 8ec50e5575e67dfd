"""control_share.compact (%): the compaction control plane's share of the
window (``core/ooda.py``, ``core/act.py``): time in ``cycle`` spans
outside ``merge`` spans, over the traced window."""

from bench.harness import trace as T


def reduce(run):
    tr = run.trace
    if tr is None or not T.spans(tr, "cycle"):
        return None
    own = T.subtract(T.spans(tr, "cycle"), T.spans(tr, "merge"))
    return 100.0 * T.total(own) * T.NS / T.window_s(tr)
