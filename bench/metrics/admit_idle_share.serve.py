"""admit_idle_share.serve (%): the slot engine's admissions
(``launch/serve.py::_generate_slots``): device-idle time inside the
program's ``serve.prefill`` (a request's prefill dispatch) and
``serve.admit`` (the wait for its cache slice and the row write) spans,
less JAX's tracing and lowering events (``retrace_share.serve`` counts
those), over the traced window."""

from bench.harness import trace as T

ADMISSION = ("serve.prefill", "serve.admit")
# JAX's host events for tracing and lowering a program, on the calling
# thread (jax 0.9.0)
LOWERING = ("trace_to_jaxpr_dynamic", "lower_sharding_computation")


def reduce(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    admission = T.union(iv for name in ADMISSION for iv in T.spans(tr, name))
    if not admission:
        return None
    lo, hi = tr.window
    lowering = T.clip(T.union((e.start, e.end) for e in tr.host
                              if e.name in LOWERING), lo, hi)
    own = T.subtract(admission, lowering)
    # busy and own are sorted and disjoint: their intersection is linear
    idle = [T.total(own) - T.total(T.intersect(own, b)) for b in T.busy(tr)]
    return 100.0 * sum(idle) / len(idle) * T.NS / T.window_s(tr)
