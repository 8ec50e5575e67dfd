"""step_gap_ms.serve (ms): the slot engine's host time per decode step
(``launch/serve.py::_generate_slots``): device-idle time inside the
program's ``serve.decode`` (upload and dispatch), ``serve.sample``
(argmax and read-back) and ``serve.emit`` (slot bookkeeping) spans, less
JAX's tracing and lowering events (``retrace_share.serve`` counts
those), over the number of ``serve.decode`` spans in the window."""

from bench.harness import trace as T

STEP = ("serve.decode", "serve.sample", "serve.emit")
# JAX's host events for tracing and lowering a program, on the calling
# thread (jax 0.9.0)
LOWERING = ("trace_to_jaxpr_dynamic", "lower_sharding_computation")


def reduce(run):
    tr = run.trace
    if tr is None or not tr.ops or not T.spans(tr, STEP[0]):
        return None
    lo, hi = tr.window
    step = T.union(iv for name in STEP for iv in T.spans(tr, name))
    lowering = T.clip(T.union((e.start, e.end) for e in tr.host
                              if e.name in LOWERING), lo, hi)
    own = T.subtract(step, lowering)
    # busy and own are sorted and disjoint: their intersection is linear
    idle = [T.total(own) - T.total(T.intersect(own, b)) for b in T.busy(tr)]
    steps = sum(1 for e in tr.host
                if e.name == STEP[0] and lo <= e.start < hi)
    return 1e3 * sum(idle) / len(idle) * T.NS / steps
