"""retrace_share.serve (%): re-tracing in the slot engine
(``launch/serve.py::_generate_slots`` builds new ``jax.jit`` objects on
every call): device-idle time inside JAX's tracing and lowering events
within the program's ``serve.generate`` spans, over the traced window."""

from bench.harness import trace as T

GENERATE = "serve.generate"
# JAX's host events for tracing and lowering a program, on the calling
# thread (jax 0.9.0)
LOWERING = ("trace_to_jaxpr_dynamic", "lower_sharding_computation")


def reduce(run):
    tr = run.trace
    if tr is None or not tr.ops or not T.spans(tr, GENERATE):
        return None
    lowering = T.intersect(
        T.union((e.start, e.end) for e in tr.host if e.name in LOWERING),
        T.spans(tr, GENERATE))
    idle = [T.total(lowering) - T.total(T.intersect(lowering, b))
            for b in T.busy(tr)]
    return 100.0 * sum(idle) / len(idle) * T.NS / T.window_s(tr)
