"""Device idle share (%): one minus the union of the chip's operation
intervals over the traced window."""

from bench.harness import trace as T


def reduce(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * T.idle_share(run.trace)
