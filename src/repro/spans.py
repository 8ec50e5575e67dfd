"""Host spans the program issues into the JAX profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: it lands on the calling
thread's line of the trace, on the clock of the device's ``XLA Ops`` and
``XLA Modules`` lines, and costs about a microsecond when no profiler
runs. Capture them with ``jax.profiler.trace(dir)`` around a compaction
cycle or a ``generate`` call and read them in Perfetto or TensorBoard.

Every span name, and the name of every kernel a per-layer metric reads
from the device trace, is defined here and nowhere else. A name never carries a
value: counts (files, bytes, request, step) are the span's arguments.
``lst/`` and ``core/`` import no JAX, so where JAX is not loaded ``span``
returns a no-op context: nothing can be profiling that process.
"""

from __future__ import annotations

import contextlib
import sys

# lst/table.py
TABLE_COMMIT = "table.commit"
TABLE_REBASE = "table.rebase"
TABLE_MANIFEST = "table.manifest"
TABLE_METADATA = "table.metadata"
# core/ooda.py
AUTOCOMP_CYCLE = "autocomp.cycle"
AUTOCOMP_PROPOSE = "autocomp.propose"
AUTOCOMP_DECIDE = "autocomp.decide"
AUTOCOMP_ACT = "autocomp.act"
# data/packing.py::merge_shards_fn
MERGE_SHARDS = "merge.shards"
MERGE_READ = "merge.read"
MERGE_CONCAT = "merge.concat"
MERGE_FILTER = "merge.filter"
MERGE_DEVICE = "merge.device"
MERGE_RESLICE = "merge.reslice"
MERGE_ENCODE = "merge.encode"
MERGE_STORE = "merge.store"
# launch/serve.py::_generate_slots
SERVE_GENERATE = "serve.generate"
SERVE_SETUP = "serve.setup"
SERVE_PREFILL = "serve.prefill"
SERVE_ADMIT = "serve.admit"
SERVE_TRANSFER_WAIT = "serve.transfer_wait"
SERVE_DECODE = "serve.decode"
SERVE_SAMPLE = "serve.sample"
SERVE_EMIT = "serve.emit"

# Kernel names: the HLO instruction a device trace's ``XLA Ops`` line
# shows for each named kernel (``<name>.<n>``)
# kernels/expert_gmm: the held experts' grouped matmul
KERNEL_EXPERT_GMM = "expert_gmm"

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args):
    """A context that records ``name`` (with ``args``) in the profiler's
    trace when one is being captured."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **args)
