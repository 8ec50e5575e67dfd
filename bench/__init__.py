"""On-chip benchmark of the compaction service and the model server.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on. Every
part that belongs to one configuration, traffic mix, driver or per-layer
metric is a file of its own under this directory, found by its name:

- ``configs/<config>.json``   the configuration as it is run
- ``traffic/<traffic>.json``  the traffic parameters; names its driver
- ``drivers/<driver>.py``     the closed loop that drives the system
- ``metrics/<metric>.py``     ``reduce(run) -> value | None``
- ``reference/``              the plain references that decide ``correct``
- ``harness/``                trace reduction, peaks, work counts, timing
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_DIR = ROOT / "bench"
_SRC = str(ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
