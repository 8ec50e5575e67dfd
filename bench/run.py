#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with the plain reference beside its
limit, also printed as the last lines of standard error. Without a TPU,
or with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench.harness.core import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
