#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last the
compared numbers under ``checks``).  Without an accelerator, or with fewer
chips than the cell needs, it prints no result and exits non-zero.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(_ROOT, ".bench_out", "tpu_logs"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
