"""Run one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
a ``breakdown``), then ``checks``: every number compared, beside its
limit.  A run that finds no TPU, fewer chips than the cell asks for, a
device kind without published peaks, or a dispatch switch that takes the
kernels off the chip exits nonzero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
