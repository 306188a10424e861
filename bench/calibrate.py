"""Readings that the benchmark's limits and rates are set from, made on
the chip in one process (the benchmark's own runs never run this).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 15]
    python bench/calibrate.py --workload <cell> --rates 1,2,3 --seconds 25

``--seeds``: for each seed, the number each check compares, read from
the program and from the control (the reference computed in the next
lower precision).  ``--rates`` (serving cells): a sweep of offered load,
to find the highest rate the system sustains.  One JSON line per reading
goes to standard output.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    harness.refuse_env(os.environ)
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell = harness.load_cell(args.workload)
    run = harness.Run(cell=cell, config=harness.load_json("configs", cell.workload["config"]),
                      traffic=harness.load_json("traffic", cell.workload["traffic"]),
                      seed=0, seconds=args.seconds, trace=False, t_process=T_PROCESS)
    harness.device_check(run)
    harness.enable_cache()
    runner = harness.plugin("runners", run.config["runner"])

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if args.rates:
        rows = runner.sweep(run, [float(r) for r in args.rates.split(",")], log)
    else:
        rows = runner.readings(run, [int(s) for s in args.seeds.split(",")], log)
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
