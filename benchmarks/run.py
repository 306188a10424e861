"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only copy,permute,...] [--smoke]

Prints ``name,us_per_call,derived`` CSV per row (derived = achieved GB/s
and fraction of host memcpy — the paper's normalization), and writes the
machine-readable record stream to ``BENCH_rearrange.json`` (op name,
achieved GB/s, fraction of memcpy, plan mode) so the perf trajectory is
tracked across PRs.  The stencil suite's rows (fused vs per-sweep plan
engine comparison) are additionally written to ``BENCH_stencil.json``,
the MoE dispatch suite's rows (dense vs rowwise-sort vs fused-sort
IndexPlan comparison) to ``BENCH_moe.json``, the mesh-aware suite's
rows (DistPlan strategies with bytes-on-wire accounting, run on 8 forced
host devices in a subprocess) to ``BENCH_dist.json``, and the serving
suite's rows (split-KV vs one-shot decode, ragged vs bucket prefill, the
multi-tenant trace with tokens/s and p50/p99 per-token latency) to
``BENCH_serve.json``, and the training suite's rows (flash fwd/bwd and
FFN phase rooflines, monolithic vs blockwise-parallel train step with
tokens/s/device) to ``BENCH_train.json``.

The head-permute and stencil suites also report the autotuned plan next
to the heuristic one (``plan_source`` field, DESIGN.md §11) so tuned and
heuristic measured paths are tracked side by side.

``--smoke`` runs every suite on tiny deterministic shapes with reduced
timing loops (interpret-safe), and — unless a ``--json*`` path is given
explicitly — suppresses the JSON artifacts so a smoke run can never
overwrite the committed bare-metal ``BENCH_*.json`` numbers.  This is
what ``tools/check_bench.py`` (``make bench-check``) replays on every PR.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import common
from repro.launch.compile_cache import enable_compile_cache

SUITES = [
    ("copy", "benchmarks.bench_copy", "Fig. 1 read/write kernels"),
    ("permute", "benchmarks.bench_permute", "Table 1 3D permute"),
    ("reorder", "benchmarks.bench_reorder", "Table 2 generic reorder"),
    ("interlace", "benchmarks.bench_interlace", "Table 3 interlace/deinterlace"),
    ("stencil", "benchmarks.bench_stencil", "Fig. 2/Table 4 2D FD stencil"),
    ("moe_dispatch", "benchmarks.bench_moe_dispatch", "beyond-paper MoE dispatch"),
    ("dist", "benchmarks.bench_dist", "beyond-paper mesh-aware engines (8 fake devices)"),
    ("serve", "benchmarks.bench_serve", "beyond-paper serving engine (split-KV decode, ragged prefill)"),
    ("train", "benchmarks.bench_train", "beyond-paper training path (flash bwd, blockwise blocks)"),
    ("roofline", "benchmarks.bench_roofline", "dry-run roofline table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny deterministic shapes, reduced timing loops, no JSON "
        "unless a --json* path is given explicitly",
    )
    ap.add_argument(
        "--json", default=None, help="machine-readable output path"
    )
    ap.add_argument(
        "--json-stencil",
        default=None,
        help="output path for the stencil suite's plan-engine rows",
    )
    ap.add_argument(
        "--json-moe",
        default=None,
        help="output path for the MoE dispatch suite's plan-engine rows",
    )
    ap.add_argument(
        "--json-dist",
        default=None,
        help="output path for the mesh-aware suite's strategy-comparison rows",
    )
    ap.add_argument(
        "--json-serve",
        default=None,
        help="output path for the serving suite's decode/prefill/trace rows",
    )
    ap.add_argument(
        "--json-train",
        default=None,
        help="output path for the training suite's phase-roofline and "
        "train-step rows",
    )
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        common.SMOKE = True
        os.environ["REPRO_BENCH_SMOKE"] = "1"  # reaches the dist subprocess
    defaults = {
        "json": "BENCH_rearrange.json",
        "json_stencil": "BENCH_stencil.json",
        "json_moe": "BENCH_moe.json",
        "json_dist": "BENCH_dist.json",
        "json_serve": "BENCH_serve.json",
        "json_train": "BENCH_train.json",
    }
    for attr, path in defaults.items():
        if getattr(args, attr) is None:
            # smoke runs never overwrite the committed bare-metal numbers
            setattr(args, attr, "" if args.smoke else path)

    enable_compile_cache()
    common.RECORDS.clear()
    failed: list[str] = []
    print("name,us_per_call,derived")
    for key, module, title in SUITES:
        if only and key not in only:
            continue
        t0 = time.time()
        print(f"# === {title} ({module}) ===", flush=True)
        n_before = len(common.RECORDS)
        try:
            mod = __import__(module, fromlist=["run"])
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            print(f"# {key} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
            print(f"{key},error,{type(e).__name__}")
            failed.append(key)
        for rec in common.RECORDS[n_before:]:
            rec.setdefault("suite", key)
        print(f"# ({time.time()-t0:.1f}s)", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                {"memcpy_gbps": round(common.memcpy_gbps(), 2), "rows": common.RECORDS},
                f,
                indent=2,
            )
            f.write("\n")
        print(f"# wrote {args.json} ({len(common.RECORDS)} rows)", flush=True)

    # per-engine comparisons get their own tracked artifacts
    for suite, path in (
        ("stencil", args.json_stencil),
        ("moe_dispatch", args.json_moe),
        ("dist", args.json_dist),
        ("serve", args.json_serve),
        ("train", args.json_train),
    ):
        suite_rows = [r for r in common.RECORDS if r.get("suite") == suite]
        if suite_rows and path:
            with open(path, "w") as f:
                json.dump(
                    {"memcpy_gbps": round(common.memcpy_gbps(), 2), "rows": suite_rows},
                    f,
                    indent=2,
                )
                f.write("\n")
            print(f"# wrote {path} ({len(suite_rows)} rows)", flush=True)

    if failed:
        # the error rows above are kept; the run itself must not pass
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
