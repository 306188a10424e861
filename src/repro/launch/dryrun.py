"""Multi-pod dry-run: lower + compile every (arch x shape cell) on the
production meshes; derive the three-term roofline per cell.

Two lowerings per cell:

  1. *scan-mode* — the production config exactly as the trainer runs it
     (scan over layers, grad accumulation).  Proves the sharding compiles
     and gives ``memory_analysis()`` (XLA sizes loop buffers correctly).
  2. *analysis-mode* — XLA's ``cost_analysis()`` counts a while body ONCE,
     so roofline terms come from scan-unrolled reduced-unit lowerings:
     per-stage unit cost = cost(2 units) - cost(1 unit), and
     total = base + sum_i (count_i - 1) * unit_i  (exact: scan bodies are
     homogeneous).  For the ssm family (per-timestep scans) costs are
     additionally linear-extrapolated from two sequence lengths.

  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both

Results land in runs/dryrun/<mesh>/<arch>--<cell>.json (resumable).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# TPU-faithful HLO: keep bf16-in/f32-out dots in the lowering (we only
# lower+compile here; nothing executes on the CPU backend).  The 512-device
# init must precede any jax import, which is why these lines sit above the
# import block.
os.environ.setdefault("REPRO_BF16_DOT", "1")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import mesh as meshlib  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.utils import hlo as hlolib  # noqa: E402
from repro.utils import roofline as rl  # noqa: E402

OUT_DIR = Path(os.environ.get("REPRO_DRYRUN_DIR", "runs/dryrun"))
TRAIN_ACCUM = int(os.environ.get("REPRO_DRYRUN_ACCUM", "8"))


def _cost_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (jax 0.4.x returns [dict])."""
    cost = compiled.cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


def lower_cell(cfg, cell, mesh, *, accum_steps: int = 1):
    """Lower + compile one (config, shape cell) on ``mesh``; returns the
    compiled executable (nothing executes — CPU backend, abstract inputs)."""
    step = specs.make_step(cfg, cell, mesh, adamw.OptConfig(), accum_steps=accum_steps)
    inputs = specs.input_specs(cfg, cell)
    in_sh = specs.input_shardings(cfg, cell, mesh)
    pshard = specs.param_shardings(cfg, mesh)
    params_abs = tf.abstract_params(cfg)

    with jax.sharding.set_mesh(mesh):
        if cell.kind == "train":
            oshard = specs.opt_shardings(cfg, mesh)
            opt_abs = jax.eval_shape(adamw.init, params_abs)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, in_sh),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            )
            return jitted.lower(params_abs, opt_abs, inputs)
        if cell.kind == "prefill":
            jitted = jax.jit(step, in_shardings=(pshard, in_sh))
            return jitted.lower(params_abs, inputs)
        jitted = jax.jit(step, in_shardings=(pshard, in_sh), donate_argnums=(1,))
        return jitted.lower(params_abs, inputs)


# ---------------------------------------------------------------------------
# analysis mode (roofline terms)
# ---------------------------------------------------------------------------


def _reduced(cfg, stage_counts, enc_layers):
    plan = tuple(
        (unit, c) for (unit, _), c in zip(cfg.layer_plan(), stage_counts)
    )
    n_layers = sum(len(u) * c for u, c in plan)
    return cfg.with_(
        explicit_plan=plan, n_layers=n_layers, encoder_layers=enc_layers
    )


def _cost_triple(cfg, cell, mesh) -> np.ndarray:
    lowered = lower_cell(cfg, cell, mesh, accum_steps=1)
    compiled = lowered.compile()
    cost = _cost_dict(compiled)
    coll = hlolib.collective_stats(compiled.as_text())
    return np.array(
        [
            float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total_bytes"]),
        ]
    )


def analysis_cost(cfg, cell, mesh) -> dict:
    """Per-device (flops, bytes, collective bytes) via unrolled marginals."""
    os.environ["REPRO_UNROLL_SCANS"] = "1"
    try:
        cfg_a = cfg.with_(attn_chunk=max(cfg.attn_chunk, 2048))
        plan = cfg.layer_plan()
        counts = [c for _, c in plan]
        enc = cfg.encoder_layers
        seq_marginal = cfg.family == "ssm" and cell.kind in ("train", "prefill")

        def costs_at(cell_v) -> tuple[np.ndarray, list[np.ndarray], np.ndarray | None]:
            base_cfg = _reduced(cfg_a, [1] * len(counts), min(enc, 1))
            base = _cost_triple(base_cfg, cell_v, mesh)
            units = []
            for i, cnt in enumerate(counts):
                if cnt > 1:
                    sc = [2 if j == i else 1 for j in range(len(counts))]
                    v = _cost_triple(_reduced(cfg_a, sc, min(enc, 1)), cell_v, mesh)
                    units.append(v - base)
                else:
                    units.append(np.zeros(3))
            enc_unit = None
            if enc > 1:
                v = _cost_triple(
                    _reduced(cfg_a, [1] * len(counts), 2), cell_v, mesh
                )
                enc_unit = v - base
            return base, units, enc_unit

        if seq_marginal:
            # recurrent costs are exactly linear in S, so the marginal can
            # be taken at tiny S (unrolling 64+ timesteps explodes XLA
            # compile time; 8/16 compile in seconds and extrapolate exactly)
            s1, s2 = 8, 16
            c1 = dataclasses.replace(cell, seq_len=s1)
            c2 = dataclasses.replace(cell, seq_len=s2)
            b1, u1, e1 = costs_at(c1)
            b2, u2, e2 = costs_at(c2)
            s = cell.seq_len

            def extrap(a1, a2):
                slope = (a2 - a1) / (s2 - s1)
                return a1 + slope * (s - s1)

            base = extrap(b1, b2)
            units = [extrap(x, y) for x, y in zip(u1, u2)]
            enc_unit = extrap(e1, e2) if e1 is not None else None
        else:
            base, units, enc_unit = costs_at(cell)

        total = base.copy()
        for cnt, u in zip(counts, units):
            total += (cnt - 1) * u
        if enc_unit is not None:
            total += (enc - 1) * enc_unit
        return {
            "flops_per_dev": float(total[0]),
            "bytes_per_dev": float(total[1]),
            "coll_bytes_per_dev": float(total[2]),
            "base": base.tolist(),
            "per_stage_unit": [u.tolist() for u in units],
            "method": "unrolled-marginal"
            + ("+seq-extrapolated" if seq_marginal else ""),
        }
    finally:
        os.environ["REPRO_UNROLL_SCANS"] = "0"


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, cell_name: str, multi_pod: bool, *, force: bool = False,
             analysis: bool = True) -> dict:
    """Dry-run one cell end to end (lower, compile, roofline) and persist
    the record to runs/dryrun/ — existing records short-circuit (resume)."""
    mesh_name = "multi" if multi_pod else "single"
    out_path = OUT_DIR / mesh_name / f"{arch}--{cell_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    out_path.parent.mkdir(parents=True, exist_ok=True)

    cfg = configs.get_config(arch)
    cell = configs.SHAPE_CELLS[cell_name]
    applicable = [c.name for c in configs.cells_for(cfg)]
    rec: dict = {
        "arch": arch,
        "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "timestamp": time.time(),
    }
    if cell_name not in applicable:
        rec["status"] = "skipped"
        rec["reason"] = (
            "long_500k requires sub-quadratic attention; "
            f"{arch} is full-attention (see DESIGN.md §7)"
        )
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    try:
        # phase 1: production (scan-mode) compile — memory + schedule proof
        accum = TRAIN_ACCUM if cell.kind == "train" else 1
        t0 = time.time()
        lowered = lower_cell(cfg, cell, mesh, accum_steps=accum)
        compiled = lowered.compile()
        t1 = time.time()
        mem = compiled.memory_analysis()
        cost = _cost_dict(compiled)
        rec.update(
            status="ok",
            compile_s=round(t1 - t0, 1),
            accum_steps=accum,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            scan_mode_cost={
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "note": "while bodies counted once; see analysis for true terms",
            },
            collectives_scan_mode=hlolib.collective_stats(compiled.as_text()),
        )

        # phase 2: roofline terms (single-pod only, per spec)
        if analysis and not multi_pod:
            t2 = time.time()
            ana = analysis_cost(cfg, cell, mesh)
            rec["analysis"] = ana
            rec["analysis_s"] = round(time.time() - t2, 1)
            roof = rl.Roofline(
                flops_per_dev=ana["flops_per_dev"],
                bytes_per_dev=ana["bytes_per_dev"],
                coll_bytes_per_dev=ana["coll_bytes_per_dev"],
                model_flops_global=rl.model_flops(cfg, cell),
                n_chips=mesh.size,
            )
            rec["roofline"] = roof.to_dict()
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    """CLI driver: dry-run every requested (arch, cell, mesh) combination."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-analysis", action="store_true")
    args = ap.parse_args()

    archs = list(configs.ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    cells = list(configs.SHAPE_CELLS) if args.cell == "all" else args.cell.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for multi in meshes:
        for arch in archs:
            for cell in cells:
                t0 = time.time()
                rec = run_cell(
                    arch, cell, multi, force=args.force,
                    analysis=not args.no_analysis,
                )
                status = rec.get("status")
                extra = ""
                if status == "ok" and "roofline" in rec:
                    r = rec["roofline"]
                    extra = (
                        f" bottleneck={r['bottleneck']}"
                        f" step={r['step_time_s']*1e3:.1f}ms"
                        f" mfu_bound={r['mfu_bound']:.2f}"
                    )
                elif status == "error":
                    extra = " " + rec.get("error", "")[:160]
                print(
                    f"[{'multi' if multi else 'single'}] {arch} x {cell}: "
                    f"{status}{extra} ({time.time()-t0:.0f}s)",
                    flush=True,
                )


if __name__ == "__main__":
    main()
