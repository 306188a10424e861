"""Static bundle counts of the fused stencil kernel, compiled for a
described TPU v5e (nothing runs, no chip is needed):

    PYTHONPATH=src JAX_PLATFORMS=cpu python tools/stencil_bundles.py \
        [--shape 65536 7168] [--boundary reflect] [--dump DIR]

A child process compiles Jacobi ``repeat(8)`` (the ``lib-hbm.stencil``
cell's program) with the TPU compiler's bundle dump on; the compiler may
abort after writing the dump, so the parent reads the files afterwards.
Printed: the kernel's total static bundles, its vector rotations,
selects, multiplies, adds and spill loads/stores, and the bundles an
interior grid step executes: the then-branch of every two-way
conditional skipped (the edge-row branch, which only panels at the
grid's edges take), each loop over the 8 stages counted 8 times.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

KERNEL = "stencil2d_pipeline"


def compile_for_v5e(shape, boundary):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core import stencil as st
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops.use_pallas = lambda: True  # this process sees only the CPU
    ops._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    prog = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4).repeat(8)
    print(prog.compile(shape, jnp.float32, boundary=boundary).describe(), flush=True)
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    jax.jit(lambda a: prog(a, boundary=boundary)).lower(x).compile()


def summarize(dump: str, trips: int) -> str:
    files = sorted(glob.glob(f"{dump}/*{KERNEL}*-final_bundles.txt"))
    bundles = [f for f in files if "schedule-analysis" not in f]
    if not bundles:
        raise SystemExit(f"no {KERNEL} bundle dump under {dump}")
    text = open(bundles[-1]).read()
    ops = collections.Counter(re.findall(r"= ([a-z][a-z0-9._]*)", text))
    total, branches = 0, []
    for line in text.splitlines():
        m = re.match(r"\s*0x([0-9a-f]+)\s", line)
        if m:
            b = int(m.group(1), 16)
            total = b + 1
            branches += [
                (b, neg, p, int(t))
                for neg, p, t in re.findall(
                    r"sbr\.rel \((!?)(%p\d+_p\d+)\) target bundleno = (\d+)", line
                )
            ]
    # a two-way conditional: `sbr.rel (!p)` over the then-branch, and a
    # `sbr.rel (p)` over the else-branch at its end
    plain = {p for _, neg, p, _ in branches if not neg}
    then = [(b, t) for b, neg, p, t in branches if neg and p in plain and t > b]
    # a loop over the stages: a backward branch other than the grid's own
    # (the first one, to the kernel's entry)
    grid_entry = min((t for b, _, _, t in branches if t < b), default=0)
    loops = [(t, b) for b, _, _, t in branches if grid_entry < t < b]
    live = [(t, b) for t, b in loops if not any(s < t and b < e for s, e in then)]
    executed = (total - sum(t - b - 1 for b, t in then)
                + (trips - 1) * sum(b - t + 1 for t, b in live))
    spill = r" \[vmem:\[#allocation\d+_spill"
    return (
        f"static bundles {total}; an interior step (every then-branch "
        f"skipped, {len(live)} stage loop(s) of {trips} trips) executes "
        f"{executed}\n"
        f"vrot.slane {ops['vrot.slane']}  "
        f"vrot.lane {sum(v for k, v in ops.items() if k.startswith('vrot.lane'))}  "
        f"vsel {ops['vsel']}  vmul.f32 {ops['vmul.f32']}  vadd.f32 {ops['vadd.f32']}  "
        f"spill loads {len(re.findall('vld[a-z.]*' + spill, text))}  "
        f"spill stores {len(re.findall('vst[a-z.]*' + spill, text))}"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=2, default=(65536, 7168))
    ap.add_argument("--boundary", default="reflect")
    ap.add_argument("--dump", help="dump directory (default: a fresh temporary one)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        compile_for_v5e(tuple(args.shape), args.boundary)
        return
    dump = args.dump or tempfile.mkdtemp(prefix="stencil_bundles_")
    env = dict(os.environ, TPU_LOG_DIR=os.environ.get("TPU_LOG_DIR", "disabled"))
    env["LIBTPU_INIT_ARGS"] = f"{env.get('LIBTPU_INIT_ARGS', '')} --xla_jf_dump_to={dump}"
    child = [sys.executable, __file__, "--child", "--boundary", args.boundary,
             "--shape", *map(str, args.shape)]
    out = subprocess.run(child, env=env, capture_output=True, text=True)
    print(out.stdout.strip())
    if not glob.glob(f"{dump}/*{KERNEL}*-final_bundles.txt"):
        raise SystemExit(out.stderr.strip().splitlines()[-1])
    print(summarize(dump, trips=8))


if __name__ == "__main__":
    main()
