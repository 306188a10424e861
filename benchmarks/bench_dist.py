"""Mesh-aware plan engines (DESIGN.md §10) — beyond-paper suite.

Three strategy comparisons on an 8-fake-device host mesh, with
bytes-on-wire accounting from the DistPlan cost model:

* sharded permute: comm-free local plan vs all_to_all redistribution vs
  the replicate (all_gather) fallback — same logical op, three wire costs;
* ``repeat(k)`` stencil: per-sweep execution (k ppermute pairs, k local
  kernels) vs the halo-blocked plan (one pair + one fused kernel per
  k-block) — same bytes on wire, k/blocks fewer collective latencies;
* MoE dispatch: dense (GSPMD one-hot einsums, XLA chooses collectives) vs
  expert-parallel sort (§4 blocked kernels around one all_to_all pair).

The harness process owns a single CPU device, so ``run()`` re-executes
this module in a subprocess with ``--xla_force_host_platform_device_count=8``
(the same recipe as ``make test-dist``) and adopts the child's records.
On this CPU container the timings are methodology stand-ins; the wire
bytes come from the plan cost model and are platform-independent.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REC_PREFIX = "##REC "


def _child() -> None:
    """Runs inside the 8-device subprocess: measure and stream records."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import common
    from repro import configs
    from repro.core import dist_plan as dp
    from repro.core import stencil as st
    from repro.models import moe

    rng = np.random.default_rng(0)
    auto = jax.sharding.AxisType.Auto
    mesh = jax.make_mesh((8,), ("x",), axis_types=(auto,))
    mk = dp.mesh_key(mesh)
    # a second, 2-axis mesh: requesting the output on the OTHER axis has no
    # aligned collective, which is what exercises the replicate fallback
    mesh2 = jax.make_mesh((2, 4), ("a", "b"), axis_types=(auto,) * 2)

    # --- sharded permute: one op, three strategies -----------------------
    shape, dt = ((16, 16, 32) if common.smoke() else (64, 128, 256)), jnp.float32
    x = jnp.asarray(rng.standard_normal(shape), dt)
    gbytes = 2 * x.size * x.dtype.itemsize  # read + write, the §3 metric
    cases = [
        ("permute_local", mesh, P("x"), None),
        ("permute_a2a", mesh, P("x"), P(None, None, "x")),
        ("permute_replicate", mesh2, P("b"), P(None, None, "a")),
    ]
    for name, m, in_spec, out_spec in cases:
        plan = dp.plan_dist_rearrange(
            dp.mesh_key(m), in_spec,
            None if out_spec is None else out_spec, shape, dt, (1, 0, 2),
        )
        xs = jax.device_put(x, NamedSharding(m, in_spec))
        fn = jax.jit(
            lambda v, _m=m, _i=in_spec, _o=out_spec: dp.shard_permute(
                v, (1, 0, 2), mesh=_m, in_spec=_i, out_spec=_o
            )
        )
        secs = common.time_fn(fn, xs)
        print(common.row(
            name, secs, gbytes,
            note=f"[{plan.strategy}]",
            strategy=plan.strategy,
            bytes_on_wire=plan.bytes_on_wire,
            collectives=len(plan.collectives),
            plan=plan.describe(),
        ))

    # --- stencil: per-sweep vs halo-blocked ------------------------------
    jac = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)
    g = jnp.asarray(
        rng.standard_normal((128, 64) if common.smoke() else (1024, 512)),
        jnp.float32,
    )
    gs = jax.device_put(g, NamedSharding(mesh, P("x", None)))
    k = 4 if common.smoke() else 8
    prog = jac.repeat(k)
    gb_grid = 2 * g.size * g.dtype.itemsize

    blocked = jax.jit(lambda v: prog.shard(v, mesh=mesh, axis="x"))
    plan_b = dp.plan_dist_stencil(mk, "x", g.shape, g.dtype, prog.stages, "zero")
    secs = common.time_fn(blocked, gs)
    print(common.row(
        f"stencil_halo_blocked_k{k}", secs, k * gb_grid,
        note=f"[{len(plan_b.detail)} blocks]",
        strategy=plan_b.strategy,
        bytes_on_wire=plan_b.bytes_on_wire,
        collectives=len(plan_b.collectives),
        plan=plan_b.describe(),
    ))

    sweep = jac.repeat(1)
    plan_s = dp.plan_dist_stencil(mk, "x", g.shape, g.dtype, sweep.stages, "zero")

    def per_sweep(v):
        for _ in range(k):
            v = sweep.shard(v, mesh=mesh, axis="x")
        return v

    secs = common.time_fn(jax.jit(per_sweep), gs)
    print(common.row(
        f"stencil_per_sweep_k{k}", secs, k * gb_grid,
        note=f"[{k} exchanges]",
        strategy="halo-per-sweep",
        bytes_on_wire=k * plan_s.bytes_on_wire,
        collectives=k * len(plan_s.collectives),
        plan=plan_s.describe(),
    ))

    # --- MoE: dense (GSPMD einsums) vs expert-parallel sort --------------
    cfg = configs.get_config("deepseek-moe-16b-smoke")
    seq_m = 8 if common.smoke() else 32
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    xm = jax.random.normal(
        jax.random.PRNGKey(1), (8, seq_m, cfg.d_model), jnp.float32
    ).astype(cfg.np_dtype)
    t = 8 * seq_m
    cap_ep = t // 8  # dropless per shard
    act_bytes = 2 * xm.size * xm.dtype.itemsize

    dense = jax.jit(lambda v: moe.moe_dense(p, cfg, v)[0])
    secs = common.time_fn(dense, xm)
    print(common.row(
        "moe_dense", secs, act_bytes,
        note="[one-hot einsum dispatch]",
        strategy="dense",
        collectives=-1,  # under GSPMD, XLA's choice — not plan-accounted
    ))

    plan_m = dp.plan_dist_moe(
        mk, "x", t, cfg.d_model, cfg.moe.n_experts, cap_ep, cfg.moe.top_k, xm.dtype
    )
    ep = jax.jit(
        lambda v: moe.moe_sort_ep(p, cfg, v, mesh=mesh, axis="x", capacity=cap_ep)[0]
    )
    secs = common.time_fn(ep, xm)
    print(common.row(
        "moe_sort_ep", secs, act_bytes,
        note=f"[{plan_m.strategy}]",
        strategy=plan_m.strategy,
        bytes_on_wire=plan_m.bytes_on_wire,
        collectives=len(plan_m.collectives),
        plan=plan_m.describe(),
    ))

    for rec in common.RECORDS:
        print(_REC_PREFIX + json.dumps(rec))


def run() -> list[str]:
    """Spawn the 8-device child, adopt its records, relay its CSV rows."""
    from benchmarks import common
    from repro.launch.mesh import fake_device_env

    # the forced host devices are CPU devices: pin the child to the CPU so
    # it never contends for an accelerator this process may hold
    env = {
        **os.environ,
        **fake_device_env(8),
        "JAX_PLATFORMS": "cpu",
        "REPRO_DIST_BENCH_CHILD": "1",
        "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_dist"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bench_dist child failed:\n{r.stderr[-2000:]}")
    out = []
    for line in r.stdout.splitlines():
        if line.startswith(_REC_PREFIX):
            common.RECORDS.append(json.loads(line[len(_REC_PREFIX):]))
        elif line.strip():
            out.append(line)
    return out


if __name__ == "__main__":
    if os.environ.get("REPRO_DIST_BENCH_CHILD") == "1":
        _child()
    else:
        for row in run():
            print(row)
