"""Mesh-aware plan engines on 8 fake host devices (DESIGN.md §10).

Runs the three distributed workloads end to end and checks each against
its single-device oracle:

  1. sharded permute — comm-free when the output sharding rides the
     permutation, ONE tiled all_to_all when it doesn't;
  2. a repeat(k) Jacobi program with ppermute halo exchange — one
     neighbor-pair exchange per k-block, fused §9 kernel per shard;
  3. expert-parallel MoE sort dispatch — the §4 blocked kernels around a
     capacity-bucketed all_to_all pair.

No TPU needed: the mesh is 8 forced host (CPU) devices.

  PYTHONPATH=src python examples/dist_permute.py
"""

import os

# must land before jax initializes its backends (same recipe as
# repro.launch.mesh.fake_device_env / make test-dist)
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import dist_plan as dp  # noqa: E402
from repro.core import stencil as st  # noqa: E402
from repro.models import moe  # noqa: E402


def main() -> None:
    rng = np.random.default_rng(0)
    mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
    print(f"devices: {jax.device_count()}  mesh: {dict(dp.mesh_key(mesh))}")

    # 1 — sharded permute: (B, S, D) sharded over B, swap B and S
    x = jnp.asarray(rng.standard_normal((64, 96, 128)), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("x")))
    y_local = dp.shard_permute(xs, (1, 0, 2), mesh=mesh, in_spec=P("x"))
    plan = dp.plan_dist_rearrange(
        dp.mesh_key(mesh), P("x"), None, x.shape, x.dtype, (1, 0, 2)
    )
    print("\npermute, sharding rides the perm:\n ", plan.describe())
    y_a2a = dp.shard_permute(
        xs, (1, 0, 2), mesh=mesh, in_spec=P("x"), out_spec=P(None, None, "x")
    )
    plan = dp.plan_dist_rearrange(
        dp.mesh_key(mesh), P("x"), P(None, None, "x"), x.shape, x.dtype, (1, 0, 2)
    )
    print("permute, resharded output:\n ", plan.describe())
    want = jnp.transpose(x, (1, 0, 2))
    assert jnp.array_equal(y_local, want) and jnp.array_equal(y_a2a, want)
    print("  both bit-identical to the single-device permute")

    # 2 — halo-exchanged stencil: 12 fused Jacobi sweeps, rows sharded
    g = jnp.asarray(rng.standard_normal((256, 130)), jnp.float32)
    gs = jax.device_put(g, NamedSharding(mesh, P("x", None)))
    jacobi = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)
    prog = jacobi.repeat(12)
    plan = dp.plan_dist_stencil(
        dp.mesh_key(mesh), "x", g.shape, g.dtype, prog.stages, "reflect"
    )
    print("\nhalo-exchanged repeat(12) Jacobi:\n ", plan.describe())
    got = prog.shard(gs, mesh=mesh, axis="x", boundary="reflect")
    assert jnp.array_equal(got, prog(g, boundary="reflect"))
    print(f"  bit-identical to 12 single-device sweeps "
          f"({len(plan.detail)} k-block(s), one ppermute pair each)")

    # 3 — expert-parallel MoE: tokens and experts sharded over the mesh
    cfg = configs.get_config("deepseek-moe-16b-smoke")
    params = moe.moe_init(jax.random.PRNGKey(0), cfg)
    xm = jax.random.normal(
        jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32
    ).astype(cfg.np_dtype)
    t = 8 * 16
    plan = dp.plan_dist_moe(
        dp.mesh_key(mesh), "x", t, cfg.d_model, cfg.moe.n_experts,
        t // 8, cfg.moe.top_k, xm.dtype,
    )
    print("\nexpert-parallel MoE sort dispatch:\n ", plan.describe())
    y_ep, _ = moe.moe_sort_ep(params, cfg, xm, mesh=mesh, axis="x", capacity=t // 8)
    y_ref, _ = moe.moe_sort(params, cfg, xm, capacity=t)  # dropless oracle
    assert jnp.array_equal(y_ep, y_ref)
    print("  bit-identical to dropless single-device moe_sort")


if __name__ == "__main__":
    main()
