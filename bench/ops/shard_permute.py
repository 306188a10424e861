"""shard_permute: an N-D permute of an array sharded over a mesh of the
cell's chips, through the distributed plan engine
(``repro.core.dist_plan.shard_permute``): the input sharded by
``in_spec``, the output by ``out_spec``."""

from bench.libops import Op, normal


def build(entry, key, devices) -> Op:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core import dist_plan as dp

    shape, dt = tuple(entry["shape"]), jnp.dtype(entry["dtype"])
    perm = tuple(entry["perm"])
    mesh = jax.make_mesh((len(devices),), (entry["axis"],), devices=devices,
                         axis_types=(AxisType.Auto,))
    in_spec, out_spec = P(*entry["in_spec"]), P(*entry["out_spec"])
    return Op(
        label=f"shard_permute{''.join(map(str, perm))}_{'x'.join(map(str, shape))}_{dt.name}",
        args=(normal(key, shape, dt, NamedSharding(mesh, in_spec)),),
        program=lambda a: dp.shard_permute(a, perm, mesh=mesh, in_spec=in_spec,
                                           out_spec=out_spec),
        reference=lambda a: jnp.transpose(a, perm),
        work={"shape": shape, "itemsize": dt.itemsize},
        out_shardings=NamedSharding(mesh, out_spec),
    )
