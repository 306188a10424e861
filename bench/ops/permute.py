"""permute: an N-D transpose through the plan engine, ``repro.kernels.ops.permute``."""

from bench.libops import Op, normal


def build(entry, key, devices) -> Op:
    import jax.numpy as jnp

    from repro.kernels import ops

    shape, dt = tuple(entry["shape"]), jnp.dtype(entry["dtype"])
    perm = tuple(entry["perm"])
    return Op(
        label=f"permute{''.join(map(str, perm))}_{'x'.join(map(str, shape))}_{dt.name}",
        args=(normal(key, shape, dt),),
        program=lambda a: ops.permute(a, perm),
        reference=lambda a: jnp.transpose(a, perm),
        work={"shape": shape, "itemsize": dt.itemsize},
    )
