"""copy: the paper's read/write kernel, ``repro.kernels.ops.copy``."""

from bench.libops import Op, normal


def build(entry, key, devices) -> Op:
    import jax.numpy as jnp

    from repro.kernels import ops

    shape, dt = tuple(entry["shape"]), jnp.dtype(entry["dtype"])
    return Op(
        label=f"copy{'x'.join(map(str, shape))}_{dt.name}",
        args=(normal(key, shape, dt),),
        program=ops.copy,
        reference=lambda a: a,
        work={"shape": shape, "itemsize": dt.itemsize},
    )
