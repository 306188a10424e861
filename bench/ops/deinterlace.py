"""deinterlace: one array of ``n * length`` into ``n``, array ``k`` taking
elements ``k, k+n, ...`` (``repro.kernels.ops.deinterlace``)."""

from bench.libops import Op, normal


def split(x, n):
    """Plain de-interlace as lane-strided slices of a (rows, n * 128) view:
    a view with ``n`` as its minor dimension would be padded to the chip's
    128 lanes (32x the array)."""
    rows = x.reshape(-1, n * 128)
    return tuple(rows[:, k::n].reshape(-1) for k in range(n))


def build(entry, key, devices) -> Op:
    import jax.numpy as jnp

    from repro.kernels import ops

    n, length, dt = int(entry["n"]), int(entry["length"]), jnp.dtype(entry["dtype"])
    return Op(
        label=f"deinterlace{n}x{length}_{dt.name}",
        args=(normal(key, (n * length,), dt),),
        program=lambda a: tuple(ops.deinterlace(a, n)),
        reference=lambda a: split(a, n),
        work={"n": n, "length": length, "itemsize": dt.itemsize},
    )
