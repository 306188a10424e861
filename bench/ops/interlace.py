"""interlace: ``n`` arrays of ``length`` into one, element ``j*n + k`` from
array ``k`` (``repro.kernels.ops.interlace``)."""

from bench.libops import Op, normal

#: elements of each input the plain interlace takes at a time
REF_CHUNK = 1 << 20


def interlaced(arrays):
    """Plain interlace, :data:`REF_CHUNK` elements of each input at a time: a whole
    (length, n) intermediate would pad its n-wide minor dimension to the
    chip's 128 lanes."""
    import jax
    import jax.numpy as jnp

    length = arrays[0].shape[0]
    chunk = min(REF_CHUNK, length)

    def one(i):
        parts = [jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk) for a in arrays]
        return jnp.stack(parts, axis=-1).reshape(-1)

    return jax.lax.map(one, jnp.arange(length // chunk)).reshape(-1)


def build(entry, key, devices) -> Op:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    n, length, dt = int(entry["n"]), int(entry["length"]), jnp.dtype(entry["dtype"])
    args = tuple(normal(jax.random.fold_in(key, k), (length,), dt) for k in range(n))
    return Op(
        label=f"interlace{n}x{length}_{dt.name}",
        args=args,
        program=lambda *a: ops.interlace(list(a)),
        reference=lambda *a: interlaced(a),
        work={"n": n, "length": length, "itemsize": dt.itemsize},
    )
