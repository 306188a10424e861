"""gather_rows: a masked row gather through the IndexPlan engine,
``repro.kernels.ops.gather_rows(..., masked=True)``: ``out[i] = x[idx[i]]``,
a zero row where ``idx[i] < 0``.  As many rows out as in, their indices
uniform in ``[-1, rows)``: one row in ``rows + 1`` is the sentinel."""

from bench.libops import Op, normal


def gathered(x, idx):
    """Plain masked gather."""
    import jax.numpy as jnp

    rows = jnp.take(x, jnp.clip(idx, 0, x.shape[0] - 1), axis=0)
    return jnp.where((idx >= 0)[:, None], rows, jnp.zeros((), x.dtype))


def build(entry, key, devices) -> Op:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rows, width = entry["shape"]
    dt = jnp.dtype(entry["dtype"])
    x = normal(key, (rows, width), dt)
    idx = jax.jit(lambda k: jax.random.randint(k, (rows,), -1, rows, jnp.int32))(
        jax.random.fold_in(key, 1))
    valid = int(jnp.sum(idx >= 0))
    return Op(
        label=f"gather{rows}of{rows}x{width}_{dt.name}",
        args=(x, idx),
        program=lambda a, i: ops.gather_rows(a, i, masked=True),
        reference=gathered,
        work={"rows_out": rows, "row_bytes": width * dt.itemsize, "valid": valid},
    )
