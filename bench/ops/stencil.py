"""stencil: a linear stencil program ``repeat`` sweeps deep through the
stencil plan engine (``repro.core.stencil``), ``out[p] = sum_k w_k *
in[p + o_k]`` summed in the order of the taps, the boundary extended
before every sweep."""

from bench.libops import Op, normal

#: jnp.pad modes of the boundary conditions
PAD_MODES = {"zero": "constant", "nearest": "edge", "reflect": "reflect",
             "periodic": "wrap"}


def swept(x, offsets, weights, repeat, boundary):
    """Plain stencil program: ``repeat`` full-grid sweeps."""
    import jax.numpy as jnp

    r = max(max(abs(dy), abs(dx)) for dy, dx in offsets)
    h, w = x.shape
    for _ in range(repeat):
        xp = jnp.pad(x, r, mode=PAD_MODES[boundary])
        acc = None
        for (dy, dx), wt in zip(offsets, weights):
            term = wt * xp[r + dy:r + dy + h, r + dx:r + dx + w]
            acc = term if acc is None else acc + term
        x = acc
    return x


def build(entry, key, devices) -> Op:
    import jax.numpy as jnp

    from repro.core import stencil as st

    shape, dt = tuple(entry["shape"]), jnp.dtype(entry["dtype"])
    offsets = tuple(tuple(o) for o in entry["offsets"])
    weights = tuple(float(w) for w in entry["weights"])
    repeat, boundary = int(entry["repeat"]), entry["boundary"]
    prog = st.Stencil(offsets, weights).repeat(repeat)
    return Op(
        label=f"stencil{len(offsets)}pt_r{repeat}_{boundary}_{'x'.join(map(str, shape))}",
        args=(normal(key, shape, dt),),
        program=lambda a: prog(a, boundary=boundary),
        reference=lambda a: swept(a, offsets, weights, repeat, boundary),
        work={"shape": shape, "itemsize": dt.itemsize, "taps": len(offsets),
              "repeat": repeat},
    )
