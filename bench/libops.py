"""Shared pieces of the library ops in ``ops/``: inputs made on the
device from the seed, the bit-for-bit comparison, and the lower
precision that the control computes in."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable


@dataclass
class Op:
    """One op of a library traffic mix, built at its timed size."""

    label: str
    args: tuple  #: device arrays the program is called with
    program: Callable  #: calls the library under test
    reference: Callable  #: plain jnp of the same semantics, independent of it
    work: dict  #: keyword arguments of ``work/<op>.py``
    out_shardings: object = None  #: where the reference puts its result


def normal(key, shape, dtype, sharding=None):
    """Standard normal values of ``dtype``, made on the device in one call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32).astype(dtype),
                 out_shardings=sharding)
    return fn(key)


def mismatches(got, want) -> int:
    """Elements whose bits differ between two trees of arrays (NaN-safe,
    -0 != +0); a leaf of another shape or dtype counts every element."""
    import jax
    import jax.numpy as jnp

    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(gl) != len(wl):
        return sum(int(w.size) for w in wl) or 1
    total = 0
    for g, w in zip(gl, wl):
        if g.shape != w.shape or g.dtype != w.dtype:
            total += int(w.size)
            continue
        udt = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[w.dtype.itemsize]
        n = jax.jit(lambda a, b: jnp.sum(
            jax.lax.bitcast_convert_type(a, udt) != jax.lax.bitcast_convert_type(b, udt),
            dtype=jnp.int32))(g, w)
        total += int(n)
    return total


def lower_format(dtype):
    """(exponent bits, mantissa bits) of the nearest precision below
    ``dtype`` that a later change might be tempted by: bfloat16 for
    float32, float8 e4m3 for bfloat16 and float16; ``None`` for integers."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return 8, 7
    if dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return 4, 3
    return None


def control(reference: Callable) -> Callable:
    """The reference computed in the lower precision: floating inputs and
    results rounded to :func:`lower_format`.  ``reduce_precision`` rounds
    where a cast down and back up would be folded away by XLA, which may
    keep excess precision."""
    import jax

    def low(a):
        fmt = lower_format(a.dtype)
        return a if fmt is None else jax.lax.reduce_precision(a, *fmt)

    def fn(*args):
        return jax.tree.map(low, reference(*[low(a) for a in args]))

    return fn


def expects_kernel(hlo_text: str) -> bool:
    """True when a compiled program holds a Pallas kernel."""
    return "tpu_custom_call" in hlo_text
