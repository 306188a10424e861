"""Smoke test of the main path on a TPU chip.

    python chip_smoke.py             # one chip: library phase + serve phase
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

Library phase: copy, permute, reorder, interlace / de-interlace, a fused
Jacobi program and a masked row gather at HBM-filling sizes, each compiled
to a Pallas kernel (``tpu_custom_call``) and checked bit-exact against the
jnp oracles of ``repro.kernels.ref`` on the same chip.

Serve phase: ``qwen2-7b`` at its published widths with the depth cut to 8
layers and seeded random weights, served through ``serve.engine.Engine``
(ragged admission, chunked prefill, split-KV decode); then one request's
prefill logits through the Pallas kernels are compared with the same
model's XLA-attention path.

Mesh phase (``--chips 4``): a sharded permute that needs one
``all_to_all`` and a halo-exchanged ``repeat(8)`` Jacobi program on a
4-device mesh, each bit-exact against the single-device program.

Phase names, plans and set-up / compile seconds go to earlier lines; they
are not speed measurements.  The last line of standard output is one JSON
object ``{"ok": true, "device": {...}}``; a failing phase prints no such
line and exits nonzero.  The program runs in this one process: it starts
no child that could contend for the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: dispatch switches that would take the kernels off the chip
_FORBIDDEN = {
    "REPRO_PALLAS_INTERPRET": None,
    "REPRO_DISABLE_PALLAS": None,
    "REPRO_FLASH_KERNEL": "0",
    "REPRO_DECODE_KERNEL": "0",
}

#: qwen2-7b layers kept on one 16 GB chip (28 layers are 15.2 GB of bf16)
SERVE_LAYERS = 8
#: bound on the relative L2 distance between kernel-path and XLA-path
#: prefill logits.  Both paths run the bf16 model with f32 softmax
#: statistics; they differ only in where bf16 rounding falls (tile order of
#: the online softmax, the p·V product), about 2^-8 relative per rounding,
#: compounded over 8 layers.  A wrong mask, a stale KV row or a misplaced
#: block moves the logits by O(1), far above this bound.
LOGIT_REL_L2 = 5e-2


def log(msg: str) -> None:
    """One progress line on stdout (never the final JSON line)."""
    print(msg, flush=True)


def refuse_bad_env() -> None:
    """Exit before touching JAX when a switch would bypass the kernels."""
    for var, bad in _FORBIDDEN.items():
        val = os.environ.get(var)
        if val is not None and (bad is None or val == bad):
            sys.exit(f"chip_smoke: refusing to start with {var}={val}")
    # plans come from the analytic planners: no tuning runs, no tuning cache
    os.environ["REPRO_TUNE"] = "off"


class Timer:
    """Seconds of set-up and compilation per phase, for the log lines."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        t, self.t0 = self.t0, time.perf_counter()
        return self.t0 - t


def compiled(fn, *args):
    """AOT-compile ``fn`` for ``args``; returns (executable, HLO text)."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    return exe, exe.as_text()


def same_bits(a, b) -> bool:
    """Bit-exact equality of two arrays on the device (NaN-safe, -0 != 0)."""
    import jax
    import jax.numpy as jnp

    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    udt = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    eq = jax.jit(
        lambda x, y: jnp.all(
            jax.lax.bitcast_convert_type(x, udt) == jax.lax.bitcast_convert_type(y, udt)
        )
    )
    return bool(eq(a, b))


def check_kernel(name: str, fn, oracle, *args, describe: str = ""):
    """Compile ``fn`` to a program that holds a Pallas kernel, run it,
    compare its outputs bit-exactly with ``oracle`` on the same chip, and
    return them."""
    import jax

    if describe:
        log(f"  plan {name}: {describe}")
    t = Timer()
    exe, text = compiled(fn, *args)
    n_kernels = text.count("tpu_custom_call")
    if n_kernels == 0:
        raise AssertionError(f"{name}: compiled program holds no tpu_custom_call")
    got = jax.block_until_ready(exe(*args))
    want = jax.block_until_ready(jax.jit(oracle)(*args))
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(got_l) != len(want_l) or not all(
        same_bits(g, w) for g, w in zip(got_l, want_l)
    ):
        raise AssertionError(f"{name}: result differs from the oracle")
    log(f"  {name}: {n_kernels} tpu_custom_call, bit-exact vs ref "
        f"(compile+run {t.lap():.1f} s)")
    return got


def library_phase(seed: int) -> None:
    """The paper's op families at sizes its users call real."""
    import jax
    import jax.numpy as jnp

    from repro.core import plan as plan_mod
    from repro.core import stencil as st
    from repro.core.index_plan import plan_index_op
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(seed)

    def normal(i, shape, dtype):
        return jax.jit(
            lambda k: jax.random.normal(k, shape, jnp.float32).astype(dtype)
        )(jax.random.fold_in(key, i))

    x = normal(0, (8192, 8192), jnp.float32)  # 256 MiB
    check_kernel("copy 8192^2 f32", ops.copy, ref.copy, x,
                 describe="streaming copy kernel, (rows, 8192) panels")
    del x

    perms = [
        ("permute (0,2,1,3) (8,4096,28,128) bf16", (8, 4096, 28, 128),
         jnp.bfloat16, (0, 2, 1, 3)),
        ("permute (2,1,0) 512^3 f32", (512, 512, 512), jnp.float32, (2, 1, 0)),
        ("reorder 5-D (4,2,0,3,1) (32,32,32,32,64) f32", (32, 32, 32, 32, 64),
         jnp.float32, (4, 2, 0, 3, 1)),
    ]
    for i, (name, shape, dtype, perm) in enumerate(perms, start=1):
        x = normal(i, shape, dtype)
        p = plan_mod.plan_rearrange(shape, dtype, perm)
        check_kernel(name, lambda a, q=perm: ops.permute(a, q),
                     lambda a, q=perm: ref.permute(a, q), x,
                     describe=p.describe())
        del x

    L, n, c = 1 << 26, 4, 1 << 20
    srcs = [normal(10 + k, (L,), jnp.float32) for k in range(n)]  # 4x256 MiB

    # the oracles build an (L, n) intermediate whose n-wide lane dim XLA
    # pads to 128 lanes on the chip (32 GiB here), so they run on slices
    def il_oracle(*a):
        def one(i):
            return ref.interlace([jax.lax.dynamic_slice_in_dim(x, i * c, c) for x in a])
        return jax.lax.map(one, jnp.arange(L // c)).reshape(-1)

    def dil_oracle(x):
        def one(i):
            return tuple(ref.deinterlace(jax.lax.dynamic_slice_in_dim(x, i * n * c, n * c), n))
        return tuple(p.reshape(-1) for p in jax.lax.map(one, jnp.arange(L // c)))

    mixed = check_kernel(
        "interlace 4 x 2^26 f32", lambda *a: ops.interlace(list(a)), il_oracle,
        *srcs, describe="lane-gather interlace, (L/128, 4*128) output view",
    )
    back = check_kernel("deinterlace 2^28 f32 n=4",
                        lambda a: tuple(ops.deinterlace(a, n)), dil_oracle, mixed)
    if not all(same_bits(a, b) for a, b in zip(back, srcs)):
        raise AssertionError("deinterlace(interlace(x)) != x")
    log("  interlace -> deinterlace round trip: bit-exact")
    del srcs, mixed, back

    jacobi = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4).repeat(8)
    # the largest square grid (in 512-row steps) the plan still runs fused
    n = max(
        k for k in range(4096, 16385, 512)
        if jacobi.compile((k, k), jnp.float32, boundary="reflect").mode == "fused"
    )
    plan = jacobi.compile((n, n), jnp.float32, boundary="reflect")
    g = normal(20, (n, n), jnp.float32)
    check_kernel(
        f"jacobi repeat(8) reflect {n}^2 f32",
        lambda a: jacobi(a, boundary="reflect"),
        lambda a: ref.stencil_pipeline(a, plan.stages_exec, boundary="reflect"),
        g, describe=plan.describe(),
    )
    del g

    rows = normal(30, (65536, 3584), jnp.bfloat16)  # 448 MiB
    idx = jax.random.randint(jax.random.fold_in(key, 31), (65536,), -1, 65536)
    ip = plan_index_op(rows.shape, rows.dtype, 65536, "gather", masked=True)
    check_kernel("masked row gather (65536,3584) bf16",
                 lambda a, i: ops.gather_rows(a, i, masked=True),
                 ref.gather_rows_masked, rows, idx, describe=ip.describe())


def serve_phase(seed: int) -> None:
    """qwen2-7b widths, 8 layers, through the continuous-batching engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import transformer as tf
    from repro.serve.engine import Engine, Request

    full = configs.get_config("qwen2-7b")
    cfg = full.with_(n_layers=SERVE_LAYERS)
    log(f"  model {full.name}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}; "
        f"depth cut {full.n_layers} -> {cfg.n_layers} layers (the full model's "
        f"15.2 GB of weights leave no room for a KV cache on one 16 GB chip)")
    t = Timer()
    params = jax.block_until_ready(
        jax.jit(lambda k: tf.init_params(k, cfg))(jax.random.PRNGKey(seed))
    )
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    log(f"  weights: {n_bytes / 1e9:.2f} GB from seed {seed} "
        f"(set-up {t.lap():.1f} s)")

    eng = Engine(cfg, params, batch_slots=4, s_max=4096, prompt_bucket=512,
                 prefill_mode="ragged", chunk=512)
    nan_calls = []

    def watched(name, fn):
        def run(*a):
            out = fn(*a)
            if bool(jnp.isnan(out[0]).any()):
                nan_calls.append(name)
            return out
        return run

    eng._decode = watched("decode", eng._decode)
    eng._prefill_ragged = watched("ragged prefill", eng._prefill_ragged)
    eng._prefill_chunk = watched("chunk prefill", eng._prefill_chunk)

    rng = np.random.default_rng(seed)
    lengths = rng.integers(128, 2049, size=8)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=int(n)).astype(np.int32),
                max_new=16)
        for i, n in enumerate(lengths)
    ]
    log(f"  requests: 8, prompt lengths {lengths.tolist()}, max_new 16, "
        f"4 slots, s_max 4096, chunk 512")
    done = eng.run(reqs)
    log(f"  served {len(done)} requests (compile+run {t.lap():.1f} s)")
    short = [r.rid for r in reqs if len(r.out) != 16]
    if len(done) != 8 or short:
        raise AssertionError(f"requests without 16 tokens: {short}")
    if nan_calls:
        raise AssertionError(f"NaN logits from: {sorted(set(nan_calls))}")
    log("  every request returned 16 tokens; no NaN logits")

    # one request's prefill logits: Pallas kernels vs the XLA-attention path
    toks = jnp.asarray(reqs[0].prompt[None])
    kern = jax.jit(lambda p, x: tf.prefill(p, cfg, x)[0])
    exe, text = compiled(kern, params, toks)
    if "tpu_custom_call" not in text:
        raise AssertionError("kernel-path prefill holds no tpu_custom_call")
    got = np.asarray(exe(params, toks), np.float32)
    saved = {v: os.environ.get(v) for v in ("REPRO_DISABLE_PALLAS", "REPRO_FLASH_KERNEL")}
    os.environ.update(REPRO_DISABLE_PALLAS="1", REPRO_FLASH_KERNEL="0")
    try:
        xla = jax.jit(lambda p, x: tf.prefill(p, cfg, x)[0])
        exe_x, text_x = compiled(xla, params, toks)
    finally:
        for v, val in saved.items():
            if val is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = val
    if "tpu_custom_call" in text_x:
        raise AssertionError("XLA-path prefill still holds a Pallas kernel")
    want = np.asarray(exe_x(params, toks), np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError("non-finite prefill logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    top = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"  prefill logits, request 0 ({toks.shape[1]} tokens): kernel vs XLA "
        f"rel-L2 {rel:.3e} (bound {LOGIT_REL_L2}), max|diff| "
        f"{np.abs(got - want).max():.3e}, top-1 agreement {top:.2f} "
        f"(compile+run {t.lap():.1f} s)")
    if not rel <= LOGIT_REL_L2:
        raise AssertionError(f"prefill logits differ: rel-L2 {rel} > {LOGIT_REL_L2}")


def mesh_phase(seed: int) -> None:
    """The mesh layer on four chips, each workload against one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core import dist_plan as dp
    from repro.core import stencil as st

    if len(jax.devices()) != 4:
        raise AssertionError(f"--chips 4 wants 4 devices, found {len(jax.devices())}")
    mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(seed)

    x = jax.random.normal(key, (64, 4096, 1024), jnp.float32)  # 1 GiB
    xs = jax.device_put(x, NamedSharding(mesh, P("x")))
    out_spec = P(None, None, "x")
    plan = dp.plan_dist_rearrange(dp.mesh_key(mesh), P("x"), out_spec,
                                  x.shape, x.dtype, (1, 0, 2))
    log(f"  plan sharded permute: {plan.describe()}")
    if plan.strategy != "all_to_all":
        raise AssertionError(f"sharded permute planned {plan.strategy}, not all_to_all")
    fn = lambda a: dp.shard_permute(a, (1, 0, 2), mesh=mesh, in_spec=P("x"),  # noqa: E731
                                    out_spec=out_spec)
    t = Timer()
    exe, text = compiled(fn, xs)
    for op in ("all-to-all", "tpu_custom_call"):
        if op not in text:
            raise AssertionError(f"sharded permute program holds no {op}")
    got = exe(xs)
    want = jax.jit(lambda a: jnp.transpose(a, (1, 0, 2)))(x)
    if not same_bits(jax.device_put(got, jax.devices()[0]), want):
        raise AssertionError("sharded permute differs from one device")
    log(f"  sharded permute (64,4096,1024) f32 on 4 chips: all-to-all + kernel, "
        f"bit-exact vs one device (compile+run {t.lap():.1f} s)")
    del x, xs, got, want

    prog = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4).repeat(8)
    g = jax.random.normal(jax.random.fold_in(key, 1), (8192, 4096), jnp.float32)
    gs = jax.device_put(g, NamedSharding(mesh, P("x", None)))
    plan = dp.plan_dist_stencil(dp.mesh_key(mesh), "x", g.shape, g.dtype,
                                prog.stages, "reflect")
    log(f"  plan halo stencil: {plan.describe()}")
    exe, text = compiled(lambda a: prog.shard(a, mesh=mesh, axis="x",
                                              boundary="reflect"), gs)
    for op in ("collective-permute", "tpu_custom_call"):
        if op not in text:
            raise AssertionError(f"halo stencil program holds no {op}")
    got = exe(gs)
    one = jax.device_put(g, jax.devices()[0])
    want = jax.jit(lambda a: prog(a, boundary="reflect"))(one)
    if not same_bits(jax.device_put(got, jax.devices()[0]), want):
        raise AssertionError("halo-exchanged stencil differs from one device")
    log(f"  jacobi repeat(8) reflect (8192,4096) f32, rows over 4 chips: "
        f"collective-permute halo + kernel, bit-exact vs one device "
        f"(compile+run {t.lap():.1f} s)")


def main() -> int:
    """Run the phases; print the JSON verdict as the last line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    refuse_bad_env()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repro package is not next to this script: {e}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing to run")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    cache_dir = enable_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count(event: str, **_) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count)
    log(f"compile cache: {cache_dir}")

    phases = [("mesh", mesh_phase)] if args.chips == 4 else [
        ("library", library_phase), ("serve", serve_phase)]
    failed = []
    for name, phase in phases:
        log(f"phase {name}")
        t = Timer()
        try:
            phase(args.seed)
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            failed.append(name)
            traceback.print_exc()
            log(f"phase {name} FAILED: {type(e).__name__}: {e}")
        log(f"phase {name} done in {t.lap():.1f} s")
    log(f"compile cache: {cache_events['cache_hits']} hits, "
        f"{cache_events['cache_misses']} misses")
    if failed:
        log(f"FAILED phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
