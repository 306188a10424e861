"""Compile-only checks of the main-path kernels for a described TPU v5e.

The Pallas interpreter does not enforce the chip's tiling rules, so every
kernel here is compiled by the real TPU compiler for a ``v5e:2x2``
topology that is described, not attached (nothing runs).  Each test
asserts that the compiled program holds the Pallas kernel
(``tpu_custom_call``) — a kernel the compiler refuses raises instead.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the worker given this file may do so.
"""

import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import stencil as st
from repro.kernels import flash, grouped_matmul, ops
from repro.models import moe
from repro.models import transformer as tf


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not describable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_dispatch(monkeypatch):
    """Steer the dispatch layer to the compiled Pallas kernels (the test
    process itself sees only the CPU)."""
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _kernels(fn, sharding, *specs) -> int:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


F32, BF16 = jnp.float32, jnp.bfloat16
# qwen2-7b attention widths: 28 query heads, 4 KV heads, head_dim 128
HQ, HKV, D, S = 28, 4, 128, 4096


@pytest.mark.parametrize(
    "shape,dtype,perm",
    [
        ((8, 4096, 28, 128), BF16, (0, 2, 1, 3)),  # attention head swap
        ((512, 512, 512), F32, (2, 1, 0)),  # full 3-D reorder
        ((32, 32, 32, 32, 64), F32, (4, 2, 0, 3, 1)),  # 5-D reorder
    ],
)
def test_permute_compiles(one_chip, chip_dispatch, shape, dtype, perm):
    assert _kernels(lambda x: ops.permute(x, perm), one_chip, (shape, dtype)) >= 1


def test_interlace_and_deinterlace_compile(one_chip, chip_dispatch):
    n, L = 4, 1 << 26
    assert _kernels(lambda *a: ops.interlace(list(a)), one_chip, *[((L,), F32)] * n) >= 1
    assert _kernels(lambda x: ops.deinterlace(x, n)[0], one_chip, ((n * L,), F32)) >= 1


@pytest.mark.parametrize(
    "shape",
    [
        (4096, 4096),
        (65536, 7168),  # the benchmark's grid, the widest the fused plan takes
    ],
    ids=["4096x4096", "65536x7168"],
)
def test_fused_jacobi_compiles(one_chip, chip_dispatch, shape):
    prog = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4).repeat(8)
    assert prog.compile(shape, F32, boundary="reflect").mode == "fused"
    fn = lambda x: prog(x, boundary="reflect")  # noqa: E731
    assert _kernels(fn, one_chip, (shape, F32)) == 1


def test_flash_forward_and_backward_compile(one_chip):
    q, kv = ((1, HQ, S, D), BF16), ((1, HKV, S, D), BF16)

    def fwd(a, b, c):
        return flash.flash_attention(a, b, c, interpret=False)

    def bwd(a, b, c, do):
        return jax.vjp(fwd, a, b, c)[1](do)

    assert _kernels(fwd, one_chip, q, kv, kv) == 1
    assert _kernels(bwd, one_chip, q, kv, kv, q) == 3  # fwd + dq + dkv


def test_flash_decode_compiles(one_chip):
    def dec(q, k, v, lens):
        return flash.flash_decode(q, k, v, lengths=lens, interpret=False)

    specs = [((4, HQ, 1, D), BF16), ((4, HKV, S, D), BF16), ((4, HKV, S, D), BF16),
             ((4,), jnp.int32)]
    assert _kernels(dec, one_chip, *specs) == 2  # split stage + combine


def test_masked_blocked_gather_compiles(one_chip, chip_dispatch):
    def gather(x, idx):
        return ops.gather_rows(x, idx, masked=True)

    specs = [((65536, 3584), BF16), ((65536,), jnp.int32)]
    assert _kernels(gather, one_chip, *specs) == 1


@pytest.mark.parametrize(
    "n_src,c,dtype",
    [
        (65536, 3584, F32),  # the rearrange cell's shape in f32: 8-row fetch, 8-row group
        (24, 3584, BF16),  # whole 8-row HBM tiles, not whole 16-row VMEM tiles
    ],
    ids=["f32-65536x3584", "bf16-24x3584"],
)
def test_masked_blocked_gather_compiles_per_dtype(one_chip, chip_dispatch, n_src, c, dtype):
    def gather(x, idx):
        return ops.gather_rows(x, idx, masked=True)

    specs = [((n_src, c), dtype), ((n_src,), jnp.int32)]
    assert _kernels(gather, one_chip, *specs) == 1


def test_moe_gather_combine_compiles(one_chip, chip_dispatch):
    # mixtral-8x7b widths: d_model 4096, top-2 of 8 experts, 8192 tokens
    # at capacity factor 1.25 -> 8 * 2560 expert rows
    specs = [((20480, 4096), BF16), ((8192, 2), jnp.int32), ((8192, 2), F32)]
    assert _kernels(ops.gather_combine, one_chip, *specs) == 1


# deepseek-moe-16b widths: d_model 2048, 16 query and 16 KV heads of 128,
# experts of width 1408, top-6 of 64 routed experts, 8 of them held here
DS_D, DS_F, DS_HELD = 2048, 1408, 8


def test_flash_decode_compiles_for_mha(one_chip):
    def dec(q, k, v, lens):
        return flash.flash_decode(q, k, v, lengths=lens, interpret=False)

    specs = [((16, 16, 1, D), BF16), ((16, 16, S, D), BF16), ((16, 16, S, D), BF16),
             ((16,), jnp.int32)]
    assert _kernels(dec, one_chip, *specs) == 2


@pytest.mark.parametrize("tokens", [16, 8192], ids=["decode", "chunk-wave"])
def test_moe_dispatch_and_combine_compile_at_deepseek_width(one_chip, chip_dispatch, tokens):
    tm = moe.expert_tile_rows(tokens, 6, 64)
    rows = (-(-tokens * 6 // tm) + DS_HELD) * tm

    def dispatch_combine(x, src, back, gates):
        return ops.gather_combine(ops.gather_rows(x, src, masked=True), back, gates)

    specs = [((tokens, DS_D), BF16), ((rows,), jnp.int32), ((tokens, 6), jnp.int32),
             ((tokens, 6), F32)]
    assert _kernels(dispatch_combine, one_chip, *specs) == 2


def test_grouped_swiglu_compiles_at_deepseek_width(one_chip):
    tm, n_tiles = 128, 392  # a chunk wave of 16 x 512 rows, top-6, 8 held

    def gmm(x, wg, wu, wd, tile_group, n_used):
        return grouped_matmul.grouped_swiglu(x, wg, wu, wd, tile_group, n_used, tm=tm,
                                             interpret=False)

    specs = [((n_tiles * tm, DS_D), BF16), ((DS_HELD, DS_D, DS_F), BF16),
             ((DS_HELD, DS_D, DS_F), BF16), ((DS_HELD, DS_F, DS_D), BF16),
             ((n_tiles,), jnp.int32), ((), jnp.int32)]
    assert _kernels(gmm, one_chip, *specs) == 1


@pytest.fixture(scope="module")
def deepseek_stage(one_chip):
    """The benchmark cell's program: deepseek-moe-16b's first pipeline
    stage (the dense layer and six MoE layers) holding experts 0-7, 16
    slots of a 4096-row ring, as shapes on the described chip."""
    cfg = configs.get_config("deepseek-moe-16b")
    cfg = cfg.with_(n_layers=7, explicit_plan=((("attn_dense",), 1), (("attn_moe",), 6)),
                    moe=replace(cfg.moe, held=(0, DS_HELD)))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                           sharding=one_chip), tree)

    params = on_chip(tf.abstract_params(cfg))
    cache = on_chip(jax.eval_shape(lambda: tf.init_cache(cfg, 16, S)))
    return cfg, params, cache


def test_deepseek_decode_and_chunk_wave_compile(one_chip, chip_dispatch, deepseek_stage):
    cfg, params, cache = deepseek_stage
    b, c = 16, 512

    def decode(p, tok, kv, pos):
        return tf.decode_step(p, cfg, jnp.maximum(tok, 0), kv, pos, valid=tok >= 0)

    def chunk_wave(p, toks, kv, pos, active, last):
        return tf.prefill_chunk(p, cfg, toks, kv, pos, active, last)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    programs = {
        "decode": jax.jit(decode).lower(params, i32(b), cache, i32(b)),
        "chunk wave": jax.jit(chunk_wave).lower(
            params, i32(b, c), cache, i32(b), jax.ShapeDtypeStruct((b,), bool, sharding=one_chip),
            i32(b)),
    }
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        for kernel in ("grouped_swiglu", "gather_rows_blocked", "gather_combine_blocked"):
            assert f"%{kernel}" in text, (name, kernel)
