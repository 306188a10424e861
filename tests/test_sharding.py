"""Sharding-rule tests (pure spec logic — no devices needed) plus a
subprocess mini dry-run on 8 forced host devices."""

import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.sharding import partition


MESH_AXES = {"model": "model", "data": "data", "model_size": 16, "data_size": 16}


def spec_of(arch, path, shape):
    cfg = configs.get_config(arch)
    return partition.param_spec(path, shape, cfg=cfg, mesh_axes=MESH_AXES)


def test_megatron_col_row_rules():
    assert spec_of("qwen2-7b", "stages/0/b0/attn/w_qkv", (28, 3584, 4608)) == P(None, None, "model")
    assert spec_of("qwen2-7b", "stages/0/b0/attn/w_o", (28, 3584, 3584)) == P(None, "model", None)
    assert spec_of("qwen2-7b", "stages/0/b0/mlp/w_up", (28, 3584, 18944)) == P(None, None, "model")
    assert spec_of("qwen2-7b", "stages/0/b0/mlp/w_down", (28, 18944, 3584)) == P(None, "model", None)


def test_norms_replicated():
    assert spec_of("qwen2-7b", "stages/0/b0/attn/norm/scale", (28, 3584)) == P(None, None)


def test_vocab_parallel_embedding():
    assert spec_of("qwen2-7b", "embed/tok", (152064, 3584)) == P("model", None)


def test_indivisible_dims_stay_replicated():
    # 28 heads * 128 = 3584 divisible, but a 30-wide dim is not
    assert spec_of("qwen2-7b", "stages/0/b0/attn/w_qkv", (28, 3584, 30)) == P(None, None, None)


def test_expert_sharding_modes():
    # deepseek: 64 experts / 16 shards -> expert axis sharded
    s = spec_of("deepseek-moe-16b", "stages/1/b0/moe/w_up", (27, 64, 2048, 1408))
    assert s == P(None, "model", None, None)
    # mixtral: 8 experts < 16 -> TP inside experts on the ff dim
    s = spec_of("mixtral-8x7b", "stages/0/b0/moe/w_up", (32, 8, 4096, 14336))
    assert s[3] == "model" or s[1] == "model"  # ffn sharded (+ fsdp may add data)


def test_fsdp_adds_data_axis():
    s = spec_of("llama-3.2-vision-90b", "stages/0/b0/mlp/w_up", (20, 8192, 28672))
    assert "model" in s and "data" in s


def test_zero1_spec():
    z = partition.zero1_spec(P(None, "model"), (4096, 14336), data_axis="data", data_size=16)
    assert z == P("data", "model")
    # no divisible free axis -> unchanged
    z = partition.zero1_spec(P(None, "model"), (30, 14336), data_axis="data", data_size=16)
    assert z == P(None, "model")


def test_filter_spec_drops_missing_axes():
    assert partition.filter_spec(P(("pod", "data"), "model"), ("data", "model")) == P(
        ("data",), "model"
    )
    assert partition.filter_spec(P("pod", None), ("data", "model")) == P(None, None)


def test_cache_leaf_spec_prefers_heads_then_seq():
    # (count, B, Hkv, S, hd): heads divisible -> model on heads
    s = partition.cache_leaf_spec((28, 128, 16, 32768, 128), ("data",), model_size=16)
    assert s == P(None, ("data",), "model", None, None)
    # heads=4 not divisible -> sequence sharded
    s = partition.cache_leaf_spec((28, 128, 4, 32768, 128), ("data",), model_size=16)
    assert s == P(None, ("data",), None, "model", None)


def test_batch_pspec_divisibility():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    assert partition.batch_pspec(256, FakeMesh()) == ("data",)
    assert partition.batch_pspec(1, FakeMesh()) is None


@pytest.mark.slow
def test_mini_dryrun_8dev_subprocess(tmp_path):
    """End-to-end SPMD proof on 8 forced host devices (own process so the
    main test process keeps its single-device view)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_BF16_DOT"] = "1"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch import specs
from repro.models import transformer as tf
from repro.optim import adamw

cfg = configs.get_config("qwen2-7b-smoke").with_(n_layers=2)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = cfg.with_(attn_shard="head")  # 4 heads / 4-way model axis
step = specs.make_step(cfg, configs.SHAPE_CELLS["train_4k"], mesh)
params_abs = tf.abstract_params(cfg)
pshard = specs.param_shardings(cfg, mesh)
oshard = specs.opt_shardings(cfg, mesh)
opt_abs = jax.eval_shape(adamw.init, params_abs)
inputs = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
          "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
in_sh = {"tokens": NamedSharding(mesh, P("data", None)),
         "labels": NamedSharding(mesh, P("data", None))}
with jax.sharding.set_mesh(mesh):
    lowered = jax.jit(step, in_shardings=(pshard, oshard, in_sh),
                      out_shardings=(pshard, oshard, None)).lower(params_abs, opt_abs, inputs)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, list) else ca  # jax 0.4.x returns [dict]
print("COMPILED_OK", ca.get("flops", 0) > 0)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "COMPILED_OK True" in r.stdout, r.stderr[-2000:]
