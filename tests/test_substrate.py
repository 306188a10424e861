"""Substrate tests: optimizer, checkpoint, data pipeline, elastic plan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, Prefetcher, SyntheticSource
from repro.optim import adamw
from repro.train.checkpoint import Checkpointer
from repro.train import elastic


def test_adamw_converges_quadratic():
    oc = adamw.OptConfig(lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0]), "b": jnp.asarray(2.0)}
    state = adamw.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, metrics = adamw.update(params, g, state, oc)
    assert float(loss(params)) < 1e-2
    assert int(state["step"]) == 200


def test_lr_schedule_shape():
    oc = adamw.OptConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(adamw.lr_at(oc, jnp.asarray(s))) for s in [0, 5, 10, 55, 100]]
    assert lrs[1] == pytest.approx(0.5, abs=0.01)  # mid-warmup
    assert lrs[2] == pytest.approx(1.0, abs=0.01)  # peak
    assert lrs[3] < lrs[2]  # decaying
    assert lrs[4] == pytest.approx(0.1, abs=0.01)  # floor


def test_grad_clipping():
    oc = adamw.OptConfig(lr=0.0, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)
    g = {"w": jnp.asarray([100.0, 0.0, 0.0])}
    _, _, metrics = adamw.update(params, g, state, oc)
    assert float(metrics["grad_norm"]) == pytest.approx(100.0)


def test_checkpoint_roundtrip(tmp_path):
    ckpt = Checkpointer(tmp_path, keep_last=2, async_save=False)
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((2, 2), jnp.bfloat16), "d": [jnp.int32(7), jnp.zeros(3)]},
    }
    ckpt.save(10, tree)
    ckpt.save(20, tree)
    ckpt.save(30, tree)
    assert ckpt.all_steps() == [20, 30]  # pruned to keep_last
    skel = jax.tree.map(np.asarray, tree)
    restored = ckpt.restore(30, skel)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert jnp.asarray(a).dtype == jnp.asarray(b).dtype


def test_checkpoint_atomicity(tmp_path):
    ckpt = Checkpointer(tmp_path, async_save=False)
    ckpt.save(1, {"x": jnp.ones(4)})
    # a crashed write leaves only a .tmp dir — must be invisible
    (tmp_path / "step_00000002.tmp").mkdir()
    assert ckpt.latest_step() == 1


def test_data_determinism_and_resume():
    dc = DataConfig(batch=4, seq=16, vocab=1000, seed=7)
    src = SyntheticSource(dc)
    b5 = src.batch_at(5)
    b5_again = src.batch_at(5)
    np.testing.assert_array_equal(b5["tokens"], b5_again["tokens"])
    # labels are next-token shifted
    full = src.batch_at(3)
    assert full["tokens"].shape == (4, 16)
    # host sharding partitions the batch
    dc2 = DataConfig(batch=4, seq=16, vocab=1000, seed=7, n_hosts=2, host_id=1)
    half = SyntheticSource(dc2).batch_at(5)
    assert half["tokens"].shape == (2, 16)


def test_prefetcher_orders_steps():
    dc = DataConfig(batch=2, seq=8, vocab=100, seed=1)
    pf = Prefetcher(SyntheticSource(dc), start_step=10)
    steps = [pf.next()[0] for _ in range(4)]
    pf.close()
    assert steps == [10, 11, 12, 13]


def test_elastic_plan_mesh_shrinks_data_axis():
    # a 128-device slice losing 9 devices: model width preserved, data
    # shrinks to the largest multiple (stragglers evicted)
    shape, axes = elastic.plan_mesh_shape(119, model_width=16)
    assert shape == (7, 16) and axes == ("data", "model")
    shape, axes = elastic.plan_mesh_shape(512, model_width=16, pods=2)
    assert shape == (2, 16, 16)
    with pytest.raises(ValueError):
        elastic.plan_mesh_shape(8, model_width=16)
    assert elastic.rescale_batch(256, old_data=16, new_data=12) == 192


def test_trainer_accum_equivalence():
    """accum=2 over a doubled batch == accum=1 averaged gradients."""
    from repro import configs
    from repro.train.trainer import make_train_step

    cfg = configs.get_config("xlstm-125m-smoke")
    from repro.models import transformer as tf

    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    oc = adamw.OptConfig(lr=1e-3)
    opt = adamw.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    step1 = make_train_step(cfg, oc, None, accum_steps=1)
    step2 = make_train_step(cfg, oc, None, accum_steps=2)
    p1, _, m1 = step1(params, opt, batch)
    p2, _, m2 = step2(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=2e-2
        )


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The cache goes where JAX_COMPILATION_CACHE_DIR points (and the code
    then sets no directory of its own), else to <checkout>/.jax_cache."""
    from pathlib import Path

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        if env_dir is None:
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
