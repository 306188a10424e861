"""Per-arch smoke tests (deliverable (f)): every assigned architecture's
reduced config runs one forward/train step on CPU with finite loss and
correct shapes, plus decode-vs-forward consistency for each family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(0)
B, S = 2, 48


def build(arch):
    cfg = configs.get_config(arch + "-smoke")
    params = tf.init_params(KEY, cfg)
    frontend = None
    if cfg.encoder_layers or cfg.n_frontend_tokens:
        frontend = jax.random.normal(
            KEY, (B, cfg.n_frontend_tokens, cfg.d_model), jnp.float32
        )
    return cfg, params, frontend


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_train_step_smoke(arch):
    cfg, params, frontend = build(arch)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    labels = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    loss, grads = jax.value_and_grad(
        lambda p: tf.loss_fn(p, cfg, tokens, labels, frontend=frontend)
    )(params)
    assert np.isfinite(float(loss)), arch
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l, dtype=np.float32))) for l in leaves)


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_forward_shapes(arch):
    cfg, params, frontend = build(arch)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    h, aux = tf.forward(params, cfg, tokens, frontend=frontend)
    assert h.shape == (B, S, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(h, np.float32)))
    logits = tf._logits_chunk(params, cfg, h[:, -1:])
    assert logits.shape == (B, 1, cfg.vocab)


def _merge_cache(cache0, cache):
    def merge(dst, src):
        if isinstance(dst, dict):
            return {k: merge(dst[k], src[k]) for k in dst}
        if isinstance(dst, list):
            return [merge(a, b) for a, b in zip(dst, src)]
        if dst.ndim == src.ndim and dst.shape != src.shape:
            sl = [slice(None)] * dst.ndim
            sl[-2] = slice(0, src.shape[-2])
            return dst.at[tuple(sl)].set(src)
        return src

    return [merge(c0, c) for c0, c in zip(cache0, cache)]


@pytest.mark.parametrize(
    "arch",
    [
        "qwen2-7b",           # dense GQA full attention
        "mixtral-8x7b",       # MoE + SWA ring cache
        "recurrentgemma-2b",  # RG-LRU + local attention hybrid
        "xlstm-125m",         # recurrent states
        "seamless-m4t-large-v2",  # enc-dec cross caches
        "llama-3.2-vision-90b",   # VLM cross-attn layers
        "deepseek-moe-16b",   # shared+routed MoE (dropless decode capacity)
    ],
)
def test_decode_matches_forward(arch):
    cfg, params, frontend = build(arch)
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab)
    h, _ = tf.forward(params, cfg, toks, frontend=frontend)
    logits_full = tf._logits_chunk(params, cfg, h[:, -1:])[:, 0]
    _, cache = tf.prefill(params, cfg, toks[:, :S], frontend=frontend)
    cache = _merge_cache(tf.init_cache(cfg, B, S + 8), cache)
    src = None
    if not cfg.encoder_layers and cfg.n_frontend_tokens:
        src = frontend.astype(cfg.np_dtype)
    logits_d, _, _ = tf.decode_step(
        params, cfg, toks[:, S], cache, jnp.int32(S), frontend_src=src
    )
    err = float(jnp.max(jnp.abs(logits_d - logits_full)))
    scale = float(jnp.max(jnp.abs(logits_full))) + 1e-9
    # MoE full-forward uses finite capacity (legit token dropping), so
    # tolerate slightly more there; bf16 noise otherwise.
    tol = 0.08 if cfg.moe is not None else 0.05
    assert err / scale < tol, f"{arch}: rel err {err/scale:.4f}"


def test_moe_dense_vs_sort_dispatch_agree():
    cfg = configs.get_config("deepseek-moe-16b-smoke")
    from repro.models import moe as moe_mod

    key = jax.random.PRNGKey(3)
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.float32).astype(cfg.np_dtype)
    cap = 2 * 16 * cfg.moe.top_k  # dropless
    yd, _ = moe_mod.moe_dense(p, cfg, x, capacity=cap)
    ys, _ = moe_mod.moe_sort(p, cfg, x, capacity=cap)
    np.testing.assert_allclose(
        np.asarray(yd, np.float32), np.asarray(ys, np.float32), rtol=2e-2, atol=2e-2
    )


def test_flash_vs_exact_attention():
    from repro.models import attention as attn

    key = jax.random.PRNGKey(0)
    b, hq, hkv, s, d = 2, 8, 2, 96, 32
    q = jax.random.normal(key, (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    for chunk in (16, 32, 96, 100):
        out = attn.flash_attention(q, k, v, causal=True, chunk=chunk)
        # exact reference: full softmax with causal mask
        qg = q.reshape(b, hkv, hq // hkv, s, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * (d**-0.5)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
        want = jnp.einsum(
            "bhgqk,bhkd->bhgqd", jax.nn.softmax(logits, -1), v
        ).reshape(b, hq, s, d)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_local_attention_matches_masked_full():
    from repro.models import attention as attn

    key = jax.random.PRNGKey(0)
    b, h, s, d, w = 1, 2, 64, 16, 16
    q = jax.random.normal(key, (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.float32)
    out = attn.local_attention(q, k, v, window=w)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d**-0.5)
    qpos, kpos = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = (qpos >= kpos) & (kpos > qpos - w)
    logits = jnp.where(mask, logits, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)
