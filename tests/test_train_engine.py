"""Gradient-correctness tier for the training hot path (DESIGN.md §13).

Three families:

* **flash backward** — the custom-VJP flash kernel (`kernels/flash.py`)
  against the naive `ref.py` attention oracle: ``jax.test_util.check_grads``
  (fp32 second-order, rev mode — fwd-mode AD is unsupported on custom_vjp),
  reference-VJP comparison for bf16, causal and non-causal, ragged
  sequence lengths, ``q_offset`` continuation, and the zero-size batch;
  plus jaxpr asserts that the backward lowers to pallas_calls without
  materializing the full ``(B, H, S, S)`` attention matrix (kernel VMEM
  tiles are 2-D ``(bq, bk)`` blocks — only the naive path stages the 4-D
  batched matrix).
* **grad accumulation** — ``make_train_step(accum_steps=k)`` matches
  ``accum_steps=1`` on the same effective batch to fp32-accumulator
  tolerance (the microbatch mean-of-means reassociates the reduction, so
  exact bit identity is not attainable; the bound here is ~100x tighter
  than any training-relevant signal), and raises a clear ``ValueError``
  when the batch is not divisible.
* **blockwise-parallel blocks** — chunked attention+FFN forward
  *bit-matches* the monolithic block (fp32) for every remat policy
  (masked KV chunks pass the online-softmax state through unchanged, so
  truncation is exact); gradients tolerance-match (query-chunking
  reassociates the dk/dv accumulation).  The Pallas kernel dispatch path
  (`REPRO_PALLAS_INTERPRET=1`) is exercised explicitly.  The same equivalence
  on the 8-device mesh lives in ``tests/test_dist_plan.py`` (the ``make
  test-dist`` launcher).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro import configs
from repro.kernels import flash, ref
from repro.models import attention, common, mlp
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train import trainer

INTERP = jax.default_backend() != "tpu"
RNG = np.random.default_rng(11)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def qkv(b, hq, hkv, sq, skv, d, dtype=jnp.float32):
    return (
        rand((b, hq, sq, d), dtype),
        rand((b, hkv, skv, d), dtype),
        rand((b, hkv, skv, d), dtype),
    )


def tree_maxdiff(a, b):
    return max(
        float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# flash backward: check_grads + reference-VJP comparisons
# ---------------------------------------------------------------------------


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_check_grads_fp32_second_order(self, causal):
        """fp32 rectangular kernel: first+second order rev-mode derivatives
        match finite differences (ISSUE 7 acceptance)."""
        q, k, v = qkv(1, 4, 2, 24, 24, 8)

        def f(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8, interpret=INTERP
            )

        check_grads(f, (q, k, v), order=2, modes=["rev"], atol=2e-2, rtol=2e-2)

    def test_check_grads_triangular_fp32_second_order(self):
        """The triangular (prefetch-table) kernel differentiates too."""
        q, k, v = qkv(1, 2, 2, 24, 24, 8)

        def f(q, k, v):
            return flash.flash_attention_triangular(
                q, k, v, block_q=8, block_k=8, interpret=INTERP
            )

        check_grads(f, (q, k, v), order=2, modes=["rev"], atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_ref_fp32(self, causal):
        """First-order VJP against the naive ref.py oracle, fp32."""
        q, k, v = qkv(2, 4, 2, 32, 32, 16)
        do = rand((2, 4, 32, 16))

        def fl(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=causal, block_q=16, block_k=16, interpret=INTERP
            )

        def rf(q, k, v):
            return ref.attention(q, k, v, causal=causal)

        g1 = jax.vjp(fl, q, k, v)[1](do)
        g2 = jax.vjp(rf, q, k, v)[1](do)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
            )

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_ref_bf16(self, causal):
        """bf16 first-order reference-VJP comparison (finite differences
        are too noisy at bf16 resolution, so the oracle IS the check)."""
        q, k, v = qkv(1, 4, 2, 32, 32, 16, jnp.bfloat16)
        do = rand((1, 4, 32, 16), jnp.bfloat16)

        def fl(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=causal, block_q=16, block_k=16, interpret=INTERP
            )

        def rf(q, k, v):
            return ref.attention(q, k, v, causal=causal)

        g1 = jax.vjp(fl, q, k, v)[1](do)
        g2 = jax.vjp(rf, q, k, v)[1](do)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.08, rtol=0.08,
            )

    def test_grads_ragged_and_offset(self):
        """Non-multiple-of-block shapes + q_offset continuation: the padded
        rows are cleaned inside the kernels, so grads match ref exactly on
        the valid region (and carry no NaN)."""
        q, k, v = qkv(1, 4, 2, 13, 29, 8)
        do = rand((1, 4, 13, 8))

        def fl(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=True, q_offset=16, block_q=8, block_k=8,
                interpret=INTERP,
            )

        def rf(q, k, v):
            return ref.attention(q, k, v, causal=True, q_offset=16)

        g1 = jax.vjp(fl, q, k, v)[1](do)
        g2 = jax.vjp(rf, q, k, v)[1](do)
        for a, b in zip(g1, g2):
            assert not np.isnan(np.asarray(a)).any()
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
            )

    def test_zero_size_batch(self):
        """b=0 flows through fwd and bwd without tracing errors or NaN."""
        q, k, v = qkv(0, 4, 2, 8, 8, 8)

        def loss(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=True, block_q=8, block_k=8, interpret=INTERP
            ).sum()

        val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert float(val) == 0.0
        assert grads[0].shape == (0, 4, 8, 8)
        assert grads[1].shape == (0, 2, 8, 8)

    def test_backward_lowers_to_pallas_no_sxs(self):
        """The grad jaxpr contains the three pallas_calls (fwd + dq sweep +
        dkv sweep) and never stages the batched (B, H, S, S) attention
        matrix — the hallmark of the naive path.  Kernel-internal VMEM
        tiles are 2-D (bq, bk) blocks smaller than S, so the 4-D shape
        pattern is a precise discriminator."""
        b, hq, s, d = 2, 4, 48, 8
        q, k, v = qkv(b, hq, 2, s, s, d)

        def loss(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16, interpret=INTERP
            ).sum()

        jx = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
        assert len(re.findall(r"\bpallas_call\b", jx)) >= 3
        sxs = rf"f32\[{b},{hq},{s},{s}\]"
        assert not re.search(sxs, jx), "full attention matrix materialized"
        # the naive ref path DOES stage it — sanity-check the discriminator
        jx_ref = str(
            jax.make_jaxpr(
                jax.grad(lambda a, c, w: ref.attention(a, c, w).sum(),
                         argnums=(0, 1, 2))
            )(q, k, v)
        )
        assert re.search(sxs, jx_ref)

    def test_plan_flash_bwd_identity_and_describe(self):
        """Plan-engine contract: lru identity + human-readable describe."""
        p1 = flash.plan_flash_bwd(2, 4, 2, 256, 256, 64, jnp.float32)
        p2 = flash.plan_flash_bwd(2, 4, 2, 256, 256, 64, jnp.float32)
        assert p1 is p2
        assert p1.block_q == 256 and p1.block_k == 256
        assert "flash_bwd" in p1.describe()
        assert p1.bytes_moved == flash.bwd_dma_bytes(
            2, 4, 2, 256, 256, 64, 4, block_q=256, block_k=256
        )


# ---------------------------------------------------------------------------
# grad accumulation
# ---------------------------------------------------------------------------


def _smoke_cfg(**kw):
    return configs.get_config("qwen2-7b-smoke").with_(dtype="float32", **kw)


def _batch(cfg, b, s, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(k2, (b, s), 0, cfg.vocab),
    }


class TestGradAccum:
    def test_accum_matches_single_step(self):
        """accum_steps=2/4 reproduce the accum_steps=1 update on the same
        effective batch: loss to fp32-mean tolerance, updated params to
        ~1e-7 (fp32 accumulators; reduction reassociation only)."""
        cfg = _smoke_cfg()
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init(params)
        oc = adamw.OptConfig(lr=1e-3)
        batch = _batch(cfg, 4, 32)
        p_ref, _, m_ref = trainer.make_train_step(cfg, oc, None)(params, opt, batch)
        for k in (2, 4):
            p_k, _, m_k = trainer.make_train_step(
                cfg, oc, None, accum_steps=k
            )(params, opt, batch)
            assert abs(float(m_ref["loss"]) - float(m_k["loss"])) < 5e-6
            assert tree_maxdiff(p_ref, p_k) < 1e-6

    def test_accum_indivisible_raises(self):
        """batch % accum_steps != 0 is a clear ValueError, not a reshape
        traceback."""
        cfg = _smoke_cfg()
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init(params)
        step = trainer.make_train_step(
            cfg, adamw.OptConfig(), None, accum_steps=3
        )
        with pytest.raises(ValueError, match="divisible"):
            step(params, opt, _batch(cfg, 4, 16))


# ---------------------------------------------------------------------------
# blockwise-parallel blocks vs monolithic
# ---------------------------------------------------------------------------

POLICIES = list(common.REMAT_POLICIES)


class TestBlockwise:
    def test_remat_policy_resolution(self):
        """Name -> policy table, including the aliases and the error."""
        assert common.remat_policy(None) is None
        assert common.remat_policy("none") is None
        assert common.remat_policy("nothing_saveable") is None
        for name in POLICIES[1:]:
            assert callable(common.remat_policy(name))
        with pytest.raises(ValueError, match="unknown remat policy"):
            common.remat_policy("save_everything_twice")

    @pytest.mark.parametrize("policy", POLICIES)
    def test_loss_and_grads_match_monolithic_fp32(self, policy):
        """Forward loss bit-matches (masked-KV truncation is exact);
        gradients match to fp32 reassociation tolerance for every policy."""
        cfg = _smoke_cfg()
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        batch = _batch(cfg, 2, 96)

        def lossg(c):
            return jax.value_and_grad(
                lambda p: tf.loss_fn(p, c, batch["tokens"], batch["labels"])
            )(params)

        l_mono, g_mono = lossg(cfg)
        l_bw, g_bw = lossg(
            cfg.with_(blockwise=True, blockwise_chunk=32, remat_policy=policy)
        )
        assert float(l_mono) == float(l_bw)  # bit-identical forward
        assert tree_maxdiff(g_mono, g_bw) < 1e-6

    def test_loss_matches_monolithic_bf16(self):
        """bf16 model: tolerance match (bf16 resolution)."""
        cfg = configs.get_config("qwen2-7b-smoke")
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        batch = _batch(cfg, 2, 64)
        l1 = tf.loss_fn(params, cfg, batch["tokens"], batch["labels"])
        l2 = tf.loss_fn(
            params, cfg.with_(blockwise=True, blockwise_chunk=32),
            batch["tokens"], batch["labels"],
        )
        assert abs(float(l1) - float(l2)) < 1e-3

    def test_uneven_sequence_bitmatch(self):
        """Sequence not a multiple of the chunk: ragged tail chunk."""
        cfg = _smoke_cfg(loss_chunk=7)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        batch = _batch(cfg, 2, 77)
        l1 = tf.loss_fn(params, cfg, batch["tokens"], batch["labels"])
        l2 = tf.loss_fn(
            params, cfg.with_(blockwise=True, blockwise_chunk=32),
            batch["tokens"], batch["labels"],
        )
        assert float(l1) == float(l2)

    def test_blockwise_attention_kernel_path_bitmatch(self, monkeypatch):
        """With the Pallas kernel dispatch forced on, the q-chunked wrapper
        (static per-chunk q_offset + aligned KV truncation) bit-matches the
        monolithic kernel call in fwd AND grad."""
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        q, k, v = qkv(1, 4, 2, 64, 64, 16)
        mono = attention.flash_attention(q, k, v, causal=True, chunk=32)
        bw = attention.flash_attention_blockwise(
            q, k, v, causal=True, chunk=32, q_chunk=16
        )
        np.testing.assert_array_equal(np.asarray(mono), np.asarray(bw))
        g1 = jax.grad(
            lambda a: attention.flash_attention(a, k, v, causal=True, chunk=32).sum()
        )(q)
        g2 = jax.grad(
            lambda a: attention.flash_attention_blockwise(
                a, k, v, causal=True, chunk=32, q_chunk=16
            ).sum()
        )(q)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    def test_blockwise_grad_jaxpr_no_sxs(self, monkeypatch):
        """Under nothing_saveable + kernel dispatch, the blockwise grad
        jaxpr lowers to pallas_calls and stages no (B, H, S, S) f32
        matrix."""
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        b, h, s, d = 1, 4, 64, 16
        q, k, v = qkv(b, h, 2, s, s, d)

        def loss(q):
            return attention.flash_attention_blockwise(
                q, k, v, causal=True, chunk=32, q_chunk=32, policy=None
            ).sum()

        jx = str(jax.make_jaxpr(jax.grad(loss))(q))
        assert re.search(r"\bpallas_call\b", jx)
        assert not re.search(rf"f32\[{b},{h},{s},{s}\]", jx)

    def test_mlp_blockwise_matches(self):
        """The seq-chunked FFN is pointwise over sequence; the chunked
        output shape changes XLA's GEMM tiling, so equality holds to
        last-ulp accumulation tolerance (measured ~2e-7 fp32), ragged
        tail chunk included."""
        cfg = _smoke_cfg()
        p = mlp.mlp_init(jax.random.PRNGKey(5), cfg)
        x = rand((2, 50, cfg.d_model))
        y1 = mlp.mlp_apply(p, cfg, x)
        y2 = mlp.mlp_apply_blockwise(p, cfg, x, chunk=16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-6)
        g1 = jax.grad(lambda a: mlp.mlp_apply(p, cfg, a).sum())(x)
        g2 = jax.grad(lambda a: mlp.mlp_apply_blockwise(p, cfg, a, chunk=16).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-6)

    def test_train_step_runs_blockwise(self):
        """make_train_step over the blockwise model: finite loss + grads
        flow (the full wiring: chunked blocks -> accumulation -> AdamW)."""
        cfg = _smoke_cfg(blockwise=True, blockwise_chunk=32)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init(params)
        step = trainer.make_train_step(
            cfg, adamw.OptConfig(lr=1e-3), None, accum_steps=2
        )
        p2, _, metrics = step(params, opt, _batch(cfg, 4, 64))
        assert np.isfinite(float(metrics["loss"]))
        assert tree_maxdiff(params, p2) > 0  # params actually moved
