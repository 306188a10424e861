"""Serving-engine suite (ISSUE 6): split-KV decode attention, ragged
packed prefill, chunked prefill, and the continuous-batching engine.

Layers covered, bottom-up:

* `kernels.flash.flash_decode` — tolerance-banded vs the jnp one-shot
  oracle across fp32/bf16 with random per-slot lengths; jaxpr-asserted
  kernel counts (two ``pallas_call``s end to end, the stage-2 combine
  exactly ONE); plan identity.
* `core.index_plan.ragged_layout` / ``ragged_rows`` plans — geometry,
  zero-length sequences, masked-only validation.
* `models.transformer.prefill_ragged` + the engine's unpack — packed KV
  rows and logits match per-prompt prefill (pack/unpack oracle
  equivalence).
* `models.transformer.decode_step` with a (B,) position vector — slots
  at different positions decode exactly like single-slot scalar decode
  (the seed's max-pos bug).
* `serve.engine.Engine` — admit returns the slot, staggered multi-tenant
  traffic matches a clean per-request greedy reference in ragged,
  ragged+chunked and bucket-capacity terms, run() retires everything.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import index_plan as ip
from repro.kernels import flash
from repro.models import attention
from repro.models import transformer as tf
from repro.serve.engine import Engine, Request, _write_ragged, _write_slot

KEY = jax.random.PRNGKey(0)


# -- split-KV decode kernel --------------------------------------------------


def _rand_qkv(key, b, hq, hkv, s, d, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, 1, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize(
    "dtype,tol",
    [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
    ids=["fp32", "bf16"],
)
def test_flash_decode_matches_oneshot_oracle(pallas_interpret, dtype, tol):
    b, hq, hkv, s, d = 3, 8, 2, 100, 32
    q, k, v = _rand_qkv(KEY, b, hq, hkv, s, d, dtype)
    lens = jnp.asarray([1, 37, 100], jnp.int32)  # random-ish per-slot ring fill
    got = flash.flash_decode(q, k, v, lengths=lens, num_splits=3, block_k=32)
    ref = attention.decode_attention(q, k, v, length=lens, engine="oneshot")
    assert got.shape == ref.shape == (b, hq, 1, d)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) <= tol, float(err)


def test_flash_decode_plan_geometry_and_identity():
    plan = flash.plan_flash_decode(4, 16, 4, 512, 64, jnp.bfloat16)
    assert plan is flash.plan_flash_decode(4, 16, 4, 512, 64, jnp.bfloat16)
    ns, bk = plan.num_splits, plan.block_k
    assert ns >= 1 and bk >= 1 and bk <= 512
    assert plan.bytes_moved > 0 and plan.roofline_s > 0
    assert "flash_decode" in plan.describe()


def test_flash_decode_jaxpr_kernel_counts():
    # end to end: stage-1 split kernel + stage-2 combine = TWO pallas_calls;
    # the combine alone is exactly ONE (the fused mid-softmax reduce)
    b, hq, hkv, s, d = 2, 4, 2, 64, 16
    q, k, v = _rand_qkv(KEY, b, hq, hkv, s, d, jnp.float32)
    lens = jnp.full((b,), s, jnp.int32)
    full = jax.make_jaxpr(
        lambda a, c, w, l: flash.flash_decode(
            a, c, w, lengths=l, num_splits=2, block_k=16, interpret=True
        )
    )(q, k, v, lens)
    assert len(re.findall(r"\bpallas_call\b", str(full))) == 2
    g = hq // hkv
    mid_o = jnp.zeros((b * hkv, 2, g, d), jnp.float32)
    mid_m = jnp.zeros((b * hkv, 2, g, 1), jnp.float32)
    mid_l = jnp.zeros((b * hkv, 2, g, 1), jnp.float32)
    comb = jax.make_jaxpr(
        lambda o, m, l: flash.decode_combine(o, m, l, num_splits=2, interpret=True)
    )(mid_o, mid_m, mid_l)
    assert len(re.findall(r"\bpallas_call\b", str(comb))) == 1


def test_decode_attention_per_slot_lengths():
    # vector lengths mask per slot: each row equals its scalar-length result
    b, hq, hkv, s, d = 3, 4, 2, 48, 16
    q, k, v = _rand_qkv(KEY, b, hq, hkv, s, d, jnp.float32)
    lens = jnp.asarray([5, 20, 48], jnp.int32)
    got = attention.decode_attention(q, k, v, length=lens, engine="oneshot")
    for i, ln in enumerate([5, 20, 48]):
        one = attention.decode_attention(
            q[i : i + 1], k[i : i + 1], v[i : i + 1], length=ln, engine="oneshot"
        )
        assert jnp.allclose(got[i], one[0], atol=1e-6)


# -- ragged layout + ragged_rows plans ---------------------------------------


def test_ragged_layout_geometry():
    lay = ip.ragged_layout((3, 0, 5), bucket=8)
    assert lay.total == 8 and lay.t_pad == 8
    assert lay.indptr == (0, 3, 3, 8)
    assert lay.seg_ids.tolist() == [0, 0, 0, 2, 2, 2, 2, 2]
    assert lay.positions.tolist() == [0, 1, 2, 0, 1, 2, 3, 4]
    unp = lay.unpack_index(4)
    assert unp[0].tolist() == [0, 1, 2, -1]
    assert unp[1].tolist() == [-1, -1, -1, -1]  # zero-length: all sentinels
    assert unp[2].tolist() == [3, 4, 5, 6]
    assert ip.ragged_layout((3, 0, 5), bucket=8) is lay  # memoized


def test_ragged_rows_plan_requires_mask():
    with pytest.raises(ValueError, match="masked"):
        ip.plan_index_op((64, 16), jnp.float32, 32, "ragged_rows")
    plan = ip.plan_index_op((64, 16), jnp.float32, 32, "ragged_rows", masked=True)
    assert plan.semantics == "ragged_rows"
    assert plan is ip.plan_index_op(
        (64, 16), jnp.float32, 32, "ragged_rows", masked=True
    )


# -- packed prefill vs per-prompt prefill ------------------------------------


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_config("qwen2-7b-smoke")
    params = tf.init_params(KEY, cfg)
    return cfg, params


def test_prefill_ragged_pack_unpack_oracle(qwen):
    cfg, params = qwen
    assert tf.supports_ragged(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (5, 9)]
    lay = ip.ragged_layout(tuple(len(p) for p in prompts), bucket=8)
    toks = np.zeros((1, lay.t_pad), np.int32)
    for j, p in enumerate(prompts):
        toks[0, lay.indptr[j] : lay.indptr[j] + len(p)] = p
    last = np.asarray(lay.last_ix, np.int32)
    logits, packed = tf.prefill_ragged(
        params, cfg, jnp.asarray(toks), jnp.asarray(lay.seg_ids),
        jnp.asarray(lay.positions), jnp.asarray(last),
    )
    s_max = 32
    cache = _write_ragged(tf.init_cache(cfg, 2, s_max), packed, [0, 1], lay, s_max)
    for j, p in enumerate(prompts):
        ref_logits, ref_cache = tf.prefill(params, cfg, jnp.asarray(p)[None])
        # per-sequence last-token logits agree with the unpacked prompt
        assert int(jnp.argmax(logits[j])) == int(jnp.argmax(ref_logits[0]))
        assert jnp.allclose(
            logits[j].astype(jnp.float32),
            ref_logits[0].astype(jnp.float32),
            atol=2e-2,
        )
        # unpacked KV rows [0, len) match; the ring tail is zero-filled
        for got, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
            rows = got[:, j, :, : len(p)].astype(jnp.float32)
            want = ref[:, 0, :, : len(p)].astype(jnp.float32)
            assert jnp.allclose(rows, want, atol=2e-2)
            tail = got[:, j, :, len(p) :].astype(jnp.float32)
            assert jnp.all(tail == 0)


def test_decode_step_per_slot_positions(qwen):
    cfg, params = qwen
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (6, 13)]
    s_max = 32
    cache = tf.init_cache(cfg, 2, s_max)
    for j, p in enumerate(prompts):
        _, c1 = tf.prefill(params, cfg, jnp.asarray(p)[None])
        cache = _write_slot(cache, c1, j, s_max)
    toks = jnp.asarray([3, 7], jnp.int32)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    logits, _ = tf.decode_step(params, cfg, toks, cache, pos)
    for j, p in enumerate(prompts):
        ring1 = _write_slot(tf.init_cache(cfg, 1, s_max),
                            tf.prefill(params, cfg, jnp.asarray(p)[None])[1],
                            0, s_max)
        ref, _ = tf.decode_step(
            params, cfg, toks[j : j + 1], ring1, jnp.int32(len(p))
        )
        assert jnp.allclose(
            logits[j].astype(jnp.float32), ref[0].astype(jnp.float32), atol=2e-2
        ), f"slot {j} decoded against the wrong per-slot length"


# -- the engine --------------------------------------------------------------


def _reference_greedy(cfg, params, prompt, max_new, s_max):
    """Clean single-request greedy decode: unpadded prefill + scalar-pos
    stepwise decode (the pre-engine model path)."""
    logits, c1 = tf.prefill(params, cfg, jnp.asarray(prompt)[None])
    ring = _write_slot(tf.init_cache(cfg, 1, s_max), c1, 0, s_max)
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(out) < max_new and pos < s_max:
        lg, ring = tf.decode_step(
            params, cfg, jnp.asarray([out[-1]], np.int32), ring, jnp.int32(pos)
        )
        pos += 1
        out.append(int(jnp.argmax(lg[0])))
    return out


@pytest.fixture(scope="module")
def served(qwen):
    """Shared prompts + per-request reference outputs."""
    cfg, params = qwen
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in (7, 19, 3, 12)]
    refs = [_reference_greedy(cfg, params, p, 5, 64) for p in prompts]
    return prompts, refs


@pytest.mark.parametrize(
    "mode,chunk", [("ragged", None), ("ragged", 8), ("bucket", None)],
    ids=["ragged", "ragged_chunked", "bucket"],
)
def test_engine_staggered_traffic(qwen, served, mode, chunk):
    cfg, params = qwen
    prompts, refs = served
    engine = Engine(cfg, params, batch_slots=2, s_max=64, prompt_bucket=16,
                    prefill_mode=mode, chunk=chunk)
    reqs = [Request(rid=i, prompt=p, max_new=5) for i, p in enumerate(prompts)]
    done = engine.run(reqs)
    assert len(done) == len(prompts)  # slot reuse: 4 requests through 2 slots
    assert all(r.done and r.slot is None for r in done)
    assert all(len(r.out) == 5 for r in done)
    if mode == "ragged":
        # staggered admissions at different per-slot positions reproduce
        # the clean per-request greedy decode exactly
        for r in done:
            assert r.out == refs[r.rid], (mode, chunk, r.rid)


def test_engine_admit_returns_slot(qwen):
    cfg, params = qwen
    engine = Engine(cfg, params, batch_slots=2, s_max=64, prompt_bucket=16)
    p = np.arange(4, dtype=np.int32) % cfg.vocab
    r0, r1 = Request(rid=0, prompt=p), Request(rid=1, prompt=p)
    s0 = engine.admit(r0)
    s1 = engine.admit(r1)
    assert sorted([s0, s1]) == [0, 1]
    assert r0.slot == s0 and r1.slot == s1
    assert engine.admit(Request(rid=2, prompt=p)) is None  # full
    assert engine.free_slots() == []


def test_engine_admission_validation(qwen):
    cfg, params = qwen
    engine = Engine(cfg, params, batch_slots=2, s_max=32, prompt_bucket=16)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.admit(Request(rid=0, prompt=np.zeros(0, np.int32)))
    with pytest.raises(ValueError, match="does not fit"):
        engine.admit(Request(rid=1, prompt=np.zeros(40, np.int32)))
    with pytest.raises(ValueError, match="ragged"):
        Engine(cfg, params, prefill_mode="bucket", chunk=8)


def test_engine_step_returns_finished(qwen):
    cfg, params = qwen
    engine = Engine(cfg, params, batch_slots=2, s_max=64, prompt_bucket=16)
    p = (np.arange(5) % cfg.vocab).astype(np.int32)
    fast = Request(rid=0, prompt=p, max_new=2)
    slow = Request(rid=1, prompt=p, max_new=4)
    engine.admit_batch([fast, slow])  # each already holds its first token
    first = engine.step()
    assert first == [fast]  # retires at max_new=2, slot freed
    assert engine.live[fast.slot if fast.slot is not None else 0] is None
    rest = []
    for _ in range(4):
        rest.extend(engine.step())
    assert rest == [slow]


def test_engine_bucket_mode_for_non_ragged_arch():
    cfg = configs.get_config("xlstm-125m-smoke")
    assert not tf.supports_ragged(cfg)
    params = tf.init_params(KEY, cfg)
    engine = Engine(cfg, params, batch_slots=2, s_max=64, prompt_bucket=16)
    assert engine.mode == "bucket"  # auto-fallback
    with pytest.raises(ValueError, match="attention-only"):
        Engine(cfg, params, prefill_mode="ragged")


def test_serve_flags_tpu_gated(monkeypatch):
    """The XLA inference preset must never reach a non-TPU backend:
    unknown flags abort XLA at startup.  Explicit platform env decides;
    user-set flags always win over the preset."""
    from repro.launch import xla_flags

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    assert xla_flags.apply_serve_flags(force=True) is None

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_tpu_rwb_fusion=true")
    merged = xla_flags.apply_serve_flags(force=True)
    assert "--xla_tpu_scoped_vmem_limit_kib=28672" in merged
    assert merged.count("rwb_fusion") == 1  # the user's value survives

    # opt-in: without force, REPRO_SERVE_FLAGS gates the whole preset
    monkeypatch.delenv("REPRO_SERVE_FLAGS", raising=False)
    assert xla_flags.apply_serve_flags() is None
