"""The serving MoE layer over one chip's share of the experts
(`models.moe.moe_held`) and its grouped SwiGLU kernel
(`kernels.grouped_matmul`), on seeded random weights at a small size.

* the shares add up: eight layers holding two experts each of sixteen,
  plus the shared expert once, give the uncut layer;
* nothing is dropped, however the router sends the tokens;
* a request's logits do not depend on what else is in the batch;
* the engine's ragged admission, chunk wave and decode agree with a plain
  float32 reference of the same weights;
* the grouped kernel agrees with ``jnp.einsum`` for empty, uneven and
  full groups;
* the dry run's decode keeps the mesh MoE layer: its expert all-to-all
  and its FLOPs.
"""

import os
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import MoEConfig
from repro.core import index_plan as ip
from repro.kernels import grouped_matmul as gm
from repro.kernels import ref
from repro.models import common, moe
from repro.models import transformer as tf
from repro.serve.engine import Engine, Request
from repro.utils import spans

E, K, F, N_SHARED = 16, 4, 48, 1


def moe_cfg(held=None, dtype="float32"):
    """A tiny DeepSeekMoE-shaped model: a dense first layer, then MoE
    layers of 16 routed experts (top-4, gates not renormalised) and one
    shared expert, of which this chip holds ``held``."""
    base = configs.get_config("deepseek-moe-16b-smoke")
    return base.with_(
        n_layers=3, explicit_plan=((("attn_dense",), 1), (("attn_moe",), 2)),
        moe=MoEConfig(n_experts=E, top_k=K, d_expert=F, n_shared=N_SHARED,
                      normalize_gates=False, held=held),
        dtype=dtype,
    )


def dense_reference(p, cfg, h2):
    """The routed part of the layer computed densely in float32 over the
    held experts: each expert's SwiGLU output weighted by its gate where it
    is among the token's top-k, zero elsewhere."""
    first, n = cfg.moe.held_range()
    with jax.default_matmul_precision("highest"):
        h = h2.astype(jnp.float32)
        probs = jax.nn.softmax(h @ p["w_router"], axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.moe.top_k)
        y = jnp.zeros_like(h)
        for j in range(n):
            w = jnp.where(idx == first + j, gates, 0.0).sum(-1)
            g = h @ p["w_gate"][j].astype(jnp.float32)
            u = h @ p["w_up"][j].astype(jnp.float32)
            y = y + w[:, None] * ((jax.nn.silu(g) * u) @ p["w_down"][j].astype(jnp.float32))
    return y


def share(p, first, n):
    """The parameters a chip holding experts ``first .. first + n - 1``
    keeps: the whole router, its experts, no shared expert (counted once
    by the caller)."""
    out = {k: v for k, v in p.items() if k != "shared"}
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][first:first + n]
    return out


@pytest.mark.parametrize("dispatch", ["oracle", "interpret"])
def test_shares_add_up_to_the_uncut_layer(dispatch, monkeypatch):
    if dispatch == "interpret":
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cfg = moe_cfg()
    p = moe.moe_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model), jnp.float32)
    h2 = common.apply_norm(cfg.norm, p["norm"], x).reshape(-1, cfg.d_model)
    shared = moe.mlp.ffn_only(p["shared"], cfg, h2).reshape(x.shape)
    total = x + shared
    for first in range(0, E, 2):
        part = cfg.with_(moe=replace(cfg.moe, held=(first, 2)))
        y, _ = moe.moe_held(share(p, first, 2), part, x)
        total = total + (y - x)
    whole, _ = moe.moe_held(p, cfg, x)
    scale = float(jnp.abs(whole).max())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-6 * scale, rtol=0)
    want = x + shared + dense_reference(p, cfg, h2).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("dispatch", ["oracle", "interpret"])
def test_no_assignment_is_dropped_when_every_token_picks_the_same_experts(
        dispatch, monkeypatch):
    if dispatch == "interpret":
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cfg = moe_cfg(held=(4, 8))
    p = moe.moe_init(jax.random.PRNGKey(3), cfg)
    d = cfg.d_model
    # feature 0 is large and positive for every token and is all the router
    # reads: every token ranks experts 4..7 (held) first, in the same order
    w = jnp.zeros((d, E), jnp.float32).at[0, 4:8].set(jnp.asarray([4.0, 3.5, 3.0, 2.5]))
    p = dict(p, w_router=w)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 96, d), jnp.float32).at[..., 0].set(30.0)
    y, counts = moe.moe_held(p, cfg, x)
    assert int(counts[0]) == 96 * K  # every assignment lands on a held expert
    h2 = common.apply_norm(cfg.norm, p["norm"], x).reshape(-1, d)
    want = x + (moe.mlp.ffn_only(p["shared"], cfg, h2) + dense_reference(p, cfg, h2)).reshape(
        x.shape)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5 * scale, rtol=0)
    # the capacity-bucketed dispatch the serving path used drops most of them
    full = dict(p, w_gate=jnp.zeros((E, d, F)).at[4:12].set(p["w_gate"]),
                w_up=jnp.zeros((E, d, F)).at[4:12].set(p["w_up"]),
                w_down=jnp.zeros((E, F, d)).at[4:12].set(p["w_down"]))
    dropped, _ = moe.moe_sort(full, moe_cfg(), x)
    assert float(jnp.abs(dropped - want).max()) > 100 * 2e-5 * scale


# -- a whole model -------------------------------------------------------------


def model(held=(4, 8), seed=0):
    cfg = moe_cfg(held=held)
    return cfg, tf.init_params(jax.random.PRNGKey(seed), cfg)


def test_a_requests_logits_do_not_depend_on_its_batch_peers():
    cfg, params = model()
    rng = np.random.default_rng(5)
    a = rng.integers(0, cfg.vocab, 20).astype(np.int32)
    peers = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (15, 21)]

    def admit(prompts):  # packed to 64 rows either way, the request first
        lay = ip.ragged_layout(tuple(len(q) for q in prompts), 64)
        toks = np.zeros((1, lay.t_pad), np.int32)
        for j, q in enumerate(prompts):
            toks[0, lay.indptr[j]:lay.indptr[j] + len(q)] = q
        last = np.zeros((4,), np.int32)
        last[:len(prompts)] = lay.last_ix
        assert lay.t_pad == 64 and (lay.seg_ids < 0).any()  # padding rows present
        lg, _, counts = tf.prefill_ragged(params, cfg, jnp.asarray(toks),
                                          jnp.asarray(lay.seg_ids),
                                          jnp.asarray(lay.positions), jnp.asarray(last))
        return np.asarray(lg[0]), int(counts[0])

    alone, real_alone = admit([a])
    batched, real_batched = admit([a] + peers)
    np.testing.assert_array_equal(alone, batched)
    assert real_batched > real_alone

    # a chunk wave and a decode step: the request in slot 0, the others idle
    # (alone) or live (batched); idle slots hold tokens that never route
    cache = tf.init_cache(cfg, 4, 64)
    chunk = np.zeros((4, 16), np.int32)
    chunk[0] = a[:16]
    pos = jnp.zeros((4,), jnp.int32)
    last = jnp.asarray([15, 9, 15, 3], jnp.int32)

    def wave(active):
        toks = chunk.copy()
        toks[1:] = rng_peer
        lg, c, _ = tf.prefill_chunk(params, cfg, jnp.asarray(toks), cache, pos,
                                    jnp.asarray(active), last)
        return np.asarray(lg[0]), c

    rng_peer = rng.integers(0, cfg.vocab, (3, 16)).astype(np.int32)
    lone_lg, lone_cache = wave([True, False, False, False])
    busy_lg, busy_cache = wave([True, True, True, True])
    np.testing.assert_array_equal(lone_lg, busy_lg)

    def step(cache_, toks):
        lg, _, _ = tf.decode_step(params, cfg, jnp.asarray(toks), cache_,
                                  jnp.asarray([16, 10, 16, 4], jnp.int32),
                                  valid=jnp.asarray(toks) >= 0)
        return np.asarray(lg[0])

    np.testing.assert_array_equal(step(lone_cache, [7, -1, -1, -1]),
                                  step(busy_cache, [7, 11, 12, 13]))


def reference_logits(params, cfg, seq):
    """Plain float32 forward of the tiny model over ``seq``: RMSNorm,
    causal multi-head attention with rotate-half RoPE, the dense MLP in
    layer 0 and in the MoE layers the shared expert plus the held experts
    computed densely (:func:`dense_reference`); logits at every position."""
    hd, nh = cfg.head_dim_resolved, cfg.n_heads
    t = len(seq)
    pos = jnp.arange(t)

    def rms(x, p):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * p["scale"]

    def rope(z):
        freqs = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None, None] * freqs
        z1, z2 = z[..., :hd // 2], z[..., hd // 2:]
        return jnp.concatenate([z1 * jnp.cos(ang) - z2 * jnp.sin(ang),
                                z2 * jnp.cos(ang) + z1 * jnp.sin(ang)], -1)

    def swiglu(p, h):
        return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][jnp.asarray(seq)]
        for stage, (unit, count) in zip(params["stages"], cfg.decoder_plan()):
            for i in range(count):
                blk = jax.tree.map(lambda a: a[i], stage["b0"])
                a = blk["attn"]
                qkv = rms(x, a["norm"]) @ a["w_qkv"]
                q = rope(qkv[:, :nh * hd].reshape(t, nh, hd))
                k = rope(qkv[:, nh * hd:2 * nh * hd].reshape(t, nh, hd))
                v = qkv[:, 2 * nh * hd:].reshape(t, nh, hd)
                s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
                s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(t, -1)
                x = x + o @ a["w_o"]
                if unit[0] == "attn_moe":
                    h = rms(x, blk["moe"]["norm"])
                    x = x + swiglu(blk["moe"]["shared"], h) + dense_reference(blk["moe"], cfg, h)
                else:
                    x = x + swiglu(blk["mlp"], rms(x, blk["mlp"]["norm"]))
        return rms(x, params["final_norm"]) @ params["lm_head"]


class Recording(Engine):
    """The engine, keeping the logits row behind every token it emits."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows, self._wave, self._lg = {}, None, None

    def admit_batch(self, reqs):
        self._wave = list(reqs)
        try:
            return super().admit_batch(reqs)
        finally:
            self._wave = None

    def _pull(self, logits, counts, sp):
        self._lg = super()._pull(logits, counts, sp)
        return self._lg

    def _emit(self, slot, token):
        r = self.live[slot]
        row = self._wave.index(r) if self._wave is not None else slot
        self.rows.setdefault(r.rid, []).append(np.array(self._lg[row]))
        super()._emit(slot, token)


def test_engine_agrees_with_a_plain_float32_reference():
    cfg, params = model(held=(0, 8), seed=1)
    eng = Recording(cfg, params, batch_slots=4, s_max=128, prompt_bucket=32,
                    prefill_mode="ragged", chunk=32)
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n).astype(np.int32), max_new=6)
            for i, n in enumerate((9, 70, 30, 45, 5))]  # 70 and 45: chunk waves
    eng.run(reqs)
    spans_seen = {name for name, *_ in spans.spans()}
    assert {"engine.admit", "engine.chunk_wave", "engine.decode"} <= spans_seen
    for r in reqs:
        assert r.done and len(eng.rows[r.rid]) == r.max_new
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        want = np.asarray(reference_logits(params, cfg, seq))[len(r.prompt) - 1:]
        got = np.stack(eng.rows[r.rid])
        # float32 throughout, the same weights: the program and the reference
        # differ only in summation order (attention blocks, the kernel's tile
        # matmuls), a few float32 ulps of the logits' scale; a top-k flip
        # between them would move a logit by a whole expert's share, far more
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=0)


# -- the grouped kernel ----------------------------------------------------------


@pytest.mark.parametrize("sizes", [(0, 0, 40, 0), (5, 17, 1, 33), (64, 0, 0, 0)],
                         ids=["empty", "uneven", "full"])
def test_grouped_swiglu_matches_einsum(sizes, pallas_interpret):
    g, d, f, tm = len(sizes), 32, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    wg = jax.random.normal(ks[0], (g, d, f), jnp.float32) / d ** 0.5
    wu = jax.random.normal(ks[1], (g, d, f), jnp.float32) / d ** 0.5
    wd = jax.random.normal(ks[2], (g, f, d), jnp.float32) / f ** 0.5
    n_tiles = -(-sum(sizes) // tm) + g
    starts, tile_group, n_used = gm.tile_groups(jnp.asarray(sizes, jnp.int32), tm, n_tiles)
    x = jax.random.normal(ks[3], (n_tiles * tm, d), jnp.float32)
    row_group = np.full(n_tiles * tm, -1)
    for j, (s0, n) in enumerate(zip(np.asarray(starts), sizes)):
        row_group[s0:s0 + n] = j
    real = row_group >= 0
    assert int(n_used) == sum(-(-n // tm) for n in sizes)
    with jax.default_matmul_precision("highest"):
        w = row_group.clip(0)
        hid = jax.nn.silu(jnp.einsum("rd,rdf->rf", x, wg[w])) * jnp.einsum("rd,rdf->rf", x, wu[w])
        want = np.asarray(jnp.einsum("rf,rfd->rd", hid, wd[w]))
        kern = gm.grouped_swiglu(x, wg, wu, wd, tile_group, n_used, tm=tm)
        oracle = ref.grouped_swiglu(x, wg, wu, wd, tile_group, n_used, tm)
    for got in (kern, oracle):
        np.testing.assert_allclose(np.asarray(got)[real], want[real], atol=1e-4, rtol=1e-4)
    assert not np.asarray(oracle)[int(n_used) * tm:].any()


def test_dry_run_decode_keeps_the_expert_all_to_all_and_its_flops():
    """The dry run lowers the decode step on a mesh with the mesh MoE layer
    (`moe.moe_apply`), not the serving engine's held one: on 16 host
    devices as a 16-way model axis the expert-sharded dispatch partitions
    into all-to-alls, and a device's FLOPs stay under the step's whole
    useful work (`roofline.model_flops`).  The held layer's worst-case
    buffer through the grouped oracle would read several times over it.
    Its own process, so this one keeps its single-device view."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from repro import configs
from repro.configs.base import ShapeCell
from repro.launch import dryrun
from repro.utils import roofline
cfg = configs.get_config("deepseek-moe-16b-smoke")
mesh = jax.make_mesh((1, 16), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cell = ShapeCell("decode_small", 256, 32, "decode")
compiled = dryrun.lower_cell(cfg, cell, mesh).compile()
cost = dryrun._cost_dict(compiled)
print("ALL_TO_ALL", compiled.as_text().count("all-to-all("))
print("FLOPS", cost["flops"], roofline.model_flops(cfg, cell))
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    lines = dict(line.split(" ", 1) for line in r.stdout.splitlines() if " " in line)
    assert "FLOPS" in lines, r.stderr[-2000:]
    assert int(lines["ALL_TO_ALL"]) >= 1
    flops, model = map(float, lines["FLOPS"].split())
    assert 0 < flops <= model
